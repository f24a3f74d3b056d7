"""Profiler trace of a few steady seconds, and its reduction to the
numbers the per-layer metrics read.

The harness flushes the pipeline, starts the JAX profiler, serves for
``TRACE_S`` seconds, flushes again and stops it, so the trace holds
exactly the blocks dispatched inside it.  The reduction reads the
``.xplane.pb`` file with ``jax.profiler.ProfileData`` and keeps, per chip:

* busy time — the union of the intervals in which an XLA operation ran;
* device seconds per operation name and per program (XLA module);
* idle gaps — the stretches with no operation on the first chip, each
  named by the harness span (``chipbench.*``) that covers most of it.

``Scoped`` reads the same file for what the program itself names: device
seconds per named scope of the block program (``engine.run_wave_on``),
from each operation's ``tf_op`` (``chipbench/xspace.py``), and idle gaps
named by the innermost host span, the program's ``repro.*`` stages inside
the harness's ``chipbench.*`` spans.
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

import numpy as np

from chipbench import xspace

TRACE_S = 0.5             # seconds of serving the trace covers
TOP = 10                  # entries in each list of the breakdown
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
# the named scopes of ``engine.run_wave_on``, outermost first
SCOPES = ("read_phase", "commit_loop", "newest", "validate", "install",
          "bump_sid", "push_bounds", "record", "message_stats")
SPAN_PREFIXES = ("chipbench.", "repro.")
# block programs, as ``metrics/block_ms_per_wave.py`` names them
BLOCK_PROGRAMS = ("jit__scan_block(", "jit_node_fn(")


class Events:
    """One line of a trace: names and [start, end) in ns."""

    def __init__(self, names: List[str], start, dur):
        self.names = names
        self.start = np.asarray(start, np.float64)
        self.end = self.start + np.asarray(dur, np.float64)

    def __len__(self) -> int:
        return len(self.names)


def read_profile(prof) -> Tuple[Dict[str, Dict[str, Events]], Events]:
    """Device lines per TPU chip (``{plane: {line: Events}}``) and the
    harness's host spans, from a loaded ``jax.profiler.ProfileData``."""
    devices: Dict[str, Dict[str, Events]] = {}
    spans = ([], [], [])
    for plane in prof.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and \
                name[len("/device:TPU:"):].isdigit():
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    names, start, dur = [], [], []
                    for e in line.events:
                        names.append(e.name)
                        start.append(e.start_ns)
                        dur.append(e.duration_ns)
                    lines[line.name] = Events(names, start, dur)
            devices[name] = lines
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n.startswith(SPAN_PREFIX):
                        spans[0].append(n[len(SPAN_PREFIX):])
                        spans[1].append(e.start_ns)
                        spans[2].append(e.duration_ns)
    return devices, Events(*spans)


def union(ev: Events) -> np.ndarray:
    """Merged, sorted [start, end) intervals covering every event."""
    if not len(ev):
        return np.zeros((0, 2))
    order = np.argsort(ev.start, kind="stable")
    s, e = ev.start[order], ev.end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    starts = s[new]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(s) - 1]]
    return np.stack([starts, ends], axis=1)


def per_name(ev: Events) -> Dict[str, float]:
    """Device seconds per event name."""
    out: Dict[str, float] = defaultdict(float)
    for n, d in zip(ev.names, ev.end - ev.start):
        out[n] += d * 1e-9
    return out


class Summary:
    """What a trace says, averaged over the chips used."""

    def __init__(self, devices: Dict[str, Dict[str, Events]], spans: Events,
                 window_s: float, waves: int, n_devices: int):
        chips = sorted(devices, key=lambda p: int(p.rsplit(":", 1)[1]))
        chips = chips[:n_devices]
        if not chips:
            raise ValueError("the trace holds no TPU device plane")
        self.n = len(chips)
        self.window_s = float(window_s)
        self.waves = int(waves)
        empty = Events([], [], [])
        ops = [devices[c].get(OPS_LINE, empty) for c in chips]
        mods = [devices[c].get(MODULES_LINE, empty) for c in chips]
        busy = [union(o) for o in ops]
        self.busy_s = float(np.mean([(b[:, 1] - b[:, 0]).sum() * 1e-9
                                     for b in busy]))
        self.ops = self._mean([per_name(o) for o in ops])
        self.modules = self._mean([per_name(m) for m in mods])
        self.gaps = self._gaps(busy[0], spans)

    def _mean(self, dicts: Iterable[Dict[str, float]]) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for d in dicts:
            for k, v in d.items():
                out[k] += v / self.n
        return dict(out)

    @staticmethod
    def _gaps(busy: np.ndarray, spans: Events) -> Dict[str, float]:
        """Idle seconds between device operations on one chip, each gap
        named by the harness span that overlaps it most."""
        out: Dict[str, float] = defaultdict(float)
        if len(busy) < 2:
            return dict(out)
        g0, g1 = busy[:-1, 1], busy[1:, 0]
        for a, b in zip(g0, g1):
            if b <= a:
                continue
            name = "no harness span"
            if len(spans):
                ov = np.minimum(spans.end, b) - np.maximum(spans.start, a)
                i = int(np.argmax(ov))
                if ov[i] > 0:
                    name = spans.names[i]
            out[name] += (b - a) * 1e-9
        return dict(out)

    def op_s(self, match) -> float:
        """Device seconds of the operations whose name ``match`` accepts."""
        return sum(v for k, v in self.ops.items() if match(k))

    def module_s(self, names: Iterable[str]) -> float:
        """Device seconds of the programs whose name starts with one of
        ``names``."""
        names = tuple(names)
        return sum(v for k, v in self.modules.items()
                   if k.startswith(names))

    def breakdown(self) -> dict:
        """The operations that took most device time (named up to their
        HLO ``=``) and the idle gaps by harness span, in seconds."""
        top = lambda d: [[k.split(" = ")[0], v] for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


class Tracer:
    """Start and stop the JAX profiler into ``out_dir``, then reduce."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t0 = self.t1 = 0.0

    def start(self) -> None:
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, n_devices: int, waves: int) -> Summary:
        """The trace's ``Summary``; the file's bytes stay in ``raw``."""
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        t = time.perf_counter()
        with open(paths[0], "rb") as f:
            self.raw = f.read()
        self.file_bytes = len(self.raw)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        from jax.profiler import ProfileData
        devices, spans = read_profile(
            ProfileData.from_serialized_xspace(self.raw))
        out = Summary(devices, spans, self.t1 - self.t0, waves, n_devices)
        self.reduce_s = time.perf_counter() - t
        return out


# ------------------------------------------------- what the program names
def scope_of(tf_op: str) -> str:
    """The named scopes on an operation's name stack, joined by ``/``
    (``commit_loop/install``); empty where it carries none."""
    return "/".join(c for c in tf_op.split("/") if c in SCOPES)


def host_spans(prof) -> Events:
    """The harness's and the program's spans, under their full names."""
    names, start, dur = [], [], []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    names.append(e.name)
                    start.append(e.start_ns)
                    dur.append(e.duration_ns)
    return Events(names, start, dur)


def leaves(ev: Events) -> np.ndarray:
    """True for the events that hold no other event of their line, so a
    ``while`` or ``conditional`` does not count its body twice."""
    order = np.lexsort((-ev.end, ev.start))
    inner = np.zeros(len(ev), bool)
    s, e = ev.start[order], ev.end[order]
    inner[order[:-1]] = s[1:] < e[:-1]
    return ~inner


def innermost_gaps(busy: np.ndarray, spans: Events) -> Dict[str, float]:
    """Idle seconds between device operations, each stretch put down to
    the innermost host span that covers it (the one that started last)."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in zip(busy[:-1, 1], busy[1:, 0]):
        if b <= a:
            continue
        hit = np.nonzero((spans.start < b) & (spans.end > a))[0]
        cuts = np.unique(np.clip(np.r_[a, b, spans.start[hit],
                                       spans.end[hit]], a, b))
        for x, y in zip(cuts[:-1], cuts[1:]):
            cover = hit[(spans.start[hit] <= x) & (spans.end[hit] >= y)]
            name = "no span"
            if len(cover):
                # latest start, then shortest: the innermost
                i = cover[np.lexsort((spans.end[cover],
                                      -spans.start[cover]))[0]]
                name = spans.names[i]
            out[name] += (y - x) * 1e-9
    return dict(out)


class Scoped:
    """Device seconds by named scope and idle seconds by innermost span,
    from one trace, on the first chip; beside them, busy time and idle
    seconds as the harness reads them (``summary``, its ``Summary`` of the
    same trace)."""

    def __init__(self, devices: Dict[str, Dict[str, Events]],
                 spans: Events, tf_op: Dict[str, str], waves: int,
                 summary: Summary):
        chip = min(devices, key=lambda p: int(p.rsplit(":", 1)[1]))
        empty = Events([], [], [])
        ops = devices[chip].get(OPS_LINE, empty)
        mods = devices[chip].get(MODULES_LINE, empty)
        self.waves = int(waves)
        self.scope_of = {n: scope_of(tf_op.get(n, "")) for n in
                         set(ops.names)}
        leaf = leaves(ops) if len(ops) else np.zeros(0, bool)
        self.scope_s: Dict[str, float] = defaultdict(float)
        self.unscoped: Dict[str, float] = defaultdict(float)
        for n, d, is_leaf in zip(ops.names, ops.end - ops.start, leaf):
            if is_leaf:
                self.scope_s[self.scope_of[n]] += d * 1e-9
                if not self.scope_of[n]:
                    self.unscoped[n] += d * 1e-9
        self.scope_s = dict(self.scope_s)
        self.leaf_s = sum(self.scope_s.values())
        self.busy_s = summary.busy_s
        self.block_s = sum(v for k, v in per_name(mods).items()
                           if k.startswith(BLOCK_PROGRAMS))
        self.ops = per_name(ops)
        self.gaps = innermost_gaps(union(ops), spans)
        self.harness_gaps = summary.gaps

    def scoped_s(self, prefix: str = "") -> float:
        """Leaf-op seconds under scopes that start with ``prefix`` (every
        scope where it is empty)."""
        return sum(v for k, v in self.scope_s.items()
                   if k and k.startswith(prefix))

    def breakdown(self, top: int = TOP) -> dict:
        """The operations that took most device time, each with its scope,
        the leaf operations that took most under no scope, and the idle
        gaps by innermost span and by harness span."""
        def label(name):
            short = name.split(" = ")[0]
            scope = self.scope_of.get(name)
            return f"{short} [{scope}]" if scope else short
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[label(k), v] for k, v in rank(self.ops)],
                "unscoped_ops": [[k.split(" = ")[0], v]
                                 for k, v in rank(self.unscoped)],
                "idle_gaps": rank(self.gaps),
                "idle_gaps_by_harness_span": rank(self.harness_gaps)}

    def summary(self) -> dict:
        return {"busy_s": self.busy_s, "leaf_s": self.leaf_s,
                "block_s": self.block_s, "waves": self.waves,
                "scope_s": self.scope_s,
                "claimed_share": (self.scoped_s() / self.block_s
                                  if self.block_s else None),
                **self.breakdown()}


def reduce_scoped(raw: bytes, waves: int) -> Scoped:
    """``Scoped`` of one serialized trace (``Tracer.raw``)."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(raw)
    devices, spans = read_profile(prof)
    return Scoped(devices, host_spans(prof), xspace.tf_ops(raw), waves,
                  Summary(devices, spans, 1.0, waves, 1))
