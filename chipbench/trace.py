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
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

import numpy as np

TRACE_S = 0.5             # seconds of serving the trace covers
TOP = 10                  # entries in each list of the breakdown
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."


class Events:
    """One line of a trace: names and [start, end) in ns."""

    def __init__(self, names: List[str], start, dur):
        self.names = names
        self.start = np.asarray(start, np.float64)
        self.end = self.start + np.asarray(dur, np.float64)

    def __len__(self) -> int:
        return len(self.names)


def read_xplane(path: str) -> Tuple[Dict[str, Dict[str, Events]],
                                    Events]:
    """Device lines per TPU chip (``{plane: {line: Events}}``) and the
    harness's host spans, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_file(path))


def read_profile(prof) -> Tuple[Dict[str, Dict[str, Events]], Events]:
    """``read_xplane`` for a loaded ``jax.profiler.ProfileData``."""
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
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        t = time.perf_counter()
        self.file_bytes = os.path.getsize(paths[0])
        devices, spans = read_xplane(paths[0])
        shutil.rmtree(self.out_dir, ignore_errors=True)
        out = Summary(devices, spans, self.t1 - self.t0, waves, n_devices)
        self.reduce_s = time.perf_counter() - t
        return out
