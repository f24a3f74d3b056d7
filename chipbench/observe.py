"""Run one cell and read what the program itself records of it.

    python3 chipbench/observe.py --workload smallbank.open --seed 7 \
        --seconds 10 --trace 1

The cell runs exactly as ``run.py`` runs it (``harness.run_cell``); this
script only looks on.  Besides the result line it reports:

* the window's host seconds and counts per serving stage, from the
  service's stage timers (``repro.service.obs``: ``stage_s``, ``stage_n``);
* for the window's committed requests, their commit latency split into
  five parts on one clock, from the harness's due and send times and the
  requests' own stamps (``TxnRequest.t_submit``, ``t_dispatch``,
  ``t_ack``): generator lag, admission, queue wait, block turn, and the
  rest of the tick that routed the commit;
* with ``--trace 1``, device seconds per named scope of the block program
  (``engine.run_wave_on``) from each operation's ``tf_op``
  (``chipbench/xspace.py``), the operations that took most time with their
  scope, and the idle gaps named by the innermost host span that covers
  them, the program's ``repro.*`` stages inside the harness's
  ``chipbench.*`` spans;
* the per-layer metrics of ``chipbench/metrics/`` that read these
  (``queue_wait_p95_ms``, ``block_turn_p95_ms``, ``route_us_per_txn``,
  ``admit_us_per_txn``, ``commit_loop_ms_per_wave``), given a context that
  holds them, and what one stage timer costs with tracing off and on.

The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
from collections import defaultdict
from types import SimpleNamespace
from typing import Dict
from unittest import mock

T_START = time.perf_counter()

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, trace, xspace  # noqa: E402

# the named scopes of ``engine.run_wave_on``, outermost first
SCOPES = ("read_phase", "commit_loop", "newest", "validate", "install",
          "bump_sid", "push_bounds", "record", "message_stats")
SPAN_PREFIXES = ("chipbench.", "repro.")
# block programs, as ``metrics/block_ms_per_wave.py`` names them
BLOCK_PROGRAMS = ("jit__scan_block(", "jit_node_fn(")
PARTS = ("gen_lag", "admit", "queue_wait", "block_turn", "tick_rest")
READERS = ("queue_wait_p95_ms", "block_turn_p95_ms", "route_us_per_txn",
           "admit_us_per_txn", "commit_loop_ms_per_wave")


# ------------------------------------------------------------ trace side
def scope_of(tf_op: str) -> str:
    """The named scopes on an operation's name stack, joined by ``/``
    (``commit_loop/install``); empty where it carries none."""
    return "/".join(c for c in tf_op.split("/") if c in SCOPES)


def host_spans(prof) -> trace.Events:
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
    return trace.Events(names, start, dur)


def leaves(ev: trace.Events) -> np.ndarray:
    """True for the events that hold no other event of their line, so a
    ``while`` or ``conditional`` does not count its body twice."""
    order = np.lexsort((-ev.end, ev.start))
    inner = np.zeros(len(ev), bool)
    s, e = ev.start[order], ev.end[order]
    inner[order[:-1]] = s[1:] < e[:-1]
    return ~inner


def innermost_gaps(busy: np.ndarray, spans: trace.Events
                   ) -> Dict[str, float]:
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
    seconds as the harness reads them (``summary``, its ``trace.Summary``
    of the same trace)."""

    def __init__(self, devices: Dict[str, Dict[str, trace.Events]],
                 spans: trace.Events, tf_op: Dict[str, str], waves: int,
                 summary: trace.Summary):
        chip = min(devices, key=lambda p: int(p.rsplit(":", 1)[1]))
        empty = trace.Events([], [], [])
        ops = devices[chip].get(trace.OPS_LINE, empty)
        mods = devices[chip].get(trace.MODULES_LINE, empty)
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
        self.block_s = sum(v for k, v in trace.per_name(mods).items()
                           if k.startswith(BLOCK_PROGRAMS))
        self.ops = trace.per_name(ops)
        self.gaps = innermost_gaps(trace.union(ops), spans)
        self.harness_gaps = summary.gaps

    def scoped_s(self, prefix: str = "") -> float:
        """Leaf-op seconds under scopes that start with ``prefix`` (every
        scope where it is empty)."""
        return sum(v for k, v in self.scope_s.items()
                   if k and k.startswith(prefix))

    def breakdown(self, top: int = trace.TOP) -> dict:
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


def reduce_trace(raw: bytes, waves: int) -> Scoped:
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(raw)
    devices, spans = trace.read_profile(prof)
    return Scoped(devices, host_spans(prof), xspace.tf_ops(raw), waves,
                  trace.Summary(devices, spans, 1.0, waves, 1))


# ---------------------------------------------------------- request side
def latency_parts(reqs, due, sent, tick_end) -> Dict[str, np.ndarray]:
    """Commit latency of each committed request, and its five parts on
    the harness's clock: they add up to it."""
    ok = [i for i, r in enumerate(reqs) if r.status == "committed"]
    col = lambda f: np.array([f(i) for i in ok], np.float64)
    ack = col(lambda i: tick_end[reqs[i].commit_tick])
    t_sub = col(lambda i: reqs[i].t_submit)
    t_dis = col(lambda i: reqs[i].t_dispatch)
    t_ack = col(lambda i: reqs[i].t_ack)
    d, s = col(lambda i: due[i]), col(lambda i: sent[i])
    return {"latency": ack - d, "gen_lag": s - d, "admit": t_sub - s,
            "queue_wait": t_dis - t_sub, "block_turn": t_ack - t_dis,
            "tick_rest": ack - t_ack}


def parts_summary(parts: Dict[str, np.ndarray]) -> dict:
    """Mean of each part and of the latency, in ms, and the parts of the
    request at the latency's 95th percentile (nearest rank)."""
    lat = parts["latency"]
    if not len(lat):
        return {}
    i95 = int(np.argsort(lat, kind="stable")[
        max(0, int(np.ceil(0.95 * len(lat))) - 1)])
    at95 = {p: float(parts[p][i95]) * 1e3 for p in PARTS}
    return {"n": len(lat),
            "mean_ms": {p: float(parts[p].mean()) * 1e3
                        for p in ("latency",) + PARTS},
            "p95_ms": {p: harness.percentile(parts[p], 95) * 1e3
                       for p in ("latency",) + PARTS},
            "at_p95_request_ms": dict(at95, latency=float(lat[i95]) * 1e3),
            "largest_at_p95": max(at95, key=at95.get)}


# ------------------------------------------------------------------ cost
def stage_cost(n: int = 20000, trace_dir: str = ""
               ) -> Dict[str, Dict[str, float]]:
    """Seconds one stage timer costs (``obs.stage``) and the submit timer
    (``obs.record`` after a ``perf_counter``), with no trace being taken
    and, where ``trace_dir`` is given, while one is."""
    import jax
    from repro.service import obs

    class Svc:
        def __init__(self):
            self.stage_s = defaultdict(float)
            self.stage_n = defaultdict(int)

    def per_call() -> Dict[str, float]:
        svc = Svc()
        t = time.perf_counter()
        for _ in range(n):
            with obs.stage(svc, "cost"):
                pass
        t1 = time.perf_counter()
        for _ in range(n):
            obs.record(svc, "submit", time.perf_counter())
        return {"stage_s": (t1 - t) / n,
                "submit_s": (time.perf_counter() - t1) / n}

    out = {"off": per_call()}
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            out["on"] = per_call()
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(trace_dir, ignore_errors=True)
    return out


# ------------------------------------------------------------------- run
def observe(cell_name: str, seed: int, seconds: float, trace_on: bool,
            root: str = ROOT, kernels=None) -> dict:
    """Run one cell through ``harness.run_cell`` and return the result
    line with what the program recorded (see the module's docstring)."""
    spec = harness.load_spec(root)
    cell = harness.find_cell(spec, cell_name)
    # what the run leaves in reach: the load, the context handed to the
    # metric readers, stage timers at each window mark, the trace file
    seen = SimpleNamespace(load=None, ctx=None, marks=[], raw=None)
    load_init, counters, reduce = (harness.Load.__init__, harness.counters,
                                   trace.Tracer.reduce)

    def on_load(self, *a, **kw):
        load_init(self, *a, **kw)
        seen.load = self

    def on_counters(svc):
        c = counters(svc)
        seen.marks.append((time.perf_counter(), dict(svc.stage_s),
                           dict(svc.stage_n)))
        return c

    def on_reduce(self, n_devices, waves):
        path, = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        with open(path, "rb") as f:
            seen.raw = f.read()
        return reduce(self, n_devices, waves)

    class Context(harness.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen.ctx = self

    with mock.patch.object(harness.Load, "__init__", on_load), \
            mock.patch.object(harness, "counters", on_counters), \
            mock.patch.object(harness, "Context", Context), \
            mock.patch.object(trace.Tracer, "reduce", on_reduce):
        out = harness.run_cell(cell, seed, seconds, trace_on, T_START, spec,
                               kernels=kernels, root=root)

    load, ctx = seen.load, seen.ctx
    (tw0, s0, n0), (_, s1, n1) = seen.marks[:2]
    stage_s = {k: v - s0.get(k, 0.0) for k, v in s1.items()}
    stage_n = {k: v - n0.get(k, 0) for k, v in n1.items()}
    # the window's requests, due in the ``seconds`` after its first mark
    # (the closed loop's due time is its send time)
    due = np.asarray(load.due)
    win = np.nonzero((due >= tw0) & (due < tw0 + seconds))[0]
    parts = latency_parts([load.reqs[i] for i in win], due[win],
                          np.asarray(load.sent)[win], load.s.tick_end)
    ctx.stage_s, ctx.stage_n = stage_s, stage_n
    ctx.queue_wait_s = parts["queue_wait"]
    ctx.block_turn_s = parts["block_turn"]
    ctx.scoped = None
    result = {"cell": cell_name, "seed": seed, "line": out["line"],
              "info": out["info"], "stage_s": stage_s, "stage_n": stage_n,
              "latency_parts": parts_summary(parts)}
    if seen.raw is not None:
        ctx.scoped = reduce_trace(seen.raw, ctx.trace.waves)
        result["scoped"] = ctx.scoped.summary()
        result["trace_bytes"] = len(seen.raw)
    result["readers"] = {name: harness.metric_reader(name, root)(ctx)
                         for name in READERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import run
    cell = harness.find_cell(harness.load_spec(), args.workload)
    run.require_chips(cell)
    run.enable_cache()
    result = observe(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    result["stage_cost"] = stage_cost(
        trace_dir=os.path.join(ROOT, ".chipbench_trace", "cost"))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
