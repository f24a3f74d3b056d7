"""Run one cell and read what the program itself records of it.

    python3 chipbench/observe.py --workload smallbank.open --seed 7 \
        --seconds 10 --trace 1

The cell runs exactly as ``run.py`` runs it (``harness.run_cell``), which
hands its per-layer readers what the program records; this script prints
that too.  Besides the result line it reports:

* the window's host seconds and counts per serving stage, from the
  service's stage timers (``repro.service.obs``: ``stage_s``, ``stage_n``);
* for the window's committed requests, their commit latency split into
  five parts on one clock, from the harness's due and send times and the
  requests' own stamps (``TxnRequest.t_submit``, ``t_dispatch``,
  ``t_ack``): generator lag, admission, queue wait, block turn, and the
  rest of the tick that routed the commit;
* with ``--trace 1``, device seconds per named scope of the block program
  (``engine.run_wave_on``) from each operation's ``tf_op``
  (``trace.Scoped``), the operations that took most time with their
  scope, and the idle gaps named by the innermost host span that covers
  them, the program's ``repro.*`` stages inside the harness's
  ``chipbench.*`` spans;
* the per-layer metrics of ``chipbench/metrics/`` that read these
  (``queue_wait_p95_ms``, ``block_turn_p95_ms``, ``route_us_per_txn``,
  ``admit_us_per_txn``, ``commit_loop_ms_per_wave``), whatever cells
  ``BENCHMARK.json`` lists them for, and what one stage timer costs with
  tracing off and on.

The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from collections import defaultdict
from typing import Dict

T_START = time.perf_counter()

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

READERS = ("queue_wait_p95_ms", "block_turn_p95_ms", "route_us_per_txn",
           "admit_us_per_txn", "commit_loop_ms_per_wave")


# ---------------------------------------------------------- request side
def parts_summary(parts: Dict[str, np.ndarray]) -> dict:
    """Mean of each part and of the latency, in ms, and the parts of the
    request at the latency's 95th percentile (nearest rank)."""
    lat = parts["latency"]
    if not len(lat):
        return {}
    i95 = int(np.argsort(lat, kind="stable")[
        max(0, int(np.ceil(0.95 * len(lat))) - 1)])
    at95 = {p: float(parts[p][i95]) * 1e3 for p in harness.PARTS}
    return {"n": len(lat),
            "mean_ms": {p: float(parts[p].mean()) * 1e3
                        for p in ("latency",) + harness.PARTS},
            "p95_ms": {p: harness.percentile(parts[p], 95) * 1e3
                       for p in ("latency",) + harness.PARTS},
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
    line with what the program recorded (see the module's docstring), as
    the run's own context (``ctx``) holds it."""
    spec = harness.load_spec(root)
    cell = harness.find_cell(spec, cell_name)
    out = harness.run_cell(cell, seed, seconds, trace_on, T_START, spec,
                           kernels=kernels, root=root)
    ctx = out["ctx"]
    result = {"cell": cell_name, "seed": seed, "line": out["line"],
              "info": out["info"], "stage_s": ctx.stage_s,
              "stage_n": ctx.stage_n,
              "latency_parts": parts_summary(ctx.latency_parts)}
    if ctx.scoped is not None:
        result["scoped"] = ctx.scoped.summary()
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
