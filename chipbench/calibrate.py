"""Calibration on the chip, outside any benchmark run: the knee sweep that
fixed an open cell's rate, and the control's readings of the correctness
check.  Each prints one JSON line per run.

    python3 chipbench/calibrate.py knee --workload smallbank.open \\
        --seconds 10 --seeds 5 6 7 --rates 20000 24000 28000
    python3 chipbench/calibrate.py control --workload smallbank.closed \\
        --seconds 10 --seeds 1 2 3

``knee`` serves the open cell at each rate and seed in turn and reports
goodput, the requests that failed, the service's counters over the window,
the arrivals due in the window that the generator still held back at its
close (it sends no faster than admission has room, so past the knee the
backlog gathers there) and the tails.  ``PERF.md`` gives the criterion.
``control`` serves the cell with validation switched off
(``faults.no_validation``), which the check has to fail.
"""
import json
import sys
import time

from run import enable_cache, require_chips  # also puts the checkout on sys.path

from chipbench import faults, harness


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("knee", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    require_chips(cell)
    enable_cache()
    if args.mode == "knee":
        base = harness.load_traffic(cell["traffic"])
        for rate, seed in [(r, s) for r in args.rates
                           for s in args.seeds or [args.seed]]:
            out = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), spec,
                                   traffic=dict(base, rate_txn_s=rate))
            m = out["line"]["metrics"]
            print(json.dumps({
                "rate": rate, "seed": seed,
                "compiles_window": out["info"]["compiles_window"],
                "correct": out["line"]["correct"],
                "failed": out["line"]["failed"],
                "attempted": out["line"]["attempted"],
                "held_back": out["info"]["held_back"],
                **{k: v["value"] for k, v in m.items()},
                "window": out["info"]["window"]}), flush=True)
        return 0
    with faults.no_validation():
        for seed in args.seeds:
            out = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), spec)
            print(json.dumps({
                "seed": seed, "correct": out["line"]["correct"],
                "checks": {k: v["value"]
                           for k, v in out["line"]["checks"].items()},
                "goodput_txn_s":
                    out["line"]["metrics"]["goodput_txn_s"]["value"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
