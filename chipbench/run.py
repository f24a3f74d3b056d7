"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload smallbank.open --seed 7 --seconds 10 \
        --trace 0

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``chipbench/harness.py``).  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` a few
seconds of the window are traced with the JAX profiler and the result
carries its per-layer metrics, the device's busy seconds and a breakdown.

Where JAX finds no TPU, or fewer chips than the cell asks for, or the
kernels would not resolve to compiled Pallas, the run exits non-zero and
prints no result.  Earlier lines of standard output describe the run; the
last is the result, ``{"correct", "attempted", "failed", "metrics",
"device", ..., "checks"}``; the last lines of standard error give every
number the correctness check compared, beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` in the checkout, at a fixed path because
    the path is part of what the next run must find.  Every program is
    cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(cell: dict) -> None:
    """Exit unless JAX finds a TPU with the chips the cell asks for and
    the kernels resolve to compiled Pallas."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU: JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"chipbench: {cell['name']} needs "
                         f"{cell['chips']} chips, found {len(devs)}")
    from repro.kernels import resolve
    kcfg = resolve(None)
    if kcfg.backend != "pallas" or kcfg.interpret:
        raise SystemExit(f"chipbench: kernels resolve to {kcfg.name!r}, "
                         f"not compiled 'pallas'")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)

    require_chips(cell)
    cache = enable_cache()

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, spec)
    print(json.dumps(dict(out["info"], cell=cell["name"], seed=args.seed,
                          compile_cache=cache)), flush=True)
    line = out["line"]
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
