"""Benchmark aggregator: one block per paper table/figure + roofline + kernel
micro-benchmarks + the closed-loop service.  Prints ``name,us_per_call,
derived`` CSV (per assignment).

Run all blocks, or name the ones you want:

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python benchmarks/run.py service     # one block
    PYTHONPATH=src python -m benchmarks.run figures engine
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from repro.jaxenv import enable_compile_cache, held_to_cpu


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}", flush=True)


def _engine_figures() -> None:
    from . import (fig06_clock_skew, fig07_08_tpcc, fig09_10_smallbank,
                   fig11_comm_abort, fig12_contention, fig13_length_dist)
    from .simcost import DEFAULT_WAVES

    def n_txn_of(r):
        return DEFAULT_WAVES * (r["committed"] + r["aborted"])

    for r in fig06_clock_skew.run():
        _csv(f"fig06/clocksi/skew{r['skew_ms']}ms",
             r["engine_wall_s"] * 1e6 / n_txn_of(r),
             f"tput={r['throughput_tps']:.0f}tps abort={r['abort_pct']:.1f}%")

    for dist, tag in ((0.2, "fig07"), (0.5, "fig08")):
        for r in fig07_08_tpcc.run(dist_frac=dist):
            _csv(f"{tag}/tpcc/{r['sched']}/n{r['n_nodes']}",
                 r["engine_wall_s"] * 1e6 / n_txn_of(r),
                 f"tput={r['throughput_tps']:.0f}tps abort={r['abort_pct']:.1f}%")

    for dist, tag in ((0.2, "fig09"), (0.5, "fig10")):
        for r in fig09_10_smallbank.run(dist_frac=dist):
            _csv(f"{tag}/smallbank/{r['sched']}/n{r['n_nodes']}",
                 r["engine_wall_s"] * 1e6 / n_txn_of(r),
                 f"tput={r['throughput_tps']:.0f}tps abort={r['abort_pct']:.1f}%")

    for r in fig11_comm_abort.run():
        _csv(f"fig11/{r['sched']}", r["engine_wall_s"] * 1e6 / n_txn_of(r),
             f"cross/txn={r['cross_per_txn']:.2f} coord/txn="
             f"{r['coord_per_txn']:.2f} abort={r['abort_pct']:.1f}%")

    for r in fig12_contention.run():
        _csv(f"fig12/{r['sched']}/hot{r['hot_pct']}",
             r["engine_wall_s"] * 1e6 / n_txn_of(r),
             f"tput={r['throughput_tps']:.0f}tps abort={r['abort_pct']:.1f}%")

    for r in fig13_length_dist.run_length():
        _csv(f"fig13a/{r['sched']}/ops{r['n_ops']}",
             r["engine_wall_s"] * 1e6 / n_txn_of(r),
             f"tput={r['throughput_tps']:.0f}tps")
    for r in fig13_length_dist.run_dist():
        _csv(f"fig13b/{r['sched']}/dist{r['dist_pct']}",
             r["engine_wall_s"] * 1e6 / n_txn_of(r),
             f"tput={r['throughput_tps']:.0f}tps")


def _engine_executor() -> None:
    """Fused-scan vs per-wave executor comparison plus the wave-commit
    megakernel sweep; also refreshes BENCH_engine.json (the perf-trajectory
    datapoint, ``fused_kernel`` section included)."""
    from . import bench_engine
    report = bench_engine.run()
    report["fused_kernel"] = bench_engine.run_fused_kernel()
    bench_engine.write_report(report)     # quiet: keep stdout pure CSV
    for sched, r in report["schedulers"].items():
        n_txn = r["committed"] + r["aborted"]
        _csv(f"engine/fused/{sched}", r["fused_wall_s"] * 1e6 / n_txn,
             f"speedup={r['speedup']:.2f}x waves/s={r['waves_per_sec']:.0f} "
             f"abort={100 * r['abort_rate']:.1f}%")
        for bk, scheds in report["backends"].items():
            b = scheds[sched]
            _csv(f"engine/fused/{sched}/{bk}",
                 b["fused_wall_s"] * 1e6 / n_txn,
                 f"waves/s={b['waves_per_sec']:.0f} "
                 f"vs_default={b['vs_default']:.2f}x")
    for r in report["fused_kernel"]["rows"]:
        _csv(f"engine/wave_commit/T{r['T']}/{r['backend']}",
             r["fused_1launch_us"],
             f"vs_3op={r['speedup']:.2f}x measured={r['measured']}")


def _service() -> None:
    """Closed-loop transaction service (DESIGN.md §8); also refreshes
    BENCH_service.json (goodput/latency/retry trajectory datapoint)."""
    from . import bench_service
    report = bench_service.run()
    bench_service.write_report(report)    # quiet: keep stdout pure CSV
    for sched, rows in report["sweep"].items():
        for r in rows:
            _csv(f"service/{sched}/load{r['load_factor']}",
                 r["wall_s"] * 1e6 / max(r["executions"], 1),
                 f"goodput={r['goodput_tps']:.0f}tps retry={r['retry_rate']:.2f} "
                 f"p99={r['latency_p99']:.0f}ticks dropped={r['dropped']} "
                 f"evicted={r['evicted_visible']}")
    for row in report["gc"]["ring_sweep"]:
        _csv(f"service/gc/V{row['n_versions']}", 0.0,
             f"evicted_visible={row['evicted_visible']}")
    for r in report["streaming"]["sweep"]:
        _csv(f"service/streaming/{r['mode']}/theta{r['theta']}",
             r["wall_s"] * 1e6 / max(r["executions"], 1),
             f"goodput={r['goodput_tps']:.0f}tps "
             f"speedup={r['speedup_vs_step']:.2f}x retry={r['retry_rate']:.2f}")
    a = report["streaming"]["adaptive"]
    _csv(f"service/streaming/{a['mode']}/theta{a['theta']}",
         a["wall_s"] * 1e6 / max(a["executions"], 1),
         f"goodput={a['goodput_tps']:.0f}tps T={a['wave_T_final']} "
         f"md={a['md_events']} ai={a['ai_events']}")


def _planner() -> None:
    """Planned-vs-optimistic goodput crossover (DESIGN.md §10); merges the
    ``planned_crossover`` section into BENCH_engine.json.  With ``--smoke``
    a trimmed sweep also lands in artifacts/planner_smoke/ for CI upload."""
    import json

    from . import bench_engine
    smoke = "--smoke" in _FLAGS
    cross = bench_engine.run_planned_crossover(smoke=smoke)
    bench_engine.write_crossover(cross)   # quiet: keep stdout pure CSV
    for r in cross["rows"]:
        p = r["planned"]
        _csv(f"planner/planned/theta{r['theta']}/T{r['T']}",
             p["wall_s"] * 1e6 / r["n_txn"],
             f"goodput={p['goodput_tps']:.0f}tps lanes={p['lane_waves']} "
             f"plan={p['plan_s']*1e3:.1f}ms wins={r['planned_wins']}")
        for sched in cross["config"]["baselines"]:
            b = r[sched]
            _csv(f"planner/{sched}/theta{r['theta']}/T{r['T']}",
                 b["wall_s"] * 1e6 / r["n_txn"],
                 f"goodput={b['goodput_tps']:.0f}tps "
                 f"abort={100 * b['abort_rate']:.1f}%")
    if smoke:
        out_dir = os.path.join("artifacts", "planner_smoke")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "planner_crossover.json"), "w") as f:
            json.dump(cross, f, indent=2)
            f.write("\n")


def _dist_child() -> list:
    """Run ``benchmarks.bench_dist`` on 8 virtual CPU devices in a child
    python and return its CSV rows.  The CPU device count locks when JAX
    initializes, so :func:`main` calls this before this process imports
    JAX; the child then holds no chip that the parent needs."""
    import subprocess

    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env.setdefault("PYTHONPATH", "src")
    args = [sys.executable, "-m", "benchmarks.bench_dist"]
    if "--smoke" in _FLAGS:
        args.append("--smoke")
    out = subprocess.run(args, env=env, capture_output=True, text=True)
    if out.returncode != 0:
        raise SystemExit(f"benchmarks.bench_dist failed:\n{out.stderr[-3000:]}")
    return [line for line in out.stdout.splitlines()
            if line.startswith("dist/")]


_DIST_ROWS: list = []  # rows of the CPU child, run before JAX was imported


def _dist() -> None:
    """Distributed wave engine over the node mesh; also refreshes
    BENCH_dist.json.  On a TPU it runs in this process over
    ``jax.devices()``: a chip belongs to one process.  On the CPU its rows
    come from the virtual-device child that :func:`main` ran first."""
    if held_to_cpu():
        for line in _DIST_ROWS:
            print(line, flush=True)
        return
    import jax

    from . import bench_dist
    if jax.default_backend() != "tpu":
        raise SystemExit("the dist block needs TPU devices, or "
                         "JAX_PLATFORMS=cpu for virtual host devices")
    report = bench_dist.run(smoke="--smoke" in _FLAGS)
    bench_dist.write_report(report)
    bench_dist.print_csv(report)


def _kernel_micro() -> None:
    """XLA-path kernel micro-benchmarks (CPU wall time; derived = ideal
    throughput class).  The Pallas path is validated in tests."""
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.RandomState(0)

    def bench(fn, *args, reps=5):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
            (out[0] if isinstance(out, tuple) else out).block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    B, S, H, KH, D = 1, 1024, 8, 4, 128
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, jnp.bfloat16)
    us = bench(lambda a, b, c: ops.flash_attention(a, b, c, causal=True), q, k, v)
    fl = 4 * B * H * S * S * D / 2
    _csv("kernel/flash_attention/xla_ref/1k", us, f"{fl/us/1e3:.1f}GFLOPs")

    BH, Sx, P, N = 8, 2048, 64, 128
    x = jnp.asarray(rng.randn(BH, Sx, P) * 0.3, jnp.float32)
    dA = -jnp.asarray(np.abs(rng.rand(BH, Sx)) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.randn(2, Sx, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.randn(2, Sx, N) * 0.3, jnp.float32)
    us = bench(lambda *a: ops.ssd(*a, n_heads_per_group=4), x, dA, Bm, Cm)
    _csv("kernel/ssd_scan/xla_ref/2k", us,
         f"{BH*Sx*P*N*4/us/1e3:.1f}GFLOPs-class")

    # version_scan across every backend the platform can run (the engine
    # read-path hot spot); the label names the backend actually dispatched
    import jax
    from repro.kernels import BACKENDS, KernelConfig

    V = 8
    for bk in BACKENDS:
        if bk == "pallas" and jax.default_backend() != "tpu":
            continue                       # Mosaic cannot lower off-TPU
        # interpret mode pays per-block grid emulation — bench it at the
        # engine's wave-read size instead of stalling the block for minutes
        M, tag = (4096, "4k") if bk == "pallas_interpret" else (65536, "64k")
        cids = jnp.asarray(np.sort(rng.randint(0, 1 << 20, (M, V)), 1),
                           jnp.int32)
        tids = jnp.asarray(rng.randint(-1, 1000, (M, V)), jnp.int32)
        mc = jnp.asarray(rng.randint(0, 1 << 20, (M,)), jnp.int32)
        cfg = KernelConfig(bk)
        us = bench(lambda *a: ops.version_scan(
            *a, use_pallas=cfg.use_pallas, interpret=cfg.interpret),
            cids, tids, mc)
        _csv(f"kernel/version_scan/{bk}/{tag}", us,
             f"{M*V*8/us/1e3:.2f}GB/s-scan")

    T, O = 256, 8
    rk = jnp.asarray(rng.randint(-1, 4000, (T, O)), jnp.int32)
    wk = jnp.asarray(rng.randint(-1, 4000, (T, O)), jnp.int32)
    us = bench(lambda *a: ops.potential_matrix(*a), rk, wk)
    _csv("kernel/potential_matrix/xla_ref/256", us, f"{T*T*O*O/us/1e3:.1f}Gcmp/s")


def _roofline_headlines() -> None:
    """Dry-run roofline headlines + the compiled wave-engine HLO audit
    (bytes / FLOPs / arithmetic intensity per scheduler x kernel config).
    The engine audit lands as the ``roofline`` section of BENCH_engine.json
    and as artifacts/roofline/engine_roofline.json for CI upload."""
    import json

    from . import bench_engine, roofline
    rep = roofline.engine_roofline(smoke="--smoke" in _FLAGS)
    bench_engine.write_section("roofline", rep)   # quiet: stdout stays CSV
    out_dir = os.path.join("artifacts", "roofline")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "engine_roofline.json"), "w") as f:
        json.dump(rep, f, indent=2)
        f.write("\n")
    for r in rep["rows"]:
        _csv(f"roofline/engine/{r['sched']}/{r['backend']}", 0.0,
             f"flops={r['flops']:.3g} bytes={r['bytes']:.3g} "
             f"AI={r['arith_intensity']} platform={r['platform']}")
    for s in roofline.summary(roofline.load()):
        u = s["useful"]
        _csv(s["name"], s["bound_s"] * 1e6,
             f"dominant={s['dominant']} useful={u if u is None else round(u, 2)}")


BLOCKS = {
    "figures": _engine_figures,
    "engine": _engine_executor,
    "service": _service,
    "planner": _planner,
    "dist": _dist,
    "kernels": _kernel_micro,
    "roofline": _roofline_headlines,
}


_FLAGS: list = []      # dash-flags of the current invocation (for blocks)


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    _FLAGS[:] = [a for a in argv if a.startswith("-")]
    names = [a for a in argv if not a.startswith("-")] or list(BLOCKS)
    unknown = [n for n in names if n not in BLOCKS]
    if unknown:
        raise SystemExit(f"unknown block(s) {unknown}; pick from {list(BLOCKS)}")
    if "dist" in names and held_to_cpu():
        _DIST_ROWS[:] = _dist_child()     # before this process imports JAX
    enable_compile_cache()
    print("name,us_per_call,derived")
    for n in names:
        BLOCKS[n]()


if __name__ == "__main__":
    if __package__ in (None, ""):          # `python benchmarks/run.py ...`
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        __package__ = "benchmarks"
    main()
