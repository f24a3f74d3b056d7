"""Distributed wave-engine benchmark: the decentralized-scaling story on a
virtual-device mesh (DESIGN.md §4).

Needs more than one XLA device.  On a TPU host it runs over the chips
(``jax.devices()``), in the process that holds them.  Held to the CPU
(``JAX_PLATFORMS=cpu``), ``__main__`` defaults
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before JAX
initializes (the device count locks there) — which is also why the
``benchmarks.run dist`` block runs this module as a child on the CPU.
Three sections, all through the ONE shared commit loop
(``engine.run_wave_on``) over a ``MeshSubstrate``:

* **scaling** — goodput (committed txns/s) for every scheduler × node
  count, fused executor, fixed total key space (so more nodes = smaller
  blocks + more peer-collective fan-in, the paper's §V scaling axis);
* **executor** — fused scan-on-mesh vs per-wave dispatch at max nodes:
  the host-sync tax measured on the distributed path;
* **service** — one closed-loop SmallBank session served from the mesh
  (``TxnService(mesh=...)``) against the identical single-device session:
  commits must match exactly, walls differ.

Prints ``name,us_per_call,derived`` CSV rows (aggregator format) and writes
``BENCH_dist.json`` at the repo root.

Run:  PYTHONPATH=src python -m benchmarks.bench_dist [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_dist.json")

N_WAVES = 8
WAVE_T = 64
N_KEYS = 512            # divisible by every node count below
NODE_COUNTS = (1, 2, 4, 8)
LOAD_FACTOR = 0.9
SVC_TICKS = 10

SMOKE = dict(n_waves=3, T=16, node_counts=(1, 2), svc_ticks=5,
             scheds=("postsi", "si"))


def _mk_waves(n_waves: int, T: int, n_nodes: int, n_keys: int):
    import numpy as np
    from repro.core.workloads import smallbank_waves
    return smallbank_waves(np.random.RandomState(7), n_waves, T, n_nodes,
                           n_keys // n_nodes, dist_frac=0.3, hot_frac=0.4,
                           hot_per_node=4)


def _host_skew(sched: str, n_nodes: int):
    import numpy as np
    return (np.round(np.linspace(0, 2, n_nodes)).astype(np.int32)
            if sched == "clocksi" else None)


def _timed(setup, fn, reps: int = 3):
    """(result, best wall seconds, warmup seconds) for ``fn(setup())``.
    The first call pays jit outside the timers but its wall is *recorded*
    (compile cost is reported, not hidden); each rep's fresh store
    (allocation + device_put sharding) is built and synced *before* its
    timer starts, and every timed region ends with ``block_until_ready``
    on the actual outputs — only synced mesh execution is measured."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(setup()))
    warmup = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        arg = jax.block_until_ready(setup())
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(arg))
        best = min(best, time.perf_counter() - t0)
    return out, best, warmup


def _history_occupancy(history, n_nodes: int, kpn: int):
    """Per-node committed-txn occupancy from a driver/service history:
    each committed txn is attributed to the PHYSICAL block owner
    (``key // kpn``) of its first touched key — the node whose version
    rings its commit actually landed on under the static block layout."""
    import numpy as np
    occ = np.zeros(n_nodes, np.int64)
    for _, out in history:
        st = np.asarray(out.status)
        rk, wk = np.asarray(out.read_key), np.asarray(out.write_key)
        keys = np.where(rk >= 0, rk, wk)              # [T, O], -1 = no op
        for t in np.nonzero(st == 1)[0]:              # COMMITTED
            touched = keys[t][keys[t] >= 0]
            if touched.size:
                occ[int(touched[0]) // kpn] += 1
    return occ


def _imbalance(occ) -> float:
    return round(float(occ.max() / occ.mean()), 4) if occ.sum() else 0.0


def _scaling(scheds, node_counts, n_waves, T) -> Dict:
    from repro.core import make_store
    from repro.core.dist_engine import (make_node_mesh, run_workload_fused_dist,
                                        shard_store)
    rows = []
    for n in node_counts:
        mesh = make_node_mesh(n)
        waves = _mk_waves(n_waves, T, n, N_KEYS)
        for sched in scheds:
            hs = _host_skew(sched, n)

            def setup():
                return shard_store(make_store(N_KEYS, 8), mesh)

            def run(st):
                return run_workload_fused_dist(st, waves, mesh, sched=sched,
                                               n_nodes=n, host_skew=hs)

            (_, hist, stats), wall, warm = _timed(setup, run)
            n_txn = stats.committed + stats.aborted
            occ = _history_occupancy(hist, n, N_KEYS // n)
            rows.append({
                "sched": sched, "n_nodes": n, "wall_s": round(wall, 6),
                "warmup_s": round(warm, 6),
                "committed": stats.committed, "aborted": stats.aborted,
                "goodput_tps": round(stats.committed / wall, 1),
                "txns_per_sec": round(n_txn / wall, 1),
                "msgs_cross": stats.msgs_cross,
                "occupancy": occ.tolist(),
                "imbalance": _imbalance(occ),
            })
    return {"rows": rows}


def _executor(scheds, n, n_waves, T) -> Dict:
    from repro.core import make_store
    from repro.core.dist_engine import (make_node_mesh, run_workload_dist,
                                        run_workload_fused_dist, shard_store)
    mesh = make_node_mesh(n)
    waves = _mk_waves(n_waves, T, n, N_KEYS)
    rows = []
    for sched in scheds:
        hs = _host_skew(sched, n)

        def setup():
            return shard_store(make_store(N_KEYS, 8), mesh)

        def per_wave(st):
            return run_workload_dist(st, waves, mesh, sched=sched, n_nodes=n,
                                     host_skew=hs)

        def fused(st):
            return run_workload_fused_dist(st, waves, mesh, sched=sched,
                                           n_nodes=n, host_skew=hs)

        (_, h1, s1), wall_pw, warm_pw = _timed(setup, per_wave)
        (_, h2, s2), wall_fz, warm_fz = _timed(setup, fused)
        assert s1 == s2, (sched, s1, s2)    # bit-identical by construction
        rows.append({
            "sched": sched, "n_nodes": n,
            "per_wave_wall_s": round(wall_pw, 6),
            "fused_wall_s": round(wall_fz, 6),
            "per_wave_warmup_s": round(warm_pw, 6),
            "fused_warmup_s": round(warm_fz, 6),
            "speedup": round(wall_pw / wall_fz, 2),
            "committed": s1.committed, "aborted": s1.aborted,
        })
    return {"n_nodes": n, "rows": rows}


def _service(n, T, n_ticks, sched: str = "postsi") -> Dict:
    import numpy as np
    from repro.core.dist_engine import make_node_mesh
    from repro.core.workloads import poisson_arrivals
    from repro.service import RetryPolicy, TxnService, smallbank_txn_gen
    mesh = make_node_mesh(n)
    out = {}
    for tag, m in (("single", None), ("mesh", mesh)):
        svc = TxnService(n_keys=N_KEYS, n_versions=8, T=T, sched=sched,
                         n_nodes=n, retry=RetryPolicy(max_attempts=6),
                         seed=0, mesh=m)
        arrivals = poisson_arrivals(np.random.RandomState(100),
                                    LOAD_FACTOR * T, n_ticks)
        gen = smallbank_txn_gen(np.random.RandomState(200), n, N_KEYS // n,
                                dist_frac=0.3, hot_frac=0.5, hot_per_node=4)
        rep = svc.run_stream(arrivals, gen)
        row = rep.as_dict()
        row["verify_errors"] = len(svc.verify())
        out[tag] = row
    assert out["single"]["committed"] == out["mesh"]["committed"], out
    return out


ELASTIC_THETA = 0.99
ELASTIC_READ_FRAC = 0.97
ELASTIC_N_OPS = 2
ELASTIC_TICKS = 20
ELASTIC_REFRESH = 8
ELASTIC_LOAD = 3         # arrivals per tick = LOAD * T: offer more than the
                         # engine's admission cap (4T queue) can absorb, the
                         # open-system regime where static load-shedding
                         # starts rejecting but replica-served reads never
                         # enter the queue at all
ELASTIC_MASS = 0.95      # replica hot set: rank prefix covering this much
ELASTIC_MAX_FRAC = 0.4   # of the zipf mass, capped at 40% of the key space


def _elastic(node_counts, T, n_ticks) -> Dict:
    """Static vs elastic service pairs on IDENTICAL zipf θ=0.99 read-heavy
    streams (paper §V-D's hot-shard regime: the interleaved key encoding
    lands every node's rank-0 hot keys in node 0's physical block, so the
    static mesh serializes on one node while the others idle).

    Two goodput columns per row, honestly labeled:

    * ``goodput_tps`` — MEASURED committed/s on the virtual-device mesh.
      The elastic lever that moves this number is real: hot-key read-only
      txns are answered from the visibility-floor replicas at submit time
      and never enter the engine, so the elastic service dispatches roughly
      half the waves for the same committed work.
    * ``modeled_goodput_tps`` — simcost.py's cluster cost model (T_OP per
      executed op on the OWNING node) with the makespan taken as the MAX
      per-node busy time from measured occupancy, not the perfect-balance
      ``/ n_nodes`` the static model assumes.  Replica-served reads cost
      the engine nothing (a host hashmap hit at submit).  Cross-node
      message latency is excluded (the service report does not split
      messages per node); the column isolates the load-balance axis.
    """
    import numpy as np
    from repro.core.dist_engine import make_node_mesh
    from repro.core.workloads import zipf_hot_keys
    from repro.placement import PlacementMap
    from repro.service import TxnService, ycsb_txn_gen
    from .simcost import T_OP
    rows = []
    for n in node_counts:
        kpn = N_KEYS // n
        mesh = make_node_mesh(n)
        row = {"n_nodes": n, "theta": ELASTIC_THETA}

        def make_svc(elastic: bool) -> TxnService:
            return TxnService(
                n_keys=N_KEYS, n_versions=8, T=T, O=ELASTIC_N_OPS,
                sched="postsi", n_nodes=n, seed=0, mesh=mesh,
                placement=(PlacementMap(N_KEYS, n, headroom=2)
                           if elastic else None),
                replicas=(zipf_hot_keys(n, kpn, ELASTIC_THETA,
                                        mass=ELASTIC_MASS,
                                        max_frac=ELASTIC_MAX_FRAC)
                          if elastic else None),
                balancer=elastic or None, replica_refresh=ELASTIC_REFRESH)

        def stream():
            return ycsb_txn_gen(np.random.RandomState(31), n, kpn,
                                theta=ELASTIC_THETA,
                                read_frac=ELASTIC_READ_FRAC,
                                n_ops=ELASTIC_N_OPS)

        for tag in ("static", "elastic"):
            elastic = tag == "elastic"
            # _timed's policy applied to service sessions: XLA compiles
            # (wave fn, replica-refresh gather, move kernel pad sizes) are
            # paid by a discarded warmup session, the measured run is
            # steady-state.  The jitted fns are lru-cached per mesh/shape,
            # so a fresh service reuses them.
            warm = make_svc(elastic)
            warm.run_stream([ELASTIC_LOAD * T] * 4, stream())
            if elastic:
                for m in (5, 10, 20, 40):    # move pads 8/16/32/64
                    lo = int(np.argmax(warm.placement.owner
                                       == warm.placement.owner[0]))
                    warm.move_range(lo, lo + m,
                                    (int(warm.placement.owner[lo]) + 1) % n)
            svc = rep = None
            for _ in range(3):               # _timed's reps policy: best of 3
                cand = make_svc(elastic)
                r = cand.run_stream([ELASTIC_LOAD * T] * n_ticks, stream())
                if rep is None or r.wall_s < rep.wall_s:
                    svc, rep = cand, r
            occ = (np.asarray(rep.occupancy, np.int64) if elastic
                   else _history_occupancy(svc.history, n, kpn))
            busy_us = occ * ELASTIC_N_OPS * T_OP
            makespan_us = float(busy_us.max()) or T_OP
            row[tag] = {
                "committed": rep.committed,
                "offered": rep.offered,
                "rejected": rep.rejected,
                "wall_s": round(rep.wall_s, 6),
                "goodput_tps": round(rep.goodput_tps, 1),
                "modeled_goodput_tps": round(
                    rep.committed / makespan_us * 1e6, 1),
                "waves": rep.waves,
                "occupancy": occ.tolist(),
                "imbalance": _imbalance(occ),
                "replica_commits": rep.replica_commits,
                "placement_moves": rep.placement_moves,
                "moved_keys": rep.moved_keys,
                "verify_errors": len(svc.verify()),
            }
        row["goodput_ratio"] = round(
            row["elastic"]["goodput_tps"]
            / max(row["static"]["goodput_tps"], 1e-9), 2)
        row["modeled_ratio"] = round(
            row["elastic"]["modeled_goodput_tps"]
            / max(row["static"]["modeled_goodput_tps"], 1e-9), 2)
        rows.append(row)
    return {"read_frac": ELASTIC_READ_FRAC, "n_ops": ELASTIC_N_OPS,
            "ticks": n_ticks, "wave_T": T, "rows": rows}


def run(smoke: bool = False) -> Dict:
    import jax
    from repro.core import SCHEDULERS
    from repro.core.substrate import effective_mesh_backend
    if smoke:
        n_waves, T = SMOKE["n_waves"], SMOKE["T"]
        node_counts, scheds = SMOKE["node_counts"], SMOKE["scheds"]
        svc_ticks = SMOKE["svc_ticks"]
    else:
        n_waves, T, svc_ticks = N_WAVES, WAVE_T, SVC_TICKS
        node_counts, scheds = NODE_COUNTS, SCHEDULERS
    node_counts = tuple(n for n in node_counts if n <= jax.device_count())
    n_max = max(node_counts)
    return {
        "config": {"workload": "smallbank", "n_waves": n_waves,
                   "wave_size": T, "n_keys": N_KEYS,
                   "node_counts": list(node_counts),
                   "device_count": jax.device_count(), "smoke": smoke,
                   # honest label: what the mesh rows below actually ran —
                   # a 'pallas' process default degrades to 'jnp' on the
                   # mesh path (substrate.mesh_kernels warns and counts)
                   "kernel_backend": effective_mesh_backend()},
        "scaling": _scaling(scheds, node_counts, n_waves, T),
        "executor": _executor(scheds, n_max, n_waves, T),
        "service": _service(n_max, T, svc_ticks),
        "elastic": _elastic(node_counts, T,
                            max(3, ELASTIC_TICKS // 2) if smoke
                            else ELASTIC_TICKS),
    }


def write_report(report: Dict) -> None:
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")


def print_csv(report: Dict) -> None:
    """Aggregator-format rows (``name,us_per_call,derived``)."""
    for r in report["scaling"]["rows"]:
        n_txn = max(r["committed"] + r["aborted"], 1)
        print(f"dist/fused/{r['sched']}/n{r['n_nodes']},"
              f"{r['wall_s'] * 1e6 / n_txn:.2f},"
              f"goodput={r['goodput_tps']:.0f}tps "
              f"cross/txn={r['msgs_cross'] / n_txn:.2f}", flush=True)
    for r in report["executor"]["rows"]:
        n_txn = max(r["committed"] + r["aborted"], 1)
        print(f"dist/executor/{r['sched']}/n{r['n_nodes']},"
              f"{r['fused_wall_s'] * 1e6 / n_txn:.2f},"
              f"fused_vs_per_wave={r['speedup']:.2f}x", flush=True)
    for tag in ("single", "mesh"):
        r = report["service"][tag]
        print(f"dist/service/{tag}/{r['sched']},"
              f"{r['wall_s'] * 1e6 / max(r['executions'], 1):.2f},"
              f"goodput={r['goodput_tps']:.0f}tps committed={r['committed']} "
              f"verify_errors={r['verify_errors']}", flush=True)
    for row in report.get("elastic", {}).get("rows", []):
        for tag in ("static", "elastic"):
            r = row[tag]
            print(f"dist/elastic/{tag}/n{row['n_nodes']},"
                  f"{r['wall_s'] * 1e6 / max(r['committed'], 1):.2f},"
                  f"goodput={r['goodput_tps']:.0f}tps "
                  f"modeled={r['modeled_goodput_tps']:.0f}tps "
                  f"imbalance={r['imbalance']:.2f} "
                  f"replica_commits={r['replica_commits']} "
                  f"moves={r['placement_moves']}", flush=True)


def elastic_smoke() -> Dict:
    """CI gate (the ``elastic-smoke`` workflow leg): elastic must beat
    static at the paper's hardest skew on the full 8-virtual-device mesh,
    with zero silent kernel degrades, and the artifacts go to
    ``artifacts/elastic_smoke`` for the run page."""
    from repro.core.substrate import mesh_degrade_count
    import jax
    n = min(8, jax.device_count())
    report = {"config": {"n_nodes": n, "theta": ELASTIC_THETA,
                         "device_count": jax.device_count()},
              "elastic": _elastic((1, n), WAVE_T, ELASTIC_TICKS)}
    rows = report["elastic"]["rows"]
    by_n = {r["n_nodes"]: r for r in rows}
    top = by_n[n]
    assert top["elastic"]["goodput_tps"] >= top["static"]["goodput_tps"], top
    modeled = [r["elastic"]["modeled_goodput_tps"] for r in rows]
    assert modeled == sorted(modeled), \
        f"elastic modeled goodput not non-decreasing 1->{n}: {modeled}"
    assert top["elastic"]["verify_errors"] == 0, top
    assert mesh_degrade_count() == 0, mesh_degrade_count()
    out_dir = os.path.join(os.path.dirname(OUT_PATH), "artifacts",
                           "elastic_smoke")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "elastic_smoke.json"), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    for row in rows:
        print(f"elastic-smoke n={row['n_nodes']}: "
              f"static={row['static']['goodput_tps']:.0f}tps "
              f"elastic={row['elastic']['goodput_tps']:.0f}tps "
              f"(x{row['goodput_ratio']:.2f} measured, "
              f"x{row['modeled_ratio']:.2f} modeled) "
              f"replica_commits={row['elastic']['replica_commits']} "
              f"moves={row['elastic']['placement_moves']}", flush=True)
    print("ELASTIC-SMOKE-OK", flush=True)
    return report


def main(argv=None) -> Dict:
    argv = sys.argv[1:] if argv is None else argv
    if "--elastic-smoke" in argv:
        return elastic_smoke()
    report = run(smoke="--smoke" in argv)
    write_report(report)
    print_csv(report)
    return report


if __name__ == "__main__":
    from repro.jaxenv import held_to_cpu
    # virtual host devices stand in for chips only on the CPU; the flag
    # must precede JAX's backend init, where the device count locks
    if held_to_cpu():
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    main()
