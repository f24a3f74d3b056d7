"""Bring-up smoke: the served PostSI path on one TPU chip at 2^23 keys.

Drives ``TxnService.run_streaming`` — the served transaction path, down to
``engine._scan_block`` and the Mosaic kernels — with a YCSB-A-shaped open
stream (50/50 read/RMW, zipfian 0.99, 10% distributed transactions) over
an 8-node logical cluster whose 2^23-record store (about 1.1 GB) lives in
HBM.  Every served history must pass ``TxnService.verify()`` (the SI
checker plus the final store against a serial replay), and must equal the
same stream served through the plain ``jnp`` reference kernels: the same
commit set, the same per-wave outcomes and the same final store.

    python chip_smoke.py               # one chip: default kernels (warm-up
                                       # + warm run), jnp reference,
                                       # pallas+fused
    python chip_smoke.py --four-chips  # the store sharded over four chips
                                       # against the same stream on one

Where JAX finds no TPU it exits non-zero and prints no result: there is
no CPU or interpreter fallback.  Earlier lines report each phase as a JSON
object; the last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``JAX_COMPILATION_CACHE_DIR`` places the compile cache (default:
``.jax_cache`` in the checkout).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.dist_engine import make_node_mesh  # noqa: E402
from repro.core.substrate import mesh_degrade_count  # noqa: E402
from repro.jaxenv import enable_compile_cache  # noqa: E402
from repro.kernels import resolve  # noqa: E402
from repro.service import TxnService, ycsb_txn_gen  # noqa: E402

SEED = 0
N_VERSIONS = 8
THETA, READ_FRAC, DIST_FRAC = 0.99, 0.5, 0.1   # YCSB-A mix, zipfian 0.99
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Size(NamedTuple):
    """Scale of one smoke stream; ``ticks`` ticks of ``2*T`` arrivals."""
    n_keys: int
    n_nodes: int
    T: int
    O: int
    B: int
    K: int
    ticks: int


# 2^23 records: the 10M-record YCSB setups, cut to a power of two
ONE_CHIP = Size(n_keys=2 ** 23, n_nodes=8, T=256, O=4, B=4, K=2, ticks=16)
# the same store over a 4-node cluster: 2^21 records per chip when sharded
FOUR_CHIPS = ONE_CHIP._replace(n_nodes=4)


class SmokeFailure(Exception):
    """A phase produced a wrong or unverifiable result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Counts backend compiles, and sums their seconds, while entered."""

    def __init__(self):
        self.n, self.secs = 0, 0.0

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += duration


def serve(size: Size, kernels=None, mesh=None):
    """Serve the seeded stream through ``TxnService.run_streaming``.
    Returns ``(svc, wall_s)``; the wall runs from the first submit until
    the final store is ready on the device."""
    svc = TxnService(n_keys=size.n_keys, n_versions=N_VERSIONS, T=size.T,
                     O=size.O, sched="postsi", n_nodes=size.n_nodes,
                     seed=SEED, mesh=mesh, kernels=kernels)
    gen = ycsb_txn_gen(np.random.RandomState(SEED), size.n_nodes,
                       size.n_keys // size.n_nodes, theta=THETA,
                       read_frac=READ_FRAC, dist_frac=DIST_FRAC,
                       n_ops=size.O)
    jax.block_until_ready(svc.store)
    t0 = time.perf_counter()
    svc.run_streaming([2 * size.T] * size.ticks, gen, B=size.B, K=size.K)
    jax.block_until_ready(svc.store)
    return svc, time.perf_counter() - t0


def verified(svc, phase: str, wall_s: float, clock: CompileClock) -> None:
    """Check the served history and report the phase."""
    errors = svc.verify()
    check(errors == [], f"{phase}: verify() found {len(errors)} "
                        f"violation(s), first: {errors[:3]}")
    rep = svc.report()
    report(phase=phase, kernels=svc.kernels.name, wall_s=wall_s,
           committed=rep.committed, aborted=rep.executions - rep.committed,
           dropped=rep.dropped, waves=rep.waves, blocks=rep.blocks,
           goodput_txn_per_s=rep.committed / wall_s,
           compiles_so_far=clock.n, compile_s_so_far=clock.secs,
           verify_errors=0)


def committed_ids(svc) -> list:
    return sorted(r.req_id for r in svc.requests if r.status == "committed")


def mismatches(a, b) -> list:
    """Where two services that served the same stream disagree: commit
    set, per-wave outcomes and final store, compared bit for bit."""
    errs = []
    ca, cb = committed_ids(a), committed_ids(b)
    if ca != cb:
        errs.append(f"commit sets differ ({len(ca)} vs {len(cb)} committed)")
    if len(a.history) != len(b.history):
        errs.append(f"{len(a.history)} vs {len(b.history)} waves")
    for w, ((ta, oa), (tb, ob)) in enumerate(zip(a.history, b.history)):
        if not np.array_equal(ta, tb):
            errs.append(f"wave {w}: tids differ")
        errs += [f"wave {w}: {name} differs" for name in oa._fields
                 if not np.array_equal(getattr(oa, name), getattr(ob, name))]
    errs += [f"final store: {name} differs" for name in a.store._fields
             if not np.array_equal(np.asarray(getattr(a.store, name)),
                                   np.asarray(getattr(b.store, name)))]
    return errs


def same(a, b, what: str) -> None:
    errs = mismatches(a, b)
    check(errs == [], f"{what}: {len(errs)} mismatch(es), first: {errs[:5]}")
    report(phase=f"compare {what}", equal=True,
           committed=len(committed_ids(a)), waves=len(a.history))


def one_chip(size: Size = ONE_CHIP, default=None,
             fused: str = "pallas+fused") -> None:
    """The served path under the default kernels (a warm-up run that
    compiles, then a warm run that is timed), the same stream under the
    jnp reference, then under ``fused``; each verified and compared."""
    with CompileClock() as clock:
        svc, wall = serve(size, default)
        verified(svc, "default/warm-up (compile included)", wall, clock)
        del svc
        n0 = clock.n
        svc, wall = serve(size, default)
        verified(svc, "default/warm", wall, clock)
        check(clock.n == n0,
              f"the warm run compiled {clock.n - n0} program(s)")
        ref, wall = serve(size, "jnp")
        verified(ref, "jnp reference", wall, clock)
        same(svc, ref, "default vs jnp")
        del svc
        svc, wall = serve(size, fused)
        verified(svc, fused, wall, clock)
        same(svc, ref, f"{fused} vs jnp")


def four_chips(size: Size = FOUR_CHIPS, kernels=None) -> None:
    """The store sharded over a four-node mesh, one block per chip, against
    the same stream served from one device."""
    with CompileClock() as clock:
        sharded, wall = serve(size, kernels, mesh=make_node_mesh(4))
        shards = sharded.store.val.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"store spans {len({s.device for s in shards})} device(s)")
        check(all(s.data.shape[0] == size.n_keys // 4 for s in shards),
              f"shard rows {[s.data.shape[0] for s in shards]}")
        check(mesh_degrade_count() == 0,
              f"{mesh_degrade_count()} mesh kernel degrade(s) to jnp")
        report(phase="mesh layout",
               shards=[[str(s.device), s.data.shape[0]] for s in shards])
        verified(sharded, "mesh/4 chips (compile included)", wall, clock)
        single, wall = serve(size, kernels)
        check(len(single.store.val.devices()) == 1,
              "the single-device store spans several devices")
        verified(single, "single device (compile included)", wall, clock)
        same(sharded, single, "mesh vs single device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-store path on four chips "
                         "and the single-device run it is compared with")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {dev.platform!r}")
    cfg = resolve(None)
    if cfg.backend != "pallas" or cfg.interpret:
        raise SystemExit(f"chip_smoke: kernels resolve to {cfg.name!r}, "
                         f"not compiled 'pallas'")
    n = len(jax.devices())
    if args.four_chips and n < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 chips, found {n}")
    cache = enable_compile_cache()                # before the first compile
    report(phase="device", platform=dev.platform, kind=dev.device_kind,
           count=n, kernels=cfg.name, compile_cache=cache,
           size=(FOUR_CHIPS if args.four_chips else ONE_CHIP)._asdict())
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        raise SystemExit(f"chip_smoke: FAIL: {e}") from None
    report(phase="peak HBM",
           peak_bytes_in_use=[d.memory_stats().get("peak_bytes_in_use")
                              for d in jax.devices()])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}),
        flush=True)


if __name__ == "__main__":
    main()
