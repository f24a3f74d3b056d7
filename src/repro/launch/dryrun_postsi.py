import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Must be first (see dryrun.py).

"""Dry-run of the paper's OWN technique on the production mesh: one PostSI
wave (shard_map over 256 "node" shards, peer collectives only) lowered and
compiled for 256 devices, with the same roofline record as the LM cells.

  PYTHONPATH=src python -m repro.launch.dryrun_postsi [--nodes 256]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dist_engine import dist_wave_traceable, make_node_mesh, shard_store
from repro.core.workloads import micro_waves
from repro.core.store import make_store
from repro.launch.dryrun import (TARGET_KIND, _memory_analysis,
                                 parse_collectives, peaks)
from repro.launch.hlo_analysis import analyze


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=256)
    ap.add_argument("--keys-per-node", type=int, default=65536)
    ap.add_argument("--txns", type=int, default=2048)
    ap.add_argument("--ops", type=int, default=8)
    ap.add_argument("--out", default="experiments/dryrun_final/postsi-db__wave__16x16.json")
    args = ap.parse_args()

    mesh = make_node_mesh(args.nodes)
    rng = np.random.RandomState(0)
    wave = micro_waves(rng, 1, args.txns, args.nodes, args.keys_per_node,
                       n_ops=args.ops, read_ratio=0.6, dist_frac=0.3)[0]

    store_abs = jax.eval_shape(lambda: make_store(args.nodes * args.keys_per_node, 8))
    t0 = time.time()

    wave_fn = dist_wave_traceable(mesh, sched="postsi")

    def step(val, tid, cid, sid, head, wv, ok, okey, oval, host, tids):
        from repro.core.store import MVStore
        st = MVStore(val, tid, cid, sid, head, wv)
        from repro.core.engine import Wave
        w = Wave(ok, okey, oval, host, tids)
        st2, out, _ = wave_fn(st, w, jnp.int32(1), jnp.int32(1), args.nodes)
        return st2.val, st2.cid, out.status, out.s, out.c

    from jax.sharding import NamedSharding, PartitionSpec as P
    sh_store = NamedSharding(mesh, P("node"))
    sh_rep = NamedSharding(mesh, P())
    abs_in = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh_store)
              for a in store_abs]
    wave_abs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh_rep)
                for a in wave]
    lowered = jax.jit(step).lower(*abs_in, *wave_abs)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    txt = compiled.as_text()
    hlo = analyze(txt, args.nodes)
    coll = parse_collectives(txt, args.nodes)
    mem = _memory_analysis(compiled)
    pk = peaks(TARGET_KIND)
    terms = {"compute": hlo["flops"] / pk["flops"],
             "memory": hlo["bytes"] / pk["hbm_bw"],
             "collective": hlo["collective_bytes"] / pk["ici_bw"]}

    rec = {
        "arch": "postsi-db", "shape": f"wave_T{args.txns}_O{args.ops}",
        "mesh": "16x16(node)", "n_devices": args.nodes,
        "target": TARGET_KIND, "kind": "txn-wave",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem, "hlo": hlo, "collectives": coll,
        "roofline": {
            "compute_s": terms["compute"],
            "memory_s": terms["memory"],
            "collective_s": terms["collective"],
            "dominant": max(terms, key=terms.get),
            "useful_flops_frac": None,
        },
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    r = rec["roofline"]
    print(f"postsi-db wave on {args.nodes} nodes: compile={t_compile:.1f}s "
          f"dominant={r['dominant']} c={r['compute_s']:.4f}s "
          f"m={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
          f"({args.txns} txns x {args.ops} ops, "
          f"{args.nodes * args.keys_per_node / 1e6:.0f}M keys)")


if __name__ == "__main__":
    main()
