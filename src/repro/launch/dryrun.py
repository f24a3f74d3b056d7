import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.

import argparse
import json
import re
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import input_shardings
from repro.launch.train import (abstract_train_state, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models.module import (abstract, param_shardings, use_mesh_and_rules)
from repro.optim import adamw_init

# Published per-chip peaks (roofline denominators), keyed by ``device_kind``
# as JAX reports it.  Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over four links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

# The chip the dry-run meshes model (a v5e pod slice is 16x16).  The
# compile itself runs on virtual host devices, whose kind says nothing
# about the target, so the target is named here.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Per-chip peaks of ``device_kind``; a kind not in ``PEAKS`` is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+\[[^\]]*\][^\s]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<rest>[^\n]*)")
_ARR_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _ARR_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(rest: str, default: int) -> int:
    m = _GROUPS_RE.search(rest)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(rest)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Sum per-device collective traffic from the post-SPMD HLO.

    Shapes in the SPMD module are per-device; traffic model per op:
      all-gather         -> result bytes           (each chip receives ~full)
      all-reduce         -> 2 x result bytes       (ring: reduce + broadcast)
      reduce-scatter     -> result bytes x group   (full operand traverses)
      all-to-all         -> result bytes
      collective-permute -> result bytes
    """
    per_type_bytes: Dict[str, int] = {}
    per_type_count: Dict[str, int] = {}
    top: list = []
    total = 0
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        b = _shape_bytes(m.group("shape"))
        if op == "all-reduce":
            traffic = 2 * b
        elif op == "reduce-scatter":
            traffic = b * _group_size(m.group("rest"), n_devices)
        else:
            traffic = b
        per_type_bytes[op] = per_type_bytes.get(op, 0) + traffic
        per_type_count[op] = per_type_count.get(op, 0) + 1
        total += traffic
        top.append((traffic, op, m.group("shape")[:80]))
    top.sort(reverse=True)
    return {
        "collective_bytes_per_device": total,
        "per_type_bytes": per_type_bytes,
        "per_type_count": per_type_count,
        "top_ops": [{"bytes": t, "op": o, "shape": s} for t, o, s in top[:12]],
    }


def _memory_analysis(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out = {}
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                out[f] = int(v)
        return out
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or "utilization" not in k)}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (inference), N = active params."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch        # decode: 1 token/seq


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             seq_shard_kv: bool = False, remat: str | None = None,
             rules=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not applicable(cfg.family, shape_name):
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "skipped": f"long_500k not applicable to family={cfg.family} "
                           "(full attention; see DESIGN.md §5)"}
    if remat:
        cfg = cfg.replace(remat_policy=remat)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()

    with use_mesh_and_rules(mesh, rules):
        if shape.kind == "train":
            model, params, opt = abstract_train_state(cfg)
            _, step = make_train_step(cfg)
            p_sh = param_shardings(model.param_specs(), mesh, rules)
            o_sh = jax.eval_shape(adamw_init, params)
            o_sh = jax.tree_util.tree_map(lambda _: None, o_sh)
            from repro.optim.adamw import AdamWState
            o_sh = AdamWState(
                step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                m=p_sh, v=p_sh)
            batch = input_specs(cfg, shape)[0]
            b_sh = input_shardings(batch, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            from repro.launch.sharding import SERVE_RULES
            rules = rules or SERVE_RULES
            scfg = cfg.replace(param_dtype=jnp.bfloat16)
            model, pstep = make_prefill_step(scfg)
            params = abstract(model.param_specs())
            p_sh = param_shardings(model.param_specs(), mesh, rules)
            batch = input_specs(scfg, shape)[0]
            b_sh = input_shardings(batch, mesh)
            jitted = jax.jit(pstep, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            from repro.launch.sharding import SERVE_RULES
            rules = rules or SERVE_RULES
            model_axis = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
            kv_seq_sharded = seq_shard_kv or (
                cfg.n_kv_heads % model_axis != 0 and cfg.family != "ssm")
            scfg = cfg.replace(param_dtype=jnp.bfloat16,
                               decode_seq_shard=kv_seq_sharded)
            model, dstep = make_decode_step(scfg)
            params = abstract(model.param_specs())
            p_sh = param_shardings(model.param_specs(), mesh, rules)
            batch, cache = input_specs(scfg, shape)
            b_sh = input_shardings(batch, mesh)
            c_sh = input_shardings(cache, mesh, seq_shard_kv=seq_shard_kv)
            jitted = jax.jit(dstep, in_shardings=(p_sh, c_sh, b_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, cache, batch)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = _memory_analysis(compiled)
    cost = _cost_analysis(compiled)
    txt = compiled.as_text()
    coll = parse_collectives(txt, n_dev)          # loop-body-once (for reference)
    from repro.launch.hlo_analysis import analyze
    hlo = analyze(txt, n_dev)                     # with loop trip multipliers
    del txt

    flops = hlo["flops"]
    bytes_acc = hlo["bytes"]
    mf = model_flops(cfg, shape)
    pk = peaks(TARGET_KIND)
    compute_s = flops / pk["flops"]
    memory_s = bytes_acc / pk["hbm_bw"]
    collective_s = hlo["collective_bytes"] / pk["ici_bw"]
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev, "target": TARGET_KIND,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem, "cost": cost, "collectives": coll,
        "hlo": hlo,
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_dev,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "useful_flops_frac": (mf / n_dev) / flops if flops else None,
        },
        "options": {"seq_shard_kv": seq_shard_kv, "remat": remat},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--seq-shard-kv", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.outdir, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_tag = "2x16x16" if mp else "16x16"
                name = f"{arch}__{shape}__{mesh_tag}{args.tag}"
                path = os.path.join(args.outdir, name + ".json")
                if os.path.exists(path):
                    print(f"[skip] {name} (exists)")
                    continue
                print(f"[cell] {name} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp,
                                   seq_shard_kv=args.seq_shard_kv,
                                   remat=args.remat)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                           "error": repr(e)[:2000]}
                    print(f"  ERROR: {e}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if "roofline" in rec:
                    r = rec["roofline"]
                    print(f"  ok compile={rec['compile_s']}s dominant={r['dominant']}"
                          f" c={r['compute_s']:.4f}s m={r['memory_s']:.4f}s"
                          f" coll={r['collective_s']:.4f}s", flush=True)
                elif "skipped" in rec:
                    print(f"  skipped: {rec['skipped']}", flush=True)


if __name__ == "__main__":
    main()
