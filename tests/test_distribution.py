"""Distribution-layer tests.

These need more than one XLA device, and the device count is locked at jax
init — so each test runs a child python with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  Smoke tests and
benches keep seeing 1 device (per the assignment).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mini_dryrun_lower_compile_8dev():
    """Reduced config lowers + compiles on a (2,2,2) pod/data/model mesh;
    memory & cost analysis available; collectives present."""
    print(_run(r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.launch.train import make_train_step, abstract_train_state
from repro.launch.inputs import _train_batch
from repro.launch.sharding import input_shardings
from repro.models.module import use_mesh_and_rules, param_shardings
from repro.optim import adamw_init
from repro.optim.adamw import AdamWState

cfg = get_reduced("qwen3-14b")
mesh = Mesh(np.array(jax.devices()[:8]).reshape(2,2,2), ("pod","data","model"))
with use_mesh_and_rules(mesh):
    model, params, opt = abstract_train_state(cfg)
    _, step = make_train_step(cfg)
    p_sh = param_shardings(model.param_specs(), mesh)
    o_sh = AdamWState(step=NamedSharding(mesh, P()), m=p_sh, v=p_sh)
    batch = _train_batch(cfg, 8, 64, True)
    b_sh = input_shardings(batch, mesh)
    low = jax.jit(step, in_shardings=(p_sh,o_sh,b_sh),
                  out_shardings=(p_sh,o_sh,None),
                  donate_argnums=(0,1)).lower(params, opt, batch)
    comp = low.compile()
txt = comp.as_text()
assert "all-reduce" in txt or "all-gather" in txt
from repro.launch.hlo_analysis import analyze
r = analyze(txt, 8)
assert r["flops"] > 0 and r["collective_bytes"] > 0
print("MINI-DRYRUN-OK", int(r["flops"]), int(r["collective_bytes"]))
"""))


def test_real_execution_on_mesh_matches_single_device():
    """The same train step executed (a) on 1 device and (b) SPMD on a (2,2)
    mesh gives the same loss — numerics of the distribution layer."""
    print(_run(r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.launch.train import make_train_step
from repro.launch.inputs import make_batch
from repro.launch.sharding import input_shardings
from repro.models.module import use_mesh_and_rules, param_shardings
from repro.optim import adamw_init
from repro.optim.adamw import AdamWState

cfg = get_reduced("yi-9b")
model, step = make_train_step(cfg, lr=1e-3)
params = model.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = make_batch(cfg, 4, 32, "train")
_,_, m1 = jax.jit(step)(params, opt, batch)

mesh = Mesh(np.array(jax.devices()[:4]).reshape(2,2), ("data","model"))
with use_mesh_and_rules(mesh):
    p_sh = param_shardings(model.param_specs(), mesh)
    o_sh = AdamWState(step=NamedSharding(mesh, P()), m=p_sh, v=p_sh)
    b_sh = input_shardings(batch, mesh)
    pd = jax.device_put(params, p_sh)
    od = jax.device_put(opt, o_sh)
    bd = jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), batch, b_sh)
    _,_, m2 = jax.jit(step, in_shardings=(p_sh,o_sh,b_sh),
                      out_shardings=(p_sh,o_sh,None))(pd, od, bd)
d = abs(float(m1['loss']) - float(m2['loss']))
assert d < 1e-2, (float(m1['loss']), float(m2['loss']))
print("SPMD-EXEC-OK", float(m1['loss']), float(m2['loss']))
"""))


def test_compressed_psum_and_elastic_reshard():
    print(_run(r"""
import numpy as np, jax, jax.numpy as jnp, functools
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.optim.compress import compressed_psum

mesh = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("pod",))
x = jnp.asarray(np.random.RandomState(0).randn(4, 64), jnp.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                   out_specs=P("pod"), check_vma=False)
def f(xs):
    total, err = compressed_psum(xs, "pod")
    return total

out = f(x)
exact = x.sum(axis=0, keepdims=True)
rel = float(jnp.abs(out[0] - exact[0]).max() / jnp.abs(exact).max())
assert rel < 0.02, rel
print("COMPRESSED-PSUM-OK rel", rel)

# elastic reshard: state saved on a (2,2) mesh restores onto a (4,) mesh
from repro.checkpoint import PostSICheckpointer, reshard_tree
import tempfile
m1 = Mesh(np.array(jax.devices()[:4]).reshape(2,2), ("data","model"))
m2 = Mesh(np.array(jax.devices()[:4]).reshape(4,), ("data",))
tree = {"w": jax.device_put(jnp.arange(16.0).reshape(4,4),
                            NamedSharding(m1, P("data","model")))}
with tempfile.TemporaryDirectory() as d:
    ck = PostSICheckpointer(d, tree)
    assert ck.save(1, tree)
    sh2 = {"w": NamedSharding(m2, P("data", None))}
    step, out = ck.restore(tree, sh2)
assert step == 1
np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(16.0).reshape(4,4))
assert out["w"].sharding.spec == P("data", None)
print("ELASTIC-RESHARD-OK")
"""))


def test_mesh_misconfiguration_rejected_in_parent():
    """Cheap in-process check (this process sees exactly 1 CPU device):
    asking for more mesh nodes than devices is a clear ValueError, never a
    silently under-provisioned mesh."""
    import jax

    from repro.core.dist_engine import make_node_mesh

    with pytest.raises(ValueError, match="device"):
        make_node_mesh(len(jax.devices()) + 1)


def test_dist_engine_all_schedulers_match_single_device():
    """The substrate-unified mesh engine (peer collectives, no coordinator,
    ONE commit loop shared with engine.py) commits the exact same
    transactions with the exact same induced intervals as the single-device
    engine — for ALL SIX schedulers, on both the per-wave and the fused
    lax.scan-under-shard_map paths, including the GC accounting — and the
    misconfiguration guards raise instead of silently mis-sharding."""
    print(_run(r"""
import numpy as np, jax
from repro.core import SCHEDULERS, make_store, run_workload, run_workload_fused
from repro.core.dist_engine import (make_node_mesh, run_workload_dist,
                                    run_workload_fused_dist, shard_store)
from repro.core.workloads import smallbank_waves

n_nodes, kpn, W, T = 8, 32, 2, 16
mesh = make_node_mesh(n_nodes)

# misconfiguration guard: an under-provisioned mesh is a loud error
try:
    make_node_mesh(9); raise AssertionError("expected ValueError (9 > 8)")
except ValueError: pass
# non-dividing key spaces PAD with empty rows instead of erroring
# (elastic-plane satellite): 100 keys on 8 nodes -> 104 physical rows,
# the 4 pad rows empty (tid == NO_TID), and a workload over the 100 real
# keys is bit-identical to the single-device run on the unpadded store
pad = shard_store(make_store(100, 4), mesh)
assert pad.head.shape[0] == 104, pad.head.shape
assert (np.asarray(pad.tid)[100:] == -1).all()
pw = smallbank_waves(np.random.RandomState(3), 2, 16, 4, 25,
                     dist_frac=0.5, hot_frac=0.5, hot_per_node=4)
pl_st, pl_h, pl_s = run_workload(make_store(100, 4), pw, sched="postsi",
                                 n_nodes=4)
pd_st, pd_h, pd_s = run_workload_dist(pad, pw, mesh, sched="postsi",
                                      n_nodes=4)
assert pl_s == pd_s, (pl_s, pd_s)
for (t1, o1), (t2, o2) in zip(pl_h, pd_h):
    for name, f1, f2 in zip(o1._fields, o1, o2):
        np.testing.assert_array_equal(f1, f2, err_msg=f"pad.{name}")
for name, f1, f2 in zip(pl_st._fields, pl_st, pd_st):
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2)[:100],
                                  err_msg=f"pad.store.{name}")
print("PAD-SHARD-OK rows:", pad.head.shape[0])

for sched in SCHEDULERS:
    waves = smallbank_waves(np.random.RandomState(7), W, T, n_nodes, kpn,
                            dist_frac=0.5, hot_frac=0.5, hot_per_node=4)
    hs = (np.array([0,1,1,2,0,1,2,0], np.int32) if sched == "clocksi"
          else None)
    st1, h1, s1 = run_workload(make_store(n_nodes*kpn, 8), waves,
                               sched=sched, n_nodes=n_nodes, host_skew=hs,
                               gc_track=True)
    st2, h2, s2 = run_workload_dist(
        shard_store(make_store(n_nodes*kpn, 8), mesh), waves, mesh,
        sched=sched, n_nodes=n_nodes, host_skew=hs, gc_track=True)
    st3, h3, s3 = run_workload_fused_dist(
        shard_store(make_store(n_nodes*kpn, 8), mesh), waves, mesh,
        sched=sched, n_nodes=n_nodes, host_skew=hs, gc_track=True)
    assert s1 == s2 == s3, (sched, s1, s2, s3)
    for (t1, o1), (t2, o2), (t3, o3) in zip(h1, h2, h3):
        np.testing.assert_array_equal(t1, t2)
        for name, f1, f2, f3 in zip(o1._fields, o1, o2, o3):
            np.testing.assert_array_equal(f1, f2,
                                          err_msg=f"{sched}.perwave.{name}")
            np.testing.assert_array_equal(f1, f3,
                                          err_msg=f"{sched}.fused.{name}")
    for name, f1, f2, f3 in zip(st1._fields, st1, st2, st3):
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2),
                                      err_msg=f"{sched}.store.{name}")
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f3),
                                      err_msg=f"{sched}.store.fused.{name}")
    print(f"DIST-{sched}-OK commits: {s1.committed} aborts: {s1.aborted}")
"""))


def test_dist_engine_hypothesis_differential():
    """Property: for random waves (mixed reads / blind writes / RMWs, random
    contention and distribution), LocalSubstrate and MeshSubstrate commit
    the same set with identical intervals under every drawn scheduler."""
    pytest.importorskip("hypothesis")
    print(_run(r"""
import numpy as np
from hypothesis import given, settings, strategies as st
from repro.core import SCHEDULERS, make_store, run_workload
from repro.core.dist_engine import (make_node_mesh, run_workload_dist,
                                    shard_store)
from repro.core.workloads import micro_waves

n_nodes, kpn, T = 4, 16, 12
mesh = make_node_mesh(n_nodes)

@settings(max_examples=10, deadline=None, derandomize=True)
@given(seed=st.integers(0, 2**16), sched=st.sampled_from(SCHEDULERS),
       read_ratio=st.sampled_from([0.2, 0.6]),
       blind_frac=st.sampled_from([0.0, 0.8]))
def check(seed, sched, read_ratio, blind_frac):
    waves = micro_waves(np.random.RandomState(seed), 1, T, n_nodes, kpn,
                        n_ops=3, read_ratio=read_ratio, dist_frac=0.5,
                        hot_frac=0.6, hot_per_node=2, blind_frac=blind_frac)
    hs = (np.array([0, 1, 0, 2], np.int32) if sched == "clocksi" else None)
    _, h1, s1 = run_workload(make_store(n_nodes*kpn, 4), waves, sched=sched,
                             n_nodes=n_nodes, host_skew=hs)
    _, h2, s2 = run_workload_dist(
        shard_store(make_store(n_nodes*kpn, 4), mesh), waves, mesh,
        sched=sched, n_nodes=n_nodes, host_skew=hs)
    assert s1 == s2, (sched, seed, s1, s2)
    for (t1, o1), (t2, o2) in zip(h1, h2):
        for name, f1, f2 in zip(o1._fields, o1, o2):
            np.testing.assert_array_equal(f1, f2,
                                          err_msg=f"{sched}/{seed}.{name}")

check()
print("DIST-HYPOTHESIS-OK")
"""))


def test_dist_engine_kernel_backends_bit_identical():
    """The kernel-backend plane on the mesh: all SEVEN schedulers (the six
    optimistic ones plus "planned") produce bit-identical WaveOut under
    ``jnp`` vs ``pallas_interpret``, three-dispatch vs fused megakernel, on
    the MeshSubstrate, per-wave AND scan-fused — and all match the
    LocalSubstrate (acceptance gate of the backend refactor; the
    version_scan / wave_commit kernels run on each node's local block
    inside shard_map).  The pallas_interpret configs must dispatch real
    (interpreted) Pallas on the mesh: the degrade counter stays ZERO."""
    print(_run(r"""
import numpy as np
from repro.core import SCHEDULERS, make_store, run_workload
from repro.core.dist_engine import (make_node_mesh, run_workload_dist,
                                    run_workload_fused_dist, shard_store)
from repro.core.substrate import mesh_degrade_count
from repro.core.workloads import smallbank_waves
from repro.planner import run_workload_planned

n_nodes, kpn, W, T = 4, 16, 2, 12
mesh = make_node_mesh(n_nodes)
CONFIGS = ("jnp", "pallas_interpret", "jnp+fused", "pallas_interpret+fused")

for sched in SCHEDULERS:
    waves = smallbank_waves(np.random.RandomState(13), W, T, n_nodes, kpn,
                            dist_frac=0.5, hot_frac=0.5, hot_per_node=4)
    hs = (np.array([0,1,1,2], np.int32) if sched == "clocksi" else None)
    ref = run_workload(make_store(n_nodes*kpn, 8), waves, sched=sched,
                       n_nodes=n_nodes, host_skew=hs, gc_track=True,
                       kernels="jnp")
    for bk in CONFIGS:
        for drv, runner in (("perwave", run_workload_dist),
                            ("fused", run_workload_fused_dist)):
            st, h, s = runner(shard_store(make_store(n_nodes*kpn, 8), mesh),
                              waves, mesh, sched=sched, n_nodes=n_nodes,
                              host_skew=hs, gc_track=True, kernels=bk)
            assert s == ref[2], (sched, bk, drv, s, ref[2])
            for (t1, o1), (t2, o2) in zip(ref[1], h):
                np.testing.assert_array_equal(t1, t2)
                for name, f1, f2 in zip(o1._fields, o1, o2):
                    np.testing.assert_array_equal(
                        f1, f2, err_msg=f"{sched}.{bk}.{drv}.{name}")
            for name, f1, f2 in zip(ref[0]._fields, ref[0], st):
                np.testing.assert_array_equal(
                    np.asarray(f1), np.asarray(f2),
                    err_msg=f"{sched}.{bk}.{drv}.store.{name}")
    print(f"DIST-BACKEND-{sched}-OK")

# the seventh scheduler: planned lane dispatch on the mesh, every config
waves = smallbank_waves(np.random.RandomState(29), 2, 12, n_nodes, kpn,
                        dist_frac=0.5, hot_frac=0.5, hot_per_node=3)
ref = None
for bk in CONFIGS:
    st, h, s = run_workload_planned(
        shard_store(make_store(n_nodes*kpn, 8), mesh), waves, sched="postsi",
        n_nodes=n_nodes, mesh=mesh, kernels=bk)
    assert s.aborted == 0, (bk, s)
    if ref is None:
        ref = (st, h, s)
        continue
    assert s._replace(plan_s=0) == ref[2]._replace(plan_s=0), (bk, s, ref[2])
    for (t1, o1), (t2, o2) in zip(ref[1], h):
        np.testing.assert_array_equal(t1, t2)
        for name, f1, f2 in zip(o1._fields, o1, o2):
            np.testing.assert_array_equal(f1, f2,
                                          err_msg=f"planned.{bk}.{name}")
    for name, f1, f2 in zip(ref[0]._fields, ref[0], st):
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2),
                                      err_msg=f"planned.{bk}.store.{name}")
print("DIST-BACKEND-planned-OK")

# degrade gate: no config above may have been served by a silent jnp
# fallback — pallas_interpret passes through shard_map as real
# (interpreted) Pallas, and only a true compiled-'pallas' request on a
# probe-failing platform is allowed to degrade (none was made here)
assert mesh_degrade_count() == 0, mesh_degrade_count()
print("DIST-DEGRADE-ZERO-OK")
"""))


def test_mesh_service_matches_single_device():
    """The sharded closed-loop service (TxnService(mesh=...), GC watermark
    merged by lax.pmin from per-node reader floors) serves the identical
    stream to the identical outcome as the single-device service, and the
    served history verifies."""
    print(_run(r"""
import numpy as np
from repro.core.dist_engine import make_node_mesh, mesh_watermark
from repro.core.workloads import poisson_arrivals
from repro.service import RetryPolicy, TxnService, smallbank_txn_gen

n_nodes, kpn, T = 8, 32, 16
mesh = make_node_mesh(n_nodes)
reports = []
for m in (None, mesh):
    svc = TxnService(n_keys=n_nodes*kpn, n_versions=8, T=T, sched="postsi",
                     n_nodes=n_nodes, retry=RetryPolicy(max_attempts=6),
                     seed=0, mesh=m)
    arr = poisson_arrivals(np.random.RandomState(100), 0.9*T, 8)
    gen = smallbank_txn_gen(np.random.RandomState(200), n_nodes, kpn,
                            dist_frac=0.3, hot_frac=0.6, hot_per_node=3)
    reports.append(svc.run_stream(arr, gen))
    assert svc.verify() == [], svc.verify()
    # decentralized watermark: pmin merge over per-node floors == host min
    h = svc.gc.pin(3, node=5)
    assert svc.gc.watermark() == mesh_watermark(
        mesh, svc.gc.node_floors(n_nodes))
    svc.gc.release(h)
a, b = reports
assert (a.committed, a.dropped, a.retries, a.waves, a.rejected) == \
       (b.committed, b.dropped, b.retries, b.waves, b.rejected), (a, b)
assert (a.latency_p50, a.latency_p95, a.latency_p99) == \
       (b.latency_p50, b.latency_p95, b.latency_p99)
print("MESH-SERVICE-OK committed:", a.committed)
"""))


def test_elastic_mesh_matches_static_theta099():
    """The elastic placement differential at the paper's hardest skew
    (zipf θ=0.99): the sharded service with a PlacementMap + live balancer
    moves commits the EXACT same request set with the EXACT same history as
    the static service on the identical stream — for all SEVEN schedulers
    (the six optimistic ones + planned lanes), and on both kernel backends
    for the representative pair.  Engine outcomes are placement-invariant
    by construction (slot translation is injective), so live repartitioning
    is invisible to concurrency control."""
    print(_run(r"""
import numpy as np
from repro.core.dist_engine import make_node_mesh
from repro.placement import PlacementMap
from repro.service import TxnService, ycsb_txn_gen

n_nodes, kpn, T = 8, 16, 16
n_keys = n_nodes * kpn
mesh = make_node_mesh(n_nodes)
SCHEDS = ("postsi", "cv", "si", "optimal", "dsi", "clocksi", "planned")

def serve(sched, placement, balancer, kernels):
    hs = (np.array([0,1,1,2,0,1,2,0], np.int32) if sched == "clocksi"
          else None)
    svc = TxnService(n_keys=n_keys, n_versions=8, T=T,
                     sched="postsi" if sched == "planned" else sched,
                     n_nodes=n_nodes, host_skew=hs, seed=0, mesh=mesh,
                     kernels=kernels,
                     planner="planned" if sched == "planned" else None,
                     placement=placement, balancer=balancer)
    gen = ycsb_txn_gen(np.random.RandomState(42), n_nodes, kpn, theta=0.99)
    svc.run_stream([12] * 4, gen)
    return svc

for sched in SCHEDS:
    backends = (("jnp", "pallas_interpret") if sched in ("postsi", "planned")
                else ("jnp",))
    for kernels in backends:
        a = serve(sched, None, None, kernels)
        b = serve(sched, PlacementMap(n_keys, n_nodes, headroom=2), True,
                  kernels)
        cs = lambda s: sorted(r.req_id for r in s.requests
                              if r.status == "committed")
        assert cs(a) == cs(b), (sched, kernels, len(cs(a)), len(cs(b)))
        assert len(a.history) == len(b.history), (sched, kernels)
        for (t1, o1), (t2, o2) in zip(a.history, b.history):
            np.testing.assert_array_equal(t1, t2)
            for name, f1, f2 in zip(o1._fields, o1, o2):
                np.testing.assert_array_equal(
                    f1, f2, err_msg=f"{sched}.{kernels}.{name}")
        if sched != "clocksi":   # skewed hosts read stale snapshots by
            # design (paper §II anomaly) — measured, not verified; the
            # bit-equality above already proves placement invariance
            assert b.verify() == [], (sched, b.verify())
        print(f"ELASTIC-{sched}-{kernels}-OK commits: {b.committed}",
              f"moves: {b.report().placement_moves}")
print("ELASTIC-DIFFERENTIAL-OK")
"""))


def test_elastic_mesh_replicas_check_and_recovery():
    """Three elastic-plane properties that need the real 8-device mesh:
    (1) hot-key replica reads on the sharded service never run ahead of the
    lax.pmin watermark and the served history verifies; (2) the
    REPRO_PLACEMENT_CHECK=1 debug gate detects a mis-routed placement
    BEFORE dispatch instead of silently corrupting reads; (3) a crashed
    durable elastic mesh service recovers bit-identically, replaying
    interleaved REC_MOVE + REC_BLOCK records."""
    print(_run(r"""
import numpy as np, os, tempfile
from repro.core import Wave, make_store
from repro.core.dist_engine import make_node_mesh, run_wave_dist, shard_store
from repro.core.workloads import zipf_hot_keys
from repro.placement import PlacementError, PlacementMap
from repro.service import TxnService, ycsb_txn_gen

n_nodes, kpn = 8, 16
n_keys = n_nodes * kpn
mesh = make_node_mesh(n_nodes)

# 1. replica staleness on the mesh: floor <= pmin watermark clock, always
hot = zipf_hot_keys(n_nodes, kpn, theta=0.99)
svc = TxnService(n_keys=n_keys, n_versions=8, T=16, sched="postsi",
                 n_nodes=n_nodes, seed=0, mesh=mesh,
                 placement=PlacementMap(n_keys, n_nodes, headroom=2),
                 replicas=hot, balancer=True)
gen = ycsb_txn_gen(np.random.RandomState(7), n_nodes, kpn, theta=0.99)
svc.run_stream([12] * 4, gen)
assert svc.verify() == [], svc.verify()
rep = svc.replicas
assert svc.replica_commits > 0
assert rep.max_cid() <= rep.floor <= svc.gc.clock
for r in svc.requests:
    if r.replica:
        assert r.s == r.c <= svc.gc.clock
print("MESH-REPLICA-OK replica_commits:", svc.replica_commits,
      "floor:", rep.floor, "clock:", svc.gc.clock)

# 2. REPRO_PLACEMENT_CHECK=1 catches a cross-node slot corruption
os.environ["REPRO_PLACEMENT_CHECK"] = "1"
pm_bad = PlacementMap(n_keys, n_nodes, headroom=1)
slot = pm_bad.slot.copy()
slot[0], slot[-1] = slot[-1], slot[0]        # key 0's ring on node 7's block
pm_bad.slot = slot
T = 8
wave = Wave(op_kind=np.ones((T, 2), np.int32),
            op_key=np.zeros((T, 2), np.int32),
            op_val=np.zeros((T, 2), np.int32), host=np.zeros(T, np.int32),
            tid=np.arange(1, T + 1, dtype=np.int32))
st = shard_store(make_store(n_keys, 4), mesh)
try:
    run_wave_dist(st, wave, 1, 1, mesh, sched="postsi", n_nodes=n_nodes,
                  placement=pm_bad.device_arrays())
    raise AssertionError("mis-routed placement not detected")
except PlacementError as e:
    print("PLACEMENT-CHECK-OK", str(e)[:60])
os.environ["REPRO_PLACEMENT_CHECK"] = "0"

# 3. durable elastic mesh service: crash -> recover bit-identically
from repro.durability.recovery import DurabilityManager, recover
d = tempfile.mkdtemp()
mgr = DurabilityManager(d, fsync_every=1, snapshot_every=2)
svc2 = TxnService(n_keys=n_keys, n_versions=8, T=16, sched="postsi",
                  n_nodes=n_nodes, seed=1, mesh=mesh,
                  placement=PlacementMap(n_keys, n_nodes, headroom=2),
                  balancer=True, durability=mgr)
svc2.run_stream([12] * 4,
                ycsb_txn_gen(np.random.RandomState(9), n_nodes, kpn,
                             theta=0.99))
moves = svc2.report().placement_moves
assert moves >= 1, moves
mgr.crash()
state = recover(d, mesh=mesh)
for name in svc2.store._fields:
    np.testing.assert_array_equal(np.asarray(getattr(svc2.store, name)),
                                  np.asarray(getattr(state.store, name)),
                                  err_msg=name)
np.testing.assert_array_equal(state.placement_map.slot, svc2.placement.slot)
np.testing.assert_array_equal(state.placement_map.owner, svc2.placement.owner)
print("MESH-MOVE-RECOVERY-OK moves:", moves, "replayed:", state.n_replayed,
      "of", state.n_records, "records")
"""))
