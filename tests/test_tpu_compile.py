"""Ahead-of-time compiles of the served path for a described TPU v5e.

The TPU compiler installed with JAX compiles for a ``v5e:2x2`` topology
that is described, not attached, so what Mosaic or XLA would refuse on the
chip — a slice not aligned to the tiling, more VMEM than a kernel may use,
a program larger than HBM — fails here, at no chip time.  Shapes are the
served path's real ones: T=256 waves, V=8 rings, a 2^23-key store.

Only one process may load the TPU library at a time, and every test
worker imports this file, so the topology is described inside a fixture
and never at import.  The persistent compilation cache is off around these
compiles: a TPU entry written here could not be read back without a chip.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine, substrate
from repro.core.dist_engine import _block_fn
from repro.core.engine import Wave
from repro.core.store import MVStore
from repro.kernels import KernelConfig, ops

T, V, B, O_SERVED = 256, 8, 4, 4
N_KEYS = 2 ** 23
N_SERVED = 3_000_000        # the chip benchmark's SmallBank: 3 tables x 1M
HBM_BYTES = 16e9            # one v5e chip (Google Cloud documentation)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs outside
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def restore():
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        restore()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _kernel_args(op, O, sh):
    """Argument shapes of one ``kernels.ops`` op for a [T, O] wave."""
    if op == "wave_commit":
        return ([_i32((T, O, V), sh)] * 4 + [_i32((T, O), sh)] * 3
                + [jax.ShapeDtypeStruct((T, O), jnp.bool_, sharding=sh)])
    if op == "potential_matrix":
        return [_i32((T, O), sh)] * 2
    return [_i32((T * O, V), sh)] * 2 + [_i32((T * O,), sh)]


@pytest.mark.parametrize("O", [8, 12])
@pytest.mark.parametrize("op", ["wave_commit", "potential_matrix",
                                "version_scan"])
def test_kernel_compiles_for_v5e(one_chip, op, O):
    compiled = getattr(ops, op).lower(
        *_kernel_args(op, O, one_chip), use_pallas=True,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _store(sh, n_keys=N_KEYS):
    return MVStore(*([_i32((n_keys, V), sh)] * 4 + [_i32((n_keys,), sh)] * 2))


def _block(sh, t=T):
    return Wave(*([_i32((B, t, O_SERVED), sh)] * 3 + [_i32((B, t), sh)] * 2))


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("kernels", ["pallas", "pallas+fused"])
def test_scan_block_compiles_for_v5e(one_chip, kernels):
    """The streaming service's block program, as ``engine.run_block``
    dispatches it, on one chip: Mosaic kernels inside, fits HBM."""
    scalar = _i32((), one_chip)
    compiled = engine._scan_block.lower(
        _store(one_chip), _block(one_chip), scalar, scalar, scalar, None,
        None, sched="postsi", gc_track=True,
        kernels=KernelConfig(kernels)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_hbm(compiled)


def _scoped_bytes(compiled, scope):
    """(largest scoped-memory size in bytes, op name) of each compiled op
    whose ``op_name`` lies under the named ``scope``."""
    found = []
    for line in compiled.as_text().splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        used = re.search(r'"used_scoped_memory_configs":(\[[^]]*\])', line)
        if name and used and f"/{scope}/" in name.group(1):
            sizes = [int(c["size"]) for c in json.loads(used.group(1))]
            found.append((max(sizes, default=0), line.split("=")[0].strip()))
    return found


@pytest.mark.parametrize("kernels", ["pallas", "pallas+fused"])
def test_commit_loop_stages_no_whole_key_array(one_chip, kernels):
    """The block program at the benchmark's store size: no op of the commit
    loop, which runs once per transaction, stages a whole per-key array
    (``store.head`` / ``store.wave``, 4 bytes a key) in scoped memory.
    A 1-D scatter into such an array does at this size; the install's
    single-element in-place updates do not."""
    scalar = _i32((), one_chip)
    compiled = engine._scan_block.lower(
        _store(one_chip, N_SERVED), _block(one_chip), scalar, scalar, scalar,
        None, None, sched="postsi", gc_track=True,
        kernels=KernelConfig(kernels)).compile()
    scoped = _scoped_bytes(compiled, "commit_loop")
    assert scoped, "no commit-loop op found in the compiled text"
    staged = [op for size, op in scoped if size >= 4 * N_SERVED]
    assert not staged, f"whole-array staging in the commit loop: {staged}"


def _commit_loop_ops(compiled):
    """Compiled operations whose ``op_name`` lies under ``commit_loop``."""
    return sum(1 for line in compiled.as_text().splitlines()
               if re.search(r'^\s*(ROOT )?%\S+ = .*op_name="[^"]*/commit_loop/',
                            line))


@pytest.mark.parametrize("kernels", ["pallas", "pallas+fused"])
def test_commit_loop_ops_do_not_grow_with_wave_width(one_chip, kernels):
    """The commit loop is one step body over the lanes of a segment, so the
    block program at the benchmark's store size compiles to as many
    commit-loop operations at T=256 as at T=128 (within 5% for the
    compiler's own choices); an unrolled per-transaction op would double
    them."""
    scalar = _i32((), one_chip)
    ops = {t: _commit_loop_ops(engine._scan_block.lower(
        _store(one_chip, N_SERVED), _block(one_chip, t), scalar, scalar,
        scalar, None, None, sched="postsi", gc_track=True,
        kernels=KernelConfig(kernels)).compile()) for t in (128, 256)}
    assert ops[128] > 0
    assert ops[256] <= 1.05 * ops[128], ops


@pytest.mark.parametrize("kernels", ["pallas", "pallas+fused"])
def test_mesh_block_compiles_for_v5e_2x2(topo, monkeypatch, kernels):
    """The sharded-store block program (``dist_engine.run_block_dist``)
    over four chips, 2^21 keys each: Mosaic kernels inside shard_map, peer
    collectives between chips, each chip's share fits its HBM."""
    # this process runs on the CPU, where the mesh path would degrade
    # 'pallas' to jnp; the compile targets the described TPU instead
    monkeypatch.setattr(substrate, "can_compile_pallas", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("node",))
    node, rep = NamedSharding(mesh, P("node")), NamedSharding(mesh, P())
    scalar = _i32((), rep)
    fn = _block_fn(mesh, "postsi", 0, True, False, KernelConfig(kernels))
    compiled = fn.lower(
        *_store(node), *_block(rep), scalar, scalar, scalar,
        _i32((1,), rep), scalar, _i32((0,), rep), _i32((0,), rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    assert _fits_hbm(compiled)
