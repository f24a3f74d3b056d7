"""The commit loop's schedule: one step per segment of key-disjoint
transactions (``engine.run_wave_on``, ``commit_phase.last_sharer`` /
``segment_end``).

A segment is the longest run of consecutive undecided transactions of
which no two share a key.  The oracle is the same step body over one-lane
segments ``[i, i + 1)`` in index order, which is the serial schedule by
definition: it is run by patching ``engine.segment_end`` to ``a + 1``.
Every ``WaveOut`` and store leaf must match bit for bit, for every
scheduler, GC mode and placement, on waves built to conflict.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import engine
from repro.core.commit_phase import NOP, READ, RMW, WRITE, last_sharer, \
    segment_end
from repro.core.engine import SCHEDULERS, Wave
from repro.core.store import PlacementArrays, make_store
from repro.core.substrate import LocalSubstrate

T, O, V, N_NODES, N_KEYS = 16, 4, 4, 4, 24


def _wave(rows, tid0, rng):
    """A [T, O] wave from ``rows``: one list of (kind, key) per txn,
    padded with NOP ops on key 0 and NOP txns."""
    kind = np.full((T, O), NOP, np.int32)
    key = np.zeros((T, O), np.int32)
    for i, ops in enumerate(rows):
        for j, (k, x) in enumerate(ops):
            kind[i, j], key[i, j] = k, x
    val = rng.integers(1, 9, (T, O)).astype(np.int32)
    host = rng.integers(0, N_NODES, T).astype(np.int32)
    tid = (tid0 + np.arange(T)).astype(np.int32)
    return Wave(*(jnp.asarray(a) for a in (kind, key, val, host, tid)))


def _random_rows(rng, hot):
    """Skewed random txns: most ops on a few hot keys."""
    rows = []
    for _ in range(T):
        n = rng.integers(0, O + 1)
        ks = np.where(rng.random(n) < 0.6, rng.choice(hot, n),
                      rng.integers(0, N_KEYS, n))
        rows.append([(int(rng.choice([READ, WRITE, RMW])), int(k))
                     for k in ks])
    return rows


def _waves(seed=0):
    """Waves built to conflict, each a case the segments must get right."""
    rng = np.random.default_rng(seed)
    cases = [
        # RMW chain on one key
        [[(RMW, 3)]] * 8 + [[(READ, 4), (RMW, 5)]],
        # read-read sharing of one version (PostSI's SID hand-over), then
        # a writer of it, then readers again
        [[(READ, 5)], [(READ, 5), (READ, 6)], [(WRITE, 5)], [(READ, 5)],
         [(READ, 6), (WRITE, 7)], [(RMW, 6)]],
        # a key twice inside one txn, beside NOP ops on key 0 and active
        # ops on key 0
        [[(RMW, 9), (WRITE, 9)], [(READ, 9), (RMW, 9)], [(WRITE, 0)],
         [(NOP, 0), (READ, 10)], [(RMW, 0), (RMW, 10)]],
        # ring wrap past V on a hot key, within the wave
        [[(WRITE, 7)]] * (2 * V + 1) + [[(READ, 7)]],
        # every txn shares a key: T segments
        [[(RMW, 1), (READ, 2 + i)] for i in range(T)],
        # no txn shares a key: one segment
        [[(READ, 2 * i), (RMW, 2 * i + 1)] for i in range(N_KEYS // 2)],
    ]
    hot = np.array([1, 3, 7, 9])
    cases += [_random_rows(rng, hot) for _ in range(4)]
    return [_wave(rows, 1 + w * T, rng) for w, rows in enumerate(cases)]


def _run(waves, sched, gc, placement):
    """All waves through one jitted scan of ``run_wave_on``, as the fused
    executor runs them; returns (store, WaveOut with a leading wave axis)."""
    sub = LocalSubstrate("jnp")
    stacked = Wave(*(jnp.stack(f) for f in zip(*waves)))
    hs = jnp.array([0, 1, 1, 2], jnp.int32) if sched == "clocksi" else None

    def body(carry, xs):
        st, clk = carry
        wave, w_idx = xs
        st, out, clk = engine.run_wave_on(
            sub, st, wave, w_idx, clk, N_NODES, sched=sched, host_skew=hs,
            watermark=jnp.int32(0) if gc != "off" else None,
            gc_track=gc == "track", gc_block=gc == "block",
            placement=placement)
        return (st, clk), out

    @jax.jit
    def run(store):
        (st, _), outs = lax.scan(body, (store, jnp.int32(1)),
                                 (stacked, jnp.arange(1, len(waves) + 1)))
        return st, outs

    return run(make_store(N_KEYS, V))


def _segments(wave):
    """Numpy reference: the segments of a wave, as (a, b) pairs."""
    kind, key = np.asarray(wave.op_kind), np.asarray(wave.op_key)
    keysets = [set(key[i][kind[i] != NOP]) for i in range(T)]
    out, a = [], 0
    while a < T:
        b, seen = a, set()
        while b < T and not keysets[b] & seen:
            seen |= keysets[b]
            b += 1
        out.append((a, b))
        a = b
    return out


@pytest.mark.parametrize("placed", [False, True], ids=["identity", "placed"])
@pytest.mark.parametrize("gc", ["off", "track", "block"])
@pytest.mark.parametrize("sched", SCHEDULERS)
def test_segments_match_the_serial_schedule(monkeypatch, sched, gc, placed):
    waves = _waves()
    placement = None
    if placed:
        slot = np.random.default_rng(1).permutation(N_KEYS).astype(np.int32)
        placement = PlacementArrays(jnp.asarray(slot % N_NODES),
                                    jnp.asarray(slot))
    st, outs = _run(waves, sched, gc, placement)
    monkeypatch.setattr(engine, "segment_end", lambda prev, a: a + 1)
    st1, outs1 = _run(waves, sched, gc, placement)
    for name in engine.WaveOut._fields:
        if name != "commit_steps":
            np.testing.assert_array_equal(
                np.asarray(getattr(outs, name)),
                np.asarray(getattr(outs1, name)), err_msg=name)
    for name, a, b in zip(st._fields, st, st1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"store.{name}")
    np.testing.assert_array_equal(np.asarray(outs1.commit_steps),
                                  [T] * len(waves))
    np.testing.assert_array_equal(np.asarray(outs.commit_steps),
                                  [len(_segments(w)) for w in waves])
    statuses = np.asarray(outs.status)
    assert (statuses != 0).all()                 # every txn decided
    if sched in ("si", "dsi", "clocksi", "optimal") or gc == "block":
        assert (statuses == 2).any()             # the waves do conflict


def test_segment_counts_of_the_built_waves():
    counts = [len(_segments(w)) for w in _waves()]
    assert counts[4] == T and counts[5] == 1
    assert counts[0] == 8               # the RMW chain: one txn a step
    assert max(counts) == T and min(counts) == 1


def _segments_of(wave):
    """The engine's segmenter, run to the end of the wave."""
    prev = last_sharer(wave.op_key, wave.op_kind != NOP)
    out, a = [], 0
    while a < T:
        b = int(segment_end(prev, a))
        out.append((a, b))
        a = b
    return out


@pytest.mark.parametrize("seed", range(6))
def test_segments_are_contiguous_key_disjoint_and_maximal(seed):
    rng = np.random.default_rng(100 + seed)
    hot = rng.choice(N_KEYS, 3, replace=False)
    wave = _wave(_random_rows(rng, hot), 1, rng)
    segs = _segments_of(wave)
    assert segs == _segments(wave)
    kind, key = np.asarray(wave.op_kind), np.asarray(wave.op_key)
    keysets = [set(key[i][kind[i] != NOP]) for i in range(T)]
    assert segs[0][0] == 0 and segs[-1][1] == T
    for (a, b), (a2, _) in zip(segs, segs[1:]):
        assert b == a2                                  # contiguous
    for a, b in segs:
        assert a < b
        seen = set()
        for i in range(a, b):                           # key-disjoint
            assert not keysets[i] & seen
            seen |= keysets[i]
        if b < T:                                       # maximal
            assert keysets[b] & seen


def test_nop_ops_never_conflict():
    """NOP ops carry key 0, as do active ops on key 0: only the active
    ones share it."""
    rng = np.random.default_rng(0)
    rows = [[(NOP, 0), (READ, 0)], [(NOP, 0)], [(WRITE, 5), (NOP, 0)],
            [(RMW, 0)], [(NOP, 0), (NOP, 0)]]
    wave = _wave(rows, 1, rng)
    prev = np.asarray(last_sharer(wave.op_key, wave.op_kind != NOP))
    assert prev[:5].tolist() == [-1, -1, -1, 0, -1]
    assert (prev[5:] == -1).all()                       # NOP txns
    assert _segments_of(wave) == [(0, 3), (3, T)]


def test_mesh_segments_match_the_serial_schedule():
    """The same on a sharded store: ``MeshSubstrate`` under ``shard_map``
    over four virtual devices, every scheduler, segments against one-lane
    segments.  The device count locks at JAX start-up, so a child python
    runs it."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _MESH_CHILD, root], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [f"{s}-OK" for s in SCHEDULERS]


_MESH_CHILD = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, sys.argv[1] + "/tests")
from test_commit_segments import N_KEYS, N_NODES, T, V, _segments, _waves
from repro.core import SCHEDULERS, engine, make_store
from repro.core import dist_engine as de

mesh = de.make_node_mesh(N_NODES)
waves = _waves()


def run(sched):
    hs = np.array([0, 1, 1, 2], np.int32) if sched == "clocksi" else None
    return de.run_workload_fused_dist(
        de.shard_store(make_store(N_KEYS, V), mesh), waves, mesh,
        sched=sched, host_skew=hs, gc_track=True, kernels="jnp")


seg = {s: run(s) for s in SCHEDULERS}
engine.segment_end = lambda prev, a: a + 1
de._scan_fn.cache_clear()
jax.clear_caches()
for sched in SCHEDULERS:
    st, h, _ = seg[sched]
    st1, h1, _ = run(sched)
    for w, ((_, o), (_, o1)) in enumerate(zip(h, h1)):
        for name in o._fields:
            if name != "commit_steps":
                np.testing.assert_array_equal(getattr(o, name),
                                              getattr(o1, name),
                                              err_msg=f"{sched}.{name}")
        assert o.commit_steps == len(_segments(waves[w])), sched
        assert o1.commit_steps == T, sched
    for name, a, b in zip(st._fields, st, st1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{sched}.store.{name}")
    print(f"{sched}-OK")
"""
