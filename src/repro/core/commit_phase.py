"""Shared commit-phase rules + anti-dependency matrix build (DESIGN.md §7).

Both wave engines — the single-device `engine.py` and the shard_map
`dist_engine.py` — execute the exact same commit-phase arithmetic (the
paper's CV rules 5-6 and PostSI rules 3/4/5); only the data-plane
primitives differ (direct store indexing vs. gather+psum peer collectives).
This module is the single home of that replicated arithmetic so the two
engines cannot drift, and of the ``potential`` anti-dependency matrix build,
which it routes to the tiled Pallas kernel
(`repro.kernels.interval_negotiate.potential_matrix_pallas`) or the dense
jnp reference per a resolved ``kernels.backend.KernelConfig``.

Backend selection lives in ``repro.kernels.backend`` (env
``REPRO_KERNEL_BACKEND``, ``set_default_backend``, or a ``KernelConfig``
threaded through the substrate/engine); ``set_potential_backend`` /
``potential_backend`` survive as deprecated shims forwarding there.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from repro.kernels import backend as kernel_backend
from repro.kernels.backend import register_cache_clear  # re-export (compat)

# op kinds (one code per wave-op slot)
NOP, READ, WRITE, RMW = 0, 1, 2, 3
# txn status
RUNNING, COMMITTED, ABORTED = 0, 1, 2

POTENTIAL_BACKENDS = ("auto",) + kernel_backend.BACKENDS


def set_potential_backend(name: str) -> None:
    """Deprecated: forwards to ``kernels.backend.set_default_backend`` (the
    per-op global this shimmed is gone; one config now serves every op)."""
    warnings.warn(
        "set_potential_backend is deprecated; use "
        "repro.kernels.set_default_backend (process default) or thread a "
        "repro.kernels.KernelConfig through the engine/substrate",
        DeprecationWarning, stacklevel=2)
    kernel_backend.set_default_backend(name)


def potential_backend() -> str:
    """Deprecated alias of ``kernels.backend.default_backend``."""
    return kernel_backend.default_backend()


# ---------------------------------------------------------------------------
# potential[i, j] = "txn i read a key that txn j writes"
# ---------------------------------------------------------------------------

def build_potential(keys, is_read, is_write, backend=None):
    """Anti-dependency candidates for one wave: bool [T, T].

    keys: [T, O] int32 op keys (>= 0 where active); is_read / is_write:
    [T, O] bool op masks.  ``backend`` is anything ``kernels.backend.resolve``
    accepts — a resolved ``KernelConfig``, a backend name, or ``None`` for
    the process default.  All routes are bit-identical; the jnp body lives
    ONLY in ``kernels.ref.potential_matrix_ref`` (the test oracle), so there
    is exactly one copy of the rule per backend.
    """
    cfg = kernel_backend.resolve(backend)
    rk = jnp.where(is_read, keys, -1)
    wk = jnp.where(is_write, keys, -1)
    if not cfg.use_pallas:
        from repro.kernels import ref
        return ref.potential_matrix_ref(rk, wk).astype(bool)
    from repro.kernels import ops
    out = ops.potential_matrix(rk, wk, use_pallas=True,
                               interpret=cfg.interpret)
    return out.astype(bool)


# ---------------------------------------------------------------------------
# the commit loop's schedule: segments of key-disjoint transactions
# ---------------------------------------------------------------------------

def last_sharer(keys, active):
    """prev[i]: the last txn j < i with an active op on a key that an active
    op of txn i also touches, or -1.  keys: [T, O] logical (or physical:
    the slot map is injective) op keys; active: [T, O] bool, the non-NOP
    ops (NOP keys are padding and never conflict)."""
    T = keys.shape[0]
    same = ((keys[:, :, None, None] == keys[None, None, :, :])
            & active[:, :, None, None] & active[None, None, :, :])
    share = same.any(axis=(1, 3))                               # [T, T]
    lane = jnp.arange(T)
    return jnp.where(share & (lane[None, :] < lane[:, None]),
                     lane[None, :], -1).max(axis=1)


def segment_end(prev, a):
    """End (exclusive) of the segment starting at txn ``a``: the first txn
    ``i > a`` that shares a key with a txn of ``[a, i)`` (``prev[i] >= a``),
    else T.  No two txns of ``[a, end)`` share a key, and the segment is
    maximal."""
    lane = jnp.arange(prev.shape[0])
    return jnp.where((lane > a) & (prev >= a), lane, prev.shape[0]).min()


# ---------------------------------------------------------------------------
# commit-phase arithmetic shared by engine.py and dist_engine.py, over the
# lanes of one segment: a txn's inputs are its row ([T, O] -> [T])
# ---------------------------------------------------------------------------

def creator_slots(nv_tid, tid0, n_txns, status):
    """Map newest-version creator TIDs to wave-local txn ids.

    Returns (local int32, creator_committed bool), shaped like ``nv_tid``:
    local is -1 for creators from older waves (their versions are settled
    and never block a same-wave commit)."""
    local = nv_tid - tid0
    local = jnp.where((local >= 0) & (local < n_txns), local, -1)
    committed = jnp.where(
        local >= 0, status[jnp.maximum(local, 0)] == COMMITTED, False)
    return local, committed


def lost_update(r, w, nv_cid, r_cid):
    """CV rule 5(i): an RMW whose read version is no longer newest."""
    return (r & w & (nv_cid != r_cid)).any(axis=-1)


def rw_edge_to_creator(w, local, creator_committed, potential):
    """CV rule 5(ii): the newest creator of a key I write has an rw edge
    from me (I read data it overwrote) -> it is invisible to me -> I cannot
    overwrite its version.  ``potential`` holds each txn's row."""
    edge = jnp.take_along_axis(potential, jnp.maximum(local, 0), axis=-1)
    return (w & (local >= 0) & creator_committed & edge).any(axis=-1)


def ongoing_readers(potential, status):
    """[T, T] mask: row i holds the still-RUNNING txns that read a key txn i
    writes (self off)."""
    T = status.shape[0]
    return potential.T & (status == RUNNING)[None, :] & ~jnp.eye(T,
                                                                 dtype=bool)


def postsi_bounds(s_lo_i, s_hi_i, c_lo_i, r_i, w_i, nv_cid, nv_sid, cur_sid,
                  ongoing_reader, s_lo):
    """PostSI rules 3/4(a)/5 for each committing txn i.

    Inputs: current bounds (s_lo_i, s_hi_i, c_lo_i [T]), op masks r_i/w_i
    [T, O], newest-version cid/sid over i's keys (nv_cid/nv_sid [T, O]),
    re-gathered SIDs of i's read slots (cur_sid [T, O] — peers may have
    bumped them while i ran), ongoing_reader [T, T] (``ongoing_readers``)
    and the wave s_lo vector [T].
    Returns (s_i, c_i, interval_abort), each [T]."""
    w_cid_max = jnp.where(w_i, nv_cid, 0).max(axis=-1)
    # rule 3 for overwrites: creators of overwritten versions must be visible
    s_lo_i = jnp.maximum(s_lo_i, w_cid_max)
    c_lo_i = jnp.maximum(c_lo_i, w_cid_max)
    # rule 4(a): commit time above SIDs of read versions ...
    c_lo_i = jnp.maximum(c_lo_i, jnp.where(r_i, cur_sid, 0).max(axis=-1))
    # ... and above SIDs of versions we *overwrite* (blind writes): SID
    # passes committed readers' start times to later writers
    c_lo_i = jnp.maximum(c_lo_i, jnp.where(w_i, nv_sid, 0).max(axis=-1))
    # ... and above s_lo of every ongoing reader of my write set
    c_lo_i = jnp.maximum(c_lo_i,
                         jnp.where(ongoing_reader, s_lo, 0).max(axis=-1))
    # rule 5: no valid start time left
    interval_abort = s_lo_i > s_hi_i
    s_i = s_lo_i
    c_i = jnp.maximum(c_lo_i, s_i) + 1
    return s_i, c_i, interval_abort


def push_bounds(commit, s_i, c_i, potential, status, s_lo, s_hi, c_lo):
    """PostSI rule 4(b): each committing txn pushes the interval bounds of
    every conflicting *ongoing* transaction (replicated arithmetic —
    identical on every node of the dist engine).  Pushes are a max and a
    min, so the committers of one segment, none of which pushes another,
    push in any order to the same bounds as one after the other."""
    running = status == RUNNING
    big = jnp.iinfo(jnp.int32).max
    # me -rw-> them: raise their c_lo above my start
    i_reads_them = commit[:, None] & potential
    c_push = jnp.where(i_reads_them, s_i[:, None] + 1, 0).max(axis=0)
    c_lo = jnp.where(running, jnp.maximum(c_lo, c_push), c_lo)
    # them -rw-> me: cap their s_hi below my commit
    they_read_mine = commit[None, :] & potential
    s_push = jnp.where(they_read_mine, c_i[None, :] - 1, big).min(axis=1)
    s_hi = jnp.where(running, jnp.minimum(s_hi, s_push), s_hi)
    s_lo = jnp.where(commit, s_i, s_lo)
    return s_lo, s_hi, c_lo
