"""Executing a key-range move against the version store.

A move copies the full version rings of the moving keys from their old
physical slots to freshly-allocated slots inside the destination node's
block, then clears the sources to the empty state (``tid == NO_TID``
everywhere, so the freed rows answer no read and accept a later move-in).
Old and new slots are disjoint by construction — destinations were free —
so copy-then-clear is race-free in any order.

The move executes **under the GC watermark** like any writer: the service
only fires it at a block boundary, when no wave is in flight and every
retired reader's snapshot floor is at or below the current clock, so no
in-flight visibility computation can observe the half-moved state.  On the
mesh it is one ``shard_map`` program: a masked-answer + ``lax.psum``
gather of the source rows (the same peer-collective idiom as the read
phase) followed by owner-local masked scatters with OOB-dropped indices —
zero coordinator, like everything else on this mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.store import MVStore, NO_TID

from .map import MoveRecord

_N_STORE = len(MVStore._fields)
_EMPTY = {"val": 0, "tid": int(NO_TID), "cid": 0, "sid": 0,
          "head": 0, "wave": 0}


def _pad(slots: np.ndarray, m_pad: int) -> jnp.ndarray:
    """Pad a slot vector to ``m_pad`` with the ``-1`` sentinel (gathers see
    a non-owned row, scatters drop it) so the jitted mover retraces only on
    the padded size, not every move size."""
    out = np.full(m_pad, -1, np.int32)
    out[:slots.size] = slots
    return jnp.asarray(out)


def _pad_size(m: int) -> int:
    p = 8
    while p < m:
        p *= 2
    return p


def apply_move_local(store: MVStore, rec: MoveRecord) -> MVStore:
    """Single-device move: gather rings at old slots, scatter to new,
    clear sources to empty."""
    if rec.keys.size == 0:
        return store
    old = jnp.asarray(rec.old_slots)
    new = jnp.asarray(rec.new_slots)
    out = {}
    for name in MVStore._fields:
        a = getattr(store, name)
        out[name] = a.at[new].set(a[old]).at[old].set(_EMPTY[name])
    return MVStore(**out)


@functools.lru_cache(maxsize=None)
def _move_fn(mesh: Mesh):
    """Jitted shard_map mover; retraces per padded move size only."""

    def node_fn(*args):
        st = MVStore(*args[:_N_STORE])
        old, new = args[_N_STORE:]
        n_local = st.head.shape[0]
        base = lax.axis_index("node") * n_local
        lk_src = old - base
        mine_src = (old >= 0) & (lk_src >= 0) & (lk_src < n_local)
        gi = jnp.where(mine_src, lk_src, 0)
        # dropped scatter index: n_local is out of the local block, so
        # mode="drop" discards it (a plain clamp would corrupt the last row)
        si = jnp.where(mine_src, lk_src, n_local)
        lk_dst = new - base
        mine_dst = (new >= 0) & (lk_dst >= 0) & (lk_dst < n_local)
        di = jnp.where(mine_dst, lk_dst, n_local)
        out = []
        for name in MVStore._fields:
            a = getattr(st, name)
            rows = a[gi]
            mask = mine_src.reshape((-1,) + (1,) * (rows.ndim - 1))
            rows = lax.psum(jnp.where(mask, rows, 0), "node")
            out.append(a.at[di].set(rows, mode="drop")
                        .at[si].set(_EMPTY[name], mode="drop"))
        return tuple(out)

    return jax.jit(jax.shard_map(
        node_fn, mesh=mesh,
        in_specs=(P("node"),) * _N_STORE + (P(), P()),
        out_specs=(P("node"),) * _N_STORE,
        check_vma=False))


def apply_move_mesh(store: MVStore, rec: MoveRecord, mesh: Mesh) -> MVStore:
    """Mesh move as one shard_map program: psum gather of the source rings,
    owner-local scatter installs, owner-local source clears."""
    if rec.keys.size == 0:
        return store
    m_pad = _pad_size(rec.keys.size)
    out = _move_fn(mesh)(*store, _pad(rec.old_slots, m_pad),
                         _pad(rec.new_slots, m_pad))
    return MVStore(*out)


def apply_move(store: MVStore, rec: MoveRecord, mesh: Mesh | None = None
               ) -> MVStore:
    if mesh is None:
        return apply_move_local(store, rec)
    return apply_move_mesh(store, rec, mesh)


def move_payload(rec: MoveRecord, seq: int, clock: int) -> dict:
    """WAL payload for a REC_MOVE frame: the explicit arrays (replay never
    re-runs the allocator) plus the log position and the watermark clock
    the move executed under."""
    return {"seq": int(seq), "clock": int(clock),
            "lo": int(rec.lo), "hi": int(rec.hi), "dst": int(rec.dst),
            "keys": np.asarray(rec.keys, np.int32),
            "old_slots": np.asarray(rec.old_slots, np.int32),
            "new_slots": np.asarray(rec.new_slots, np.int32)}


def record_from_payload(payload: dict) -> MoveRecord:
    arr = lambda x: np.asarray(x, np.int32)
    return MoveRecord(int(payload["lo"]), int(payload["hi"]),
                      int(payload["dst"]), arr(payload["keys"]),
                      arr(payload["old_slots"]), arr(payload["new_slots"]))
