"""jit'd public wrappers around the Pallas kernels.

Each op takes ``use_pallas`` / ``interpret``:
  use_pallas=False          -> the pure-jnp oracle (ref.py) — what the models
                               and the CPU dry-run actually lower;
  use_pallas=True           -> pl.pallas_call, Mosaic on real TPU;
  use_pallas=True, interpret=True -> kernel body interpreted on CPU
                               (how the tests validate the kernels here).

Wrappers own all TPU alignment: head folding, GQA KV repetition, lane
padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import ref
from .flash_attention import flash_attention_pallas
from .interval_negotiate import potential_matrix_pallas
from .ssd_scan import ssd_scan_pallas
from .version_scan import version_scan_pallas
from .wave_commit import wave_commit_pallas


def _pad_to(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "interpret", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, use_pallas=False, interpret=False,
                    block_q=128, block_k=128):
    """q: [B, Sq, H, D]; k, v: [B, Sk, KH, D] -> [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    # fold heads; repeat KV across the GQA group (kernel-validation path; the
    # on-TPU variant maps kv blocks to head groups via the BlockSpec index map)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    if use_pallas:
        Dp = ((D + 127) // 128) * 128
        qp = _pad_to(qf, 128, 2)
        kp = _pad_to(kf, 128, 2)
        vp = _pad_to(vf, 128, 2)
        import math
        o = flash_attention_pallas(qp, kp, vp, causal=causal,
                                   block_q=min(block_q, Sq),
                                   block_k=min(block_k, kf.shape[1]),
                                   sm_scale=1.0 / math.sqrt(D),
                                   interpret=interpret)[:, :, :D]
    else:
        o = ref.attention_ref(qf, kf, vf, causal=causal)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_heads_per_group", "chunk",
                                             "use_pallas", "interpret"))
def ssd(x, dA, Bm, Cm, *, n_heads_per_group, chunk=128, use_pallas=False,
        interpret=False):
    """x: [BH, S, P]; dA: [BH, S]; Bm/Cm: [Bg, S, N] ->
    (y [BH, S, P], final state [BH, N, P])."""
    if use_pallas:
        return ssd_scan_pallas(x, dA, Bm, Cm,
                               n_heads_per_group=n_heads_per_group,
                               chunk=chunk, interpret=interpret)
    return ref.ssd_ref(x, dA, Bm, Cm, n_heads_per_group)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_m"))
def version_scan(cids, tids, max_cid, *, use_pallas=False, interpret=False,
                 block_m=256):
    """cids/tids: [M, V] int32; max_cid: [M] -> (slot [M], cid [M])."""
    if not use_pallas:
        return ref.version_scan_ref(cids, tids, max_cid)
    M = cids.shape[0]
    bm = min(block_m, M)
    cp = _pad_to(_pad_to(cids, 128, 1, value=-1), bm, 0, value=-1)
    tp = _pad_to(_pad_to(tids, 128, 1, value=-1), bm, 0, value=-1)
    mc = jnp.broadcast_to(max_cid[:, None], (M, 128))
    mc = _pad_to(mc, bm, 0)
    slot, best = version_scan_pallas(cp, tp, mc, block_m=bm,
                                     interpret=interpret)
    return slot[:M], best[:M]


# ---------------------------------------------------------------------------
# batched commit-phase data movement (jnp scatter/gather and single-element
# in-place updates: no Pallas variant; they live here so the substrate's
# whole data plane is kernel-plane ops and the engine body stays pure rule
# arithmetic over op outputs)
# ---------------------------------------------------------------------------

def sid_regather(sid, keys, slots):
    """Rule-4(a) input: re-gather the SIDs of previously read (key, slot)
    pairs — peers may have bumped them since the read phase.
    sid: [n_keys, V]; keys/slots: [...] -> [...]."""
    return sid[keys, slots]


def masked_install(val, tid, cid, sid, head, wave, *, mask, keys, values,
                   new_tid, new_cid, wave_idx):
    """Masked version install over a key batch (rule 4(c) CID stamping).

    Pushes a new ring version for every key with ``mask`` set: the slot after
    ``head`` is overwritten, SID resets to 0, ``head``/``wave`` advance.
    Keys are clamped before the ``head`` gather so masked/NOP keys (which may
    be negative padding) can never wrap to a real key; a key with ``mask``
    set must be in range.  Returns the six updated ring arrays.

    The four ``[n_keys, V]`` rings are written by one scatter each, with
    masked-off rows routed to an OOB sentinel and dropped.  ``head`` and
    ``wave`` are written by one single-element in-place update per op
    instead: on a TPU a scatter into a 1-D ``[n_keys]`` array stages the
    whole array in scoped memory wherever it fits there (at 3M keys, not at
    2^23), a full read and write of it per call, while a
    ``dynamic_update_slice`` touches one element.  Each op writes its
    clamped key's final tag: the new one where any op with ``mask`` set
    hits that key, else the one it had.  So masked-off ops and NOP keys
    change nothing, and duplicate keys end as the scatter leaves them
    (every op on one key carries the same ``h_new`` and ``wave_idx``).
    The final tags are computed before the writes, so the writes are
    independent of one another and need no read of the array between them.
    """
    n_keys, n_versions = val.shape
    k = jnp.clip(keys, 0, n_keys - 1)
    h_cur = head[k]
    h_new = (h_cur + 1) % n_versions
    k_install = jnp.where(mask, keys, n_keys)
    rows = (val.at[k_install, h_new].set(values, mode="drop"),
            tid.at[k_install, h_new].set(new_tid, mode="drop"),
            cid.at[k_install, h_new].set(new_cid, mode="drop"),
            sid.at[k_install, h_new].set(0, mode="drop"))
    k, m = k.ravel(), jnp.broadcast_to(mask, keys.shape).ravel()
    hit = ((k[:, None] == k[None, :]) & m[None, :]).any(axis=1)
    head_fin = jnp.where(hit, h_new.ravel(), h_cur.ravel())
    wave_fin = jnp.where(hit, jnp.broadcast_to(wave_idx, keys.shape).ravel(),
                         wave[k]).astype(wave.dtype)
    for j in range(k.shape[0]):
        head = _put(head, head_fin[j], k[j])
        wave = _put(wave, wave_fin[j], k[j])
    return rows + (head, wave)


def _put(a, x, i):
    """``a`` with element ``i`` (in range) set to ``x``, in place."""
    return lax.dynamic_update_slice(a, x[None], (i,),
                                    allow_negative_indices=False)


def masked_sid_bump(sid, tid, *, mask, keys, slots, expect_tid, s_val):
    """Rule-4(c) SID bump over a key batch: raise the SID of read versions to
    the reader's start time, guarded against ring slots recycled since the
    read (creator TID must still match).  Returns the updated sid array."""
    n_keys = sid.shape[0]
    ok = mask & (tid[keys, slots] == expect_tid)
    k_sid = jnp.where(ok, keys, n_keys)
    return sid.at[k_sid, slots].max(s_val, mode="drop")


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_t"))
def wave_commit(cids, tids, sids, vals, max_cid, read_key, write_key, rvalid,
                *, use_pallas=False, interpret=False, block_t=128):
    """Fused wave read phase: version-scan slot selection + selected-version
    gathers + PostSI rule-3 seed + anti-dependency build in ONE kernel
    launch (DESIGN.md §7; bodies shared with ``version_scan`` /
    ``potential_matrix``, validated bit-identical against their composition).

    cids/tids/sids/vals: [T, O, V] int32 gathered rings; max_cid/read_key/
    write_key: [T, O] int32 (-1 key sentinel = inactive op); rvalid: [T, O]
    bool — the s_lo0 seed mask (read AND owned, so the mesh substrate can
    pmax-merge per-node partial maxima).  Returns (slot, r_val, r_tid,
    r_cid, r_sid [T, O] int32, s_lo0 [T] int32, potential [T, T] int8).
    """
    if not use_pallas:
        return ref.wave_commit_ref(cids, tids, sids, vals, max_cid,
                                   read_key, write_key, rvalid)
    T, O, V = cids.shape
    assert V <= 128, V                 # ring fits one lane register
    bt = min(block_t, T)
    # rings: V -> 128 lanes, O -> 8 sublanes, T -> block multiple; padded
    # slots carry tid=-1 (never visible), padded rows/ops are sliced off
    pad3 = lambda a, v: _pad_to(_pad_to(_pad_to(a, 128, 2, value=v),
                                        8, 1, value=v), bt, 0, value=v)
    pad2 = lambda a, v: _pad_to(_pad_to(a, 8, 1, value=v), bt, 0, value=v)
    slot, r_val, r_tid, r_cid, r_sid, slo, pot = wave_commit_pallas(
        pad3(cids, 0), pad3(tids, -1), pad3(sids, 0), pad3(vals, 0),
        pad2(max_cid, 0), pad2(read_key, -1), pad2(write_key, -1),
        pad2(rvalid.astype(jnp.int32), 0), block_t=bt, interpret=interpret)
    return (slot[:T, :O], r_val[:T, :O], r_tid[:T, :O], r_cid[:T, :O],
            r_sid[:T, :O], slo[:T, 0], pot[:T, :T])


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_t"))
def potential_matrix(read_key, write_key, *, use_pallas=False, interpret=False,
                     block_t=128):
    """[T, O] read/write key sets -> [T, T] int8 anti-dependency candidates."""
    if not use_pallas:
        return ref.potential_matrix_ref(read_key, write_key)
    T = read_key.shape[0]
    bt = min(block_t, T)
    rk = _pad_to(read_key, bt, 0, value=-1)
    wk = _pad_to(write_key, bt, 0, value=-1)
    out = potential_matrix_pallas(rk, wk, block_t=bt, interpret=interpret)
    return out[:T, :T]
