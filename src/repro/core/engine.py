"""Vectorized wave-execution engine for all six schedulers.

The paper's asynchronous shared-nothing execution is mapped onto *waves*
(DESIGN.md §2): a wave is a batch of transactions whose lifespans all overlap
— they read the wave-start snapshot in parallel, keep write sets private
(paper §IV-C) and then commit in a deterministic order, which is where the
paper's rules fire (one loop step commits a segment of consecutive
transactions no two of which share a key, with the same outcome as one by
one; DESIGN.md §2):

  read phase   — CV rule 4 / PostSI §IV-B CID visibility + PostSI rule 3
                 (raise s_lo/c_lo to the CID of every version read),
  commit phase — CV rules 5-6 (write validation, anti-dependency capture) and
                 PostSI rule 4 (a: pick own interval from SIDs + ongoing
                 readers' s_lo; b: push bounds of conflicting ongoing txns;
                 c: stamp CIDs, bump SIDs) and rule 5 (abort on s_lo > s_hi).

The anti-dependency table is the dense boolean matrix ``potential[i, j]`` =
"txn i read a key that txn j writes"; an edge *exists* (paper's table entry)
once j commits, and is consulted only while i/j are ongoing — committed
readers hand over via SIDs exactly as in the paper.

Schedulers:
  postsi   — the paper's contribution (decentralized, negotiated intervals)
  cv       — Consistent Visibility only (no interval induction)
  si       — conventional SI: central coordinator allocates snapshots
             (2 coordinator round-trips per txn, counted)
  optimal  — conventional procedure minus all coordination (upper bound;
             not guaranteed correct, per the paper)
  dsi      — incremental-snapshot DSI: coordinator involved for distributed
             txns; remote-read snapshot mismatch aborts
  clocksi  — loosely synchronized per-node clocks with ``skew`` (in waves);
             behind-host txns read stale snapshots, ahead-remote reads wait

Drivers (DESIGN.md §7): ``run_workload_fused`` stacks a whole workload into
[W, T, O] batches and executes it as ONE device program — a single
``lax.scan`` over waves carrying (store, clock), no per-wave host round
trips.  ``run_workload`` dispatches one jitted wave at a time and syncs each
WaveOut to host; it is kept as the debug/differential path and the fused
executor is bit-identical to it (tests/test_fused_executor.py).

The commit-phase arithmetic (rules 3/4/5, the ``potential`` matrix build)
lives in ``commit_phase`` and is shared with the shard_map engine in
``dist_engine.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import KernelConfig, register_cache_clear, resolve
from .commit_phase import (ABORTED, COMMITTED, NOP, READ, RMW, RUNNING, WRITE,
                           creator_slots, last_sharer, lost_update,
                           ongoing_readers, postsi_bounds, push_bounds,
                           rw_edge_to_creator, segment_end)
from .store import (INF, MVStore, PlacementArrays, as_placement_arrays,
                    node_of_key)
from .substrate import LocalSubstrate

SCHEDULERS = ("postsi", "cv", "si", "optimal", "dsi", "clocksi")
WAVE_STRIDE = 1 << 16      # logical clock stride per wave for clocked baselines


class Wave(NamedTuple):
    op_kind: jax.Array    # [T, O] int32
    op_key: jax.Array     # [T, O] int32
    op_val: jax.Array     # [T, O] int32
    host: jax.Array       # [T] int32 host node per txn
    tid: jax.Array        # [T] int32 global tids (unique, > 0)


class WaveOut(NamedTuple):
    status: jax.Array     # [T] RUNNING/COMMITTED/ABORTED
    s: jax.Array          # [T] final start time
    c: jax.Array          # [T] final commit time
    read_key: jax.Array   # [T, O] (-1 where not a read)
    read_cid: jax.Array   # [T, O]
    write_key: jax.Array  # [T, O] (-1 where not a write)
    write_cid: jax.Array  # [T, O] cid stamped on installed versions
    # stats
    msgs_cross: jax.Array  # scalar: cross-node data/negotiation messages
    msgs_coord: jax.Array  # scalar: messages through the central coordinator
    waits: jax.Array       # scalar: clock-si skew waits
    evicted_visible: jax.Array  # scalar: ring-slot reuses of still-visible
                                # versions (GC watermark violations, §8)
    commit_steps: jax.Array  # scalar: commit-loop steps (one per segment)


def run_wave_on(sub, store: MVStore, wave: Wave, wave_idx: jax.Array,
                clock: jax.Array, n_nodes: jax.Array = 8,
                sched: str = "postsi", skew: int = 0,
                host_skew: jax.Array | None = None,
                watermark: jax.Array | None = None, gc_track: bool = False,
                gc_block: bool = False,
                placement: PlacementArrays | None = None,
                ) -> Tuple[MVStore, WaveOut, jax.Array]:
    """Execute one wave on a data-access substrate (DESIGN.md §4).

    This function is the ONLY copy of the concurrency-control rules for all
    six schedulers; every data-plane access (read-phase lookup, commit-phase
    re-validation read, version install, SID bump, GC watermark consult)
    goes through ``sub`` — ``substrate.LocalSubstrate`` under the jitted
    single-device ``run_wave`` below, or ``substrate.MeshSubstrate`` inside
    the ``shard_map`` bodies of ``dist_engine``, which is how one commit
    loop serves every placement.  Pure trace-level function: callers own
    jit / shard_map / scan wrapping.  Returns (store', out, clock').

    ``placement`` (elastic routing, DESIGN.md §11): when given, logical
    keys are translated ONCE here — ``pkeys = slot[key]`` is the physical
    store row every substrate access uses.  Placement changes WHERE a ring
    lives, never WHAT the schedulers decide: the locality model the rules
    and message stats consult (dsi remoteness, clocksi node skew,
    msgs_cross) stays the logical ``key % n_nodes``, so any injective slot
    map — including one that changes mid-stream via range moves — yields
    bit-identical statuses/timestamps/history to ``placement=None``.  That
    invariance is what makes live repartitioning a pure data-plane
    operation (and what the static-vs-elastic differentials pin).
    Placement-aware load/occupancy accounting is host-side, from
    ``PlacementMap.owner`` (repro.placement).  Everything the caller sees
    (``read_key``/``write_key``, statuses, timestamps) stays LOGICAL.

    Commits follow wave-local index order, one loop step per *segment*:
    the longest run of consecutive undecided transactions of which no two
    share a key (``commit_phase.last_sharer`` / ``segment_end``), committed
    by one step vectorised over its lanes.  A transaction's commit reads
    and writes only the store rows of its own keys, the bounds and status
    of transactions sharing a key with it, and the clock; so no lane of a
    segment sees another's effects, rule 4(b)'s pushes are a max and a min,
    and the clocked schedulers' ``c = clock + 1`` chain is a count of the
    segment's earlier commits.  Outcomes and store are bit-identical to one
    transaction a step (tests/test_commit_segments.py); the step count is
    ``WaveOut.commit_steps``."""
    assert sched in SCHEDULERS, sched
    T, O = wave.op_kind.shape
    clock0 = clock          # wave-entry clock = snapshot time for clocked scheds
    track_gc = gc_track or gc_block
    wm = clock if watermark is None else watermark
    with jax.named_scope("read_phase"):
        is_read = (wave.op_kind == READ) | (wave.op_kind == RMW)
        is_write = (wave.op_kind == WRITE) | (wave.op_kind == RMW)
        keys = wave.op_key
        if placement is None:
            pkeys = keys                                   # slot[k] == k
        else:
            nk = placement.slot.shape[0]
            kc = jnp.clip(keys, 0, nk - 1)
            # negative NOP sentinels pass through untranslated — the substrates'
            # sentinel-drop / clamp handling must keep seeing them
            pkeys = jnp.where(keys >= 0, placement.slot[kc], keys)

        # ------------------------------------------------------------------ reads
        if sched == "clocksi":
            hs = host_skew if host_skew is not None else jnp.zeros((1,), jnp.int32)
            my_skew = hs[wave.host]                                   # [T]
            cutoff_wave = wave_idx - my_skew                          # snapshot wave
            # visible: newest version whose wave tag < cutoff (stale snapshot)
            key_wave, head_cid = sub.key_staleness(store, pkeys)      # [T,O] each
            stale = key_wave >= cutoff_wave[:, None]
            max_cid = jnp.where(stale, head_cid - 1, INF)
        else:
            max_cid = jnp.broadcast_to(jnp.int32(INF), keys.shape)

        # the whole read phase — slot selection, the PostSI rule-3 seed (raise
        # s_lo/c_lo to the CID of every version read) and the anti-dependency
        # candidate build — is one substrate call, so the fused ``wave_commit``
        # megakernel and the three-dispatch route swap under the engine without
        # the rules seeing a difference (DESIGN.md §7)
        # the potential matrix only needs key EQUALITY, which the injective slot
        # map preserves — so building it over pkeys is identical to logical keys
        (r_val, r_tid, r_cid, r_sid, r_slot, s_lo0,
         potential) = sub.read_phase(store, pkeys, max_cid, is_read, is_write)

        read_key = jnp.where(is_read, keys, -1)
        read_cid = jnp.where(is_read, r_cid, -1)
        c_lo0 = s_lo0
        s_hi0 = jnp.full((T,), INF, jnp.int32)

    # --------------------------------------------------------------- commits
    # deterministic commit order = wave-local index (tids ascend within wave),
    # one step per segment [a, b) of key-disjoint transactions (docstring):
    # every rule is evaluated for every lane, and the lanes of [a, b) decide
    with jax.named_scope("commit_loop"):
        with jax.named_scope("segment"):
            prev = last_sharer(keys, wave.op_kind != NOP)
            b0 = segment_end(prev, 0)
        lane = jnp.arange(T)
        tid_b = jnp.broadcast_to(wave.tid[:, None], (T, O))
        val_new = jnp.where(wave.op_kind == RMW, r_val + wave.op_val,
                            wave.op_val)

    def commit_segment(carry):
        (a, b, steps, st, s_lo, s_hi, c_lo, status, s_arr, c_arr, wcid, clk,
         ev_cnt) = carry
        with jax.named_scope("newest"):
            seg = (lane >= a) & (lane < b)
            nv_val, nv_tid, nv_cid, nv_sid, nv_slot = sub.read_newest(st,
                                                                      pkeys)
            # map newest creators to wave-local ids (or -1 if older wave)
            local, creator_committed = creator_slots(nv_tid, wave.tid[0], T,
                                                     status)

        with jax.named_scope("validate"):
            # lost update: an RMW whose read version is no longer newest
            lost = lost_update(is_read, is_write, nv_cid, r_cid)
            # CV rule 5(ii): newest creator has an rw edge from me (I read
            # data it overwrote) -> it is invisible to me -> cannot overwrite
            # its version
            if sched in ("postsi", "cv"):
                rw_to_creator = rw_edge_to_creator(
                    is_write, local, creator_committed, potential)
            else:
                rw_to_creator = jnp.array(False)

            if sched in ("si", "dsi", "clocksi", "optimal"):
                # first-committer-wins: any write over a same-wave commit
                # aborts
                ww_conc = (is_write & (local >= 0) & creator_committed).any(
                    axis=-1)
            else:  # postsi / cv may overwrite a committed peer (Fig.1 t2/t3)
                ww_conc = jnp.array(False)

            abort = lost | rw_to_creator | ww_conc

            if sched == "dsi":
                # incremental snapshot: a *remote* read whose key was
                # meanwhile overwritten implies a local/global timestamp
                # mismatch -> abort
                remote = node_of_key(keys, n_nodes) != wave.host[:, None]
                stale_remote = (is_read & remote & (nv_cid != r_cid)).any(
                    axis=-1)
                abort = abort | stale_remote

            if sched == "postsi":
                # rules 3/4(a)/5 (commit_phase.postsi_bounds); SIDs of read
                # slots are re-gathered: peers may have bumped them while we
                # ran
                cur_sid = sub.read_sid(st, pkeys, r_slot)
                s_i, c_i, iv_abort = postsi_bounds(
                    s_lo, s_hi, c_lo, is_read, is_write, nv_cid, nv_sid,
                    cur_sid, ongoing_readers(potential, status), s_lo)
                abort = abort | iv_abort
            else:
                # clocked baselines: snapshot = wave-entry clock
                s_i = jnp.broadcast_to(clock0, (T,))

            # GC watermark consult (DESIGN.md §8): does any write reuse a
            # ring slot whose version is still visible above the watermark?
            if track_gc:
                evict_unsafe = is_write & sub.evicting_visible(st, pkeys, wm)
            if gc_block:
                # blocked install: abort instead of corrupting still-visible
                # reads; retried once the watermark passes the superseder
                abort = abort | evict_unsafe.any(axis=-1)

            commit = seg & ~abort
            if sched != "postsi":
                # commit = clock++, committer by committer
                c_i = clk + jnp.cumsum(commit, dtype=jnp.int32)

        # ---- install writes (masked scatter; owner/OOB handling is the
        # substrate's concern: sentinel-drop locally, owner-only on the mesh)
        with jax.named_scope("install"):
            wmask = is_write & commit[:, None]
            c_b = jnp.broadcast_to(c_i[:, None], (T, O))
            st = sub.install(st, wmask, pkeys, val_new, tid_b, c_b, wave_idx)
            wcid = jnp.where(wmask, c_b, wcid)

        # ---- rule 4(c): bump SIDs of read versions to my start time --------
        # guarded: skip if the ring slot was recycled since our wave-start read
        with jax.named_scope("bump_sid"):
            st = sub.bump_sid(st, is_read & commit[:, None], pkeys, r_slot,
                              r_tid, jnp.broadcast_to(s_i[:, None], (T, O)))

        # ---- rule 4(b): push bounds of conflicting *ongoing* transactions --
        if sched == "postsi":
            with jax.named_scope("push_bounds"):
                s_lo, s_hi, c_lo = push_bounds(commit, s_i, c_i, potential,
                                               status, s_lo, s_hi, c_lo)

        with jax.named_scope("record"):
            status = jnp.where(seg, jnp.where(abort, ABORTED, COMMITTED),
                               status)
            s_arr = jnp.where(commit, s_i, s_arr)
            c_arr = jnp.where(commit, c_i, c_arr)
            clk = jnp.maximum(clk, jnp.where(commit, c_i, clk).max())
            if track_gc:
                ev_cnt = ev_cnt + jnp.where(commit[:, None], evict_unsafe,
                                            False).sum(dtype=jnp.int32)
            # the next segment starts where this one ends
            with jax.named_scope("segment"):
                a, b = b, segment_end(prev, b)
        return (a, b, steps + 1, st, s_lo, s_hi, c_lo, status, s_arr, c_arr,
                wcid, clk, ev_cnt)

    with jax.named_scope("commit_loop"):
        status0 = jnp.full((T,), RUNNING, jnp.int32)
        s0 = jnp.full((T,), -1, jnp.int32)
        c0 = jnp.full((T,), -1, jnp.int32)
        wcid0 = jnp.full((T, O), -1, jnp.int32)

        (_, _, commit_steps, store, s_lo, s_hi, c_lo, status, s_arr, c_arr,
         wcid, clock, evicted) = lax.while_loop(
            lambda carry: carry[0] < T, commit_segment,
            (jnp.int32(0), b0, jnp.int32(0), store, s_lo0, s_hi0, c_lo0,
             status0, s0, c0, wcid0, clock, jnp.int32(0)))

        write_key = jnp.where(is_write & (status[:, None] == COMMITTED), keys, -1)

    # ------------------------------------------------------------------ stats
    # work delegation batches per (txn, remote node) pair (paper §IV-A), so
    # cross-node messages count DISTINCT remote nodes touched, not raw ops
    with jax.named_scope("message_stats"):
        MAX_NODES = 32
        op_node = node_of_key(keys, n_nodes)                               # [T,O]
        active_op = wave.op_kind != NOP
        node_ids = jnp.arange(MAX_NODES)[None, None, :]
        touch = (op_node[..., None] == node_ids) & active_op[..., None]    # [T,O,MN]
        node_touched = touch.any(axis=1)                                   # [T,MN]
        remote_mask = jnp.arange(MAX_NODES)[None, :] != wave.host[:, None]
        remote_nodes = (node_touched & remote_mask)
        msgs_cross = remote_nodes.sum()
        remote_op = (op_node != wave.host[:, None]) & active_op
        committed = status == COMMITTED
        if sched == "postsi":
            # negotiation: one message per DISTINCT peer host per committer
            edge = potential & committed[None, :]
            peer_host_hot = (wave.host[None, :, None] == node_ids) & edge[:, :, None]
            peer_hosts = peer_host_hot.any(axis=1)                         # [T,MN]
            cross_peer = peer_hosts & (jnp.arange(MAX_NODES)[None, :] != wave.host[:, None])
            msgs_cross = msgs_cross + cross_peer.sum()
            msgs_coord = jnp.int32(0)
        elif sched == "cv":
            # anti-dependency entries stored on both endpoint hosts (§IV-A):
            # insertion crosses hosts like PostSI negotiation ...
            edge = potential & committed[None, :]
            peer_host_hot = (wave.host[None, :, None] == node_ids) & edge[:, :, None]
            peer_hosts = peer_host_hot.any(axis=1)
            cross_peer = peer_hosts & (jnp.arange(MAX_NODES)[None, :] != wave.host[:, None])
            msgs_cross = msgs_cross + cross_peer.sum()
            # ... and reads consult the table on remote hosts (paper §V-D):
            # batched per (txn, remote node) visited for reading
            read_touch = (op_node[..., None] == node_ids) & (is_read & active_op)[..., None]
            read_nodes = (read_touch.any(axis=1) & remote_mask)
            msgs_cross = msgs_cross + read_nodes.sum()
            msgs_coord = jnp.int32(0)
        elif sched == "si":
            msgs_coord = jnp.int32(2 * T)                  # begin + end, per txn
        elif sched == "dsi":
            distributed = remote_op.any(axis=1)
            msgs_coord = 2 * distributed.sum()             # global txns pay globally
        elif sched == "clocksi":
            msgs_coord = jnp.int32(0)
        else:  # optimal
            msgs_coord = jnp.int32(0)

        waits = jnp.int32(0)
        if sched == "clocksi" and host_skew is not None:
            # ahead-snapshot reads on behind remote nodes must wait (paper §II)
            node_skew = host_skew[node_of_key(keys, n_nodes)]
            my_skew = host_skew[wave.host][:, None]
            waits = jnp.maximum(node_skew - my_skew, 0).sum(where=remote_op & is_read)

    out = WaveOut(status, s_arr, c_arr, read_key, read_cid, write_key, wcid,
                  msgs_cross, msgs_coord, waits, evicted, commit_steps)
    return store, out, clock


@functools.partial(jax.jit,
                   static_argnames=("sched", "skew", "gc_track", "gc_block",
                                    "kernels"))
def _run_wave_jit(store, wave, wave_idx, clock, n_nodes, sched, skew,
                  host_skew, watermark, gc_track, gc_block,
                  kernels: KernelConfig, placement=None):
    return run_wave_on(LocalSubstrate(kernels), store, wave, wave_idx, clock,
                       n_nodes, sched=sched, skew=skew, host_skew=host_skew,
                       watermark=watermark, gc_track=gc_track,
                       gc_block=gc_block, placement=placement)


def run_wave(store: MVStore, wave: Wave, wave_idx: jax.Array, clock: jax.Array,
             n_nodes: jax.Array = 8, sched: str = "postsi", skew: int = 0,
             host_skew: jax.Array | None = None,
             watermark: jax.Array | None = None, gc_track: bool = False,
             gc_block: bool = False,
             kernels: KernelConfig | str | None = None,
             placement=None) -> Tuple[MVStore, WaveOut, jax.Array]:
    """Execute one wave single-device. Returns (store', out, clock').
    ``n_nodes`` is traced, so scaling sweeps don't recompile.

    Thin jit wrapper: ``run_wave_on`` over a ``LocalSubstrate`` — the
    mesh engine wraps the very same function over a ``MeshSubstrate``
    (``dist_engine.run_wave_dist``).

    ``kernels`` picks the kernel backend for every data-plane hot spot — a
    resolved ``repro.kernels.KernelConfig``, a backend name (``"pallas"`` /
    ``"pallas_interpret"`` / ``"jnp"``), or ``None`` for the process
    default (env ``REPRO_KERNEL_BACKEND``).  It is resolved HERE, outside
    the jit boundary, so equivalent specs (a name, a config, or a matching
    process default) share one trace; the substrate is then built per
    trace with the resolved config baked in as a static argument.

    ``watermark`` is the GC watermark for version reclamation (DESIGN.md §8):
    the decentralized min over live readers' ``s_lo``.  In the wave model
    every reader's snapshot is pinned at a wave boundary, so the min
    collapses to the wave-entry clock; ``None`` defaults to exactly that.
    The closed-loop service passes an explicit (possibly lower) value when
    external readers pin it — e.g. clock-skewed hosts or retry pins.

    GC accounting is opt-in (static flags) so the pure replay path pays
    nothing for it.  With ``gc_track=True`` each install that would evict a
    version still visible above the watermark is counted in
    ``WaveOut.evicted_visible``; with ``gc_block=True`` the writer is
    aborted instead (and the counter stays 0), so the retry pipeline
    re-runs it after the watermark has advanced past the ring."""
    return _run_wave_jit(store, wave, wave_idx, clock, n_nodes, sched=sched,
                         skew=skew, host_skew=host_skew, watermark=watermark,
                         gc_track=gc_track, gc_block=gc_block,
                         kernels=resolve(kernels),
                         placement=as_placement_arrays(placement))


class RunStats(NamedTuple):
    committed: int
    aborted: int
    msgs_cross: int
    msgs_coord: int
    waits: int
    evicted_visible: int   # still-visible versions destroyed by ring reuse
    waves: int


def step_wave(store: MVStore, wave: Wave, wave_idx: int, clock,
              *, sched: str = "postsi", n_nodes: int = 8, skew: int = 0,
              host_skew: np.ndarray | None = None, watermark=None,
              gc_track: bool = True, gc_block: bool = False,
              kernels: KernelConfig | str | None = None, placement=None):
    """Closed-loop step API (DESIGN.md §8): execute ONE wave and sync the
    per-txn outcomes to host so a caller can requeue aborted transactions.

    Unlike the replay drivers below, the caller owns the loop: it keeps the
    device-resident ``store``/``clock`` opaque between steps and receives a
    numpy ``WaveOut`` whose ``status``/``s``/``c`` rows line up with
    ``wave.tid`` — everything the wave former and retry pipeline in
    ``repro.service`` need.  ``watermark``/``gc_block`` plumb the service's
    GC policy into the engine's install path.

    Returns ``(store', out_np, clock')``.
    """
    hs = None if host_skew is None else jnp.asarray(host_skew, jnp.int32)
    wm = None if watermark is None else jnp.int32(watermark)
    store, out, clock = run_wave(store, wave, jnp.int32(wave_idx), clock,
                                 jnp.int32(n_nodes), sched=sched, skew=skew,
                                 host_skew=hs, watermark=wm,
                                 gc_track=gc_track, gc_block=gc_block,
                                 kernels=kernels, placement=placement)
    return store, jax.tree_util.tree_map(np.asarray, out), clock


def run_workload(store: MVStore, waves, sched: str = "postsi", skew: int = 0,
                 host_skew: np.ndarray | None = None, n_nodes: int = 8,
                 gc_track: bool = False, gc_block: bool = False,
                 kernels: KernelConfig | str | None = None, placement=None):
    """Per-wave debug driver: one jitted dispatch + host sync per wave.

    Returns (store, history, stats); history is a list of numpy-ified
    WaveOut for the verifier.  The measured hot path is
    ``run_workload_fused`` (bit-identical output); this driver is kept as
    the reference for differential tests and wave-by-wave debugging.
    """
    clock = jnp.int32(1)
    hs = None if host_skew is None else jnp.asarray(host_skew, jnp.int32)
    history = []
    for w_idx, wave in enumerate(waves):
        store, out, clock = run_wave(store, wave, jnp.int32(w_idx + 1), clock,
                                     jnp.int32(n_nodes), sched=sched,
                                     skew=skew, host_skew=hs,
                                     gc_track=gc_track, gc_block=gc_block,
                                     kernels=kernels, placement=placement)
        history.append((np.asarray(wave.tid),
                        jax.tree_util.tree_map(np.asarray, out)))
    return store, history, _stats_of(history)


def _stats_of(history) -> RunStats:
    tot = dict(committed=0, aborted=0, msgs_cross=0, msgs_coord=0, waits=0,
               evicted_visible=0)
    for _, o in history:
        tot["committed"] += int((o.status == COMMITTED).sum())
        tot["aborted"] += int((o.status == ABORTED).sum())
        tot["msgs_cross"] += int(o.msgs_cross)
        tot["msgs_coord"] += int(o.msgs_coord)
        tot["waits"] += int(o.waits)
        tot["evicted_visible"] += int(o.evicted_visible)
    return RunStats(waves=len(history), **tot)


# ---------------------------------------------------------------------------
# fused multi-wave executor (DESIGN.md §7)
# ---------------------------------------------------------------------------

def stack_waves(waves) -> Wave:
    """Stack per-wave [T, O] arrays into one [W, T, O] batch (leading axis =
    wave index) — the scan carrier for the fused executor."""
    return Wave(*(jnp.stack([getattr(w, f) for w in waves])
                  for f in Wave._fields))


@functools.partial(jax.jit,
                   static_argnames=("sched", "skew", "gc_track", "gc_block",
                                    "kernels"))
def _scan_waves(store: MVStore, stacked: Wave, clock: jax.Array,
                n_nodes: jax.Array, sched: str = "postsi", skew: int = 0,
                host_skew: jax.Array | None = None, gc_track: bool = False,
                gc_block: bool = False,
                kernels: KernelConfig | str | None = None, placement=None):
    """One device program for a whole workload: lax.scan over the wave axis
    carrying (store, clock); each step is the run_wave computation inlined.
    ``run_workload_fused`` resolves ``kernels`` before this jit boundary.
    Returns (store', WaveOut with leading [W] axis, clock')."""
    W = stacked.op_kind.shape[0]

    def body(carry, xs):
        st, clk = carry
        wave, w_idx = xs
        st, out, clk = run_wave(st, wave, w_idx, clk, n_nodes, sched=sched,
                                skew=skew, host_skew=host_skew,
                                gc_track=gc_track, gc_block=gc_block,
                                kernels=kernels, placement=placement)
        return (st, clk), out

    (store, clock), outs = lax.scan(
        body, (store, clock), (stacked, jnp.arange(1, W + 1, dtype=jnp.int32)))
    return store, outs, clock


def run_workload_fused(store: MVStore, waves, sched: str = "postsi",
                       skew: int = 0, host_skew: np.ndarray | None = None,
                       n_nodes: int = 8, gc_track: bool = False,
                       gc_block: bool = False,
                       kernels: KernelConfig | str | None = None,
                       placement=None):
    """Fused driver: the entire workload as a single jitted dispatch.

    Same signature and same (store, history, stats) contract as
    ``run_workload``, with bit-identical WaveOut history — only the host
    round-trips per wave are gone.
    """
    stacked = stack_waves(waves)
    hs = None if host_skew is None else jnp.asarray(host_skew, jnp.int32)
    store, outs, _ = _scan_waves(store, stacked, jnp.int32(1),
                                 jnp.int32(n_nodes), sched=sched, skew=skew,
                                 host_skew=hs, gc_track=gc_track,
                                 gc_block=gc_block, kernels=resolve(kernels),
                                 placement=as_placement_arrays(placement))
    outs = jax.tree_util.tree_map(np.asarray, outs)
    history = [(np.asarray(w.tid), WaveOut(*(f[i] for f in outs)))
               for i, w in enumerate(waves)]
    return store, history, _stats_of(history)


# ---------------------------------------------------------------------------
# fused block dispatch for the streaming service plane (DESIGN.md §8)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("sched", "skew", "gc_track", "gc_block",
                                    "kernels"))
def _scan_block(store: MVStore, stacked: Wave, wave_idx0: jax.Array,
                clock: jax.Array, n_nodes: jax.Array, host_skew, watermark,
                sched: str = "postsi", skew: int = 0, gc_track: bool = False,
                gc_block: bool = False,
                kernels: KernelConfig = KernelConfig("jnp"), placement=None):
    """One device program for a block of B pre-formed waves: lax.scan over
    the leading wave axis carrying (store, clock), exactly ``_scan_waves``
    but resumable — the caller owns the wave-index origin and the GC
    watermark, so consecutive blocks stitch into one continuous closed-loop
    history.  ``watermark`` (or None for the engine's own wave-boundary
    collapse) applies to every wave of the block: it is computed by the
    service at dispatch time from the *retired* prefix of the stream, which
    can only under-estimate the true floor — safe, never unsafe."""
    B = stacked.op_kind.shape[0]
    sub = LocalSubstrate(kernels)

    def body(carry, xs):
        st, clk = carry
        wave, w_idx = xs
        st, out, clk = run_wave_on(sub, st, wave, w_idx, clk, n_nodes,
                                   sched=sched, skew=skew,
                                   host_skew=host_skew, watermark=watermark,
                                   gc_track=gc_track, gc_block=gc_block,
                                   placement=placement)
        return (st, clk), out

    (store, clock), outs = lax.scan(
        body, (store, clock),
        (stacked, wave_idx0 + jnp.arange(B, dtype=jnp.int32)))
    return store, outs, clock


def run_block(store: MVStore, stacked: Wave, wave_idx0: int, clock,
              *, sched: str = "postsi", n_nodes: int = 8, skew: int = 0,
              host_skew: np.ndarray | None = None, watermark=None,
              gc_track: bool = True, gc_block: bool = False,
              kernels: KernelConfig | str | None = None, placement=None):
    """Dispatch a block of B formed waves (``stacked`` has leading [B] axis,
    from ``stack_waves``) as ONE device program and return device-resident
    results: ``(store', outs, clock')`` where ``outs`` is a ``WaveOut``
    whose every leaf carries the leading [B] wave axis.

    Nothing here blocks on the device: under JAX async dispatch the returned
    arrays are futures, so a pipelined caller (``service.stream``) can keep
    forming the next block on the host — and even dispatch it, chaining on
    the returned store/clock — while this one executes.  Materializing the
    outcomes (``np.asarray``) is the caller's explicit synchronization
    point; ``step_block`` below does exactly that for step-style callers."""
    hs = None if host_skew is None else jnp.asarray(host_skew, jnp.int32)
    wm = None if watermark is None else jnp.int32(watermark)
    return _scan_block(store, stacked, jnp.int32(wave_idx0), clock,
                       jnp.int32(n_nodes), hs, wm, sched=sched, skew=skew,
                       gc_track=gc_track, gc_block=gc_block,
                       kernels=resolve(kernels),
                       placement=as_placement_arrays(placement))


def step_block(store: MVStore, stacked: Wave, wave_idx0: int, clock, **kw):
    """Synchronous block step: ``run_block`` + host sync of the per-wave
    outcomes (mirror of ``step_wave`` for a [B]-stacked wave block).
    Returns ``(store', outs_np, clock')``."""
    store, outs, clock = run_block(store, stacked, wave_idx0, clock, **kw)
    return store, jax.tree_util.tree_map(np.asarray, outs), clock


# stale-trace hygiene: a process-default backend switch drops traces baked
# with the old default (correctness needs no clearing — the resolved config
# is part of the static key, so the new default is a fresh entry)
register_cache_clear(_run_wave_jit)
register_cache_clear(_scan_waves)
register_cache_clear(_scan_block)
