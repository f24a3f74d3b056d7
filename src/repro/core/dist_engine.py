"""Shard_map wave engine: the paper's shared-nothing cluster as a JAX mesh.

The version store is block-partitioned over a 1-D ``("node",)`` mesh axis
(node = key // keys_per_node); transaction state (interval bounds, status)
is *replicated* and updated by identical deterministic computation on every
node, while all data accesses are peer collectives:

  read phase     each node answers the wave's key requests from its block
                 (others masked to zero); psum merges the responses — the
                 lockstep equivalent of the paper's work delegation.
  commit phase   per-commit re-validation reads use the same masked-answer
                 + psum; version installs and SID bumps apply only on the
                 owning node (masked local scatter); PostSI rule 4(b) bound
                 pushes are replicated arithmetic — **zero coordinator
                 anywhere**.

This module contains NO concurrency-control rules.  The single commit loop
lives in ``engine.run_wave_on``; here it is merely *wired* to a
``substrate.MeshSubstrate`` inside ``shard_map`` bodies, which lifts all
six schedulers (postsi, cv, si, optimal, dsi, clocksi) onto the mesh at
once.  Drivers mirror the single-device engine one-for-one:

  ``run_wave_dist``           one wave          <->  ``engine.run_wave``
  ``run_workload_dist``       per-wave driver   <->  ``engine.run_workload``
  ``run_workload_fused_dist`` one lax.scan
                              device program    <->  ``run_workload_fused``
  ``step_wave_dist``          closed-loop step  <->  ``engine.step_wave``

plus ``mesh_watermark``, the decentralized GC-watermark merge: per-node
live-reader floors reduced with ``lax.pmin`` on the mesh (DESIGN.md §8).
Semantics are bit-identical to the single-device engine — same commit sets,
same induced intervals, same final stores — for every scheduler on both the
per-wave and fused paths (tests/test_distribution.py).
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import KernelConfig
from .engine import Wave, WaveOut, _stats_of, run_wave_on
from .store import MVStore, PlacementArrays, as_placement_arrays, make_store
from .substrate import MeshSubstrate, mesh_kernels


def make_node_mesh(n_nodes: int) -> Mesh:
    """1-D ``("node",)`` mesh over the first ``n_nodes`` XLA devices.

    Raises ``ValueError`` when the platform exposes fewer devices than
    requested — ``jax.devices()[:n]`` would otherwise silently build an
    under-provisioned mesh (fewer shards than the caller sized for).
    """
    devs = jax.devices()
    if len(devs) < n_nodes:
        raise ValueError(
            f"make_node_mesh({n_nodes}): only {len(devs)} XLA device(s) "
            f"available; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_nodes} (or run on a platform with >= {n_nodes} devices)")
    return Mesh(np.array(devs[:n_nodes]), ("node",))


def shard_store(store: MVStore, mesh: Mesh,
                n_slots: int | None = None) -> MVStore:
    """Block-partition a store over the mesh's ``node`` axis.

    A key space that does not divide the node count is PADDED: trailing
    empty rows (all ``tid == NO_TID`` — never visible, never routed to by
    any valid key or placement) bring the row count up to the next multiple
    of ``n_nodes``, so the substrate's ``base = axis_index * n_local`` block
    arithmetic stays exact.  (This used to be a hard ``ValueError``; padding
    is strictly better — the pad rows are unreachable by construction.)

    ``n_slots`` (elastic placement) requests a specific padded row count —
    ``PlacementMap.n_slots``, i.e. ``capacity * n_nodes`` with headroom for
    range moves; it must be a multiple of ``n_nodes`` and >= the store's
    current rows.
    """
    n_nodes = mesh.devices.size
    n_rows = store.n_keys
    if n_slots is None:
        n_slots = -(-n_rows // n_nodes) * n_nodes        # ceil to a multiple
    if n_slots % n_nodes != 0:
        raise ValueError(f"shard_store: n_slots={n_slots} is not a multiple "
                         f"of the mesh's {n_nodes} node(s)")
    if n_slots < n_rows:
        raise ValueError(f"shard_store: n_slots={n_slots} < store rows "
                         f"{n_rows}; the store does not shrink")
    if n_slots > n_rows:
        pad = make_store(n_slots - n_rows, store.n_versions)
        # pad rows are EMPTY, not bootstrap rows: no key maps to them
        pad = pad._replace(tid=jnp.full_like(pad.tid, -1))
        store = MVStore(*(jnp.concatenate([a, b])
                          for a, b in zip(store, pad)))
    sh = NamedSharding(mesh, P("node"))
    return MVStore(*(jax.device_put(a, sh) for a in store))


# ---------------------------------------------------------------------------
# shard_map wiring: flatten (MVStore, Wave) <-> leaf arrays at the boundary
# ---------------------------------------------------------------------------

_N_STORE = len(MVStore._fields)
_N_WAVE = len(Wave._fields)
_N_OUT = len(WaveOut._fields)


def _norm_placement(placement) -> Tuple[jax.Array, jax.Array]:
    """Placement tables as two replicated leaves for the shard_map boundary
    (None cannot cross it): empty ``(0,)`` arrays are the no-placement
    sentinel — a STATIC shape, so the placement-free trace stays exactly
    the historical program."""
    p = as_placement_arrays(placement)
    if p is None:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    return p.owner, p.slot


def _denorm_placement(owner: jax.Array, slot: jax.Array):
    return (None if owner.shape[0] == 0
            else PlacementArrays(owner, slot))


def _placement_check(store: MVStore, mesh: Mesh, placement, op_key) -> None:
    """REPRO_PLACEMENT_CHECK=1: validate owner/slot routing against the
    sharded store's block layout before dispatching (host-side, off the hot
    path unless the env knob is set)."""
    if os.environ.get("REPRO_PLACEMENT_CHECK", "0") in ("", "0"):
        return
    from repro.placement.map import validate_routing
    validate_routing(int(store.head.shape[0]), mesh.devices.size,
                     as_placement_arrays(placement), op_key)


@functools.lru_cache(maxsize=None)
def _wave_fn(mesh: Mesh, sched: str, skew: int, gc_track: bool,
             gc_block: bool, kernels: KernelConfig = KernelConfig("jnp"),
             jit: bool = True):
    """Single-wave mesh executor: shard_map around ``engine.run_wave_on``
    over a ``MeshSubstrate`` carrying the resolved kernel config.
    Takes/returns flat leaves (store sharded P("node"), everything else
    replicated).  ``kernels`` must already be resolved AND mesh-degraded (it
    is part of the lru_cache key; the public drivers normalize via
    ``substrate.mesh_kernels`` so equivalent configs — e.g. ``pallas`` and
    its mesh degrade ``jnp`` — share one compile, and a process-default
    switch lands on a fresh cache entry)."""
    sub = MeshSubstrate("node", kernels)

    def node_fn(*args):
        st = MVStore(*args[:_N_STORE])
        wave = Wave(*args[_N_STORE:_N_STORE + _N_WAVE])
        wave_idx, clock, n_nodes, hs, wm, p_own, p_slot = \
            args[_N_STORE + _N_WAVE:]
        st, out, clk = run_wave_on(sub, st, wave, wave_idx, clock, n_nodes,
                                   sched=sched, skew=skew, host_skew=hs,
                                   watermark=wm, gc_track=gc_track,
                                   gc_block=gc_block,
                                   placement=_denorm_placement(p_own, p_slot))
        return (*st, *out, clk)

    mapped = jax.shard_map(
        node_fn, mesh=mesh,
        in_specs=(P("node"),) * _N_STORE + (P(),) * (_N_WAVE + 7),
        out_specs=(P("node"),) * _N_STORE + (P(),) * (_N_OUT + 1),
        check_vma=False,
    )
    return jax.jit(mapped) if jit else mapped


@functools.lru_cache(maxsize=None)
def _scan_fn(mesh: Mesh, sched: str, skew: int, gc_track: bool,
             gc_block: bool, kernels: KernelConfig = KernelConfig("jnp")):
    """Fused multi-wave mesh executor: ONE device program for a whole
    workload — lax.scan over the wave axis *inside* the shard_map body, so
    the host is not touched between waves (mesh mirror of
    ``engine._scan_waves``).  ``kernels`` must already be resolved."""
    sub = MeshSubstrate("node", kernels)

    def node_fn(*args):
        st = MVStore(*args[:_N_STORE])
        stacked = Wave(*args[_N_STORE:_N_STORE + _N_WAVE])   # [W, ...] leaves
        clock, n_nodes, hs, p_own, p_slot = args[_N_STORE + _N_WAVE:]
        W = stacked.op_kind.shape[0]
        pl = _denorm_placement(p_own, p_slot)

        def body(carry, xs):
            st, clk = carry
            wave, w_idx = xs
            st, out, clk = run_wave_on(sub, st, wave, w_idx, clk, n_nodes,
                                       sched=sched, skew=skew, host_skew=hs,
                                       gc_track=gc_track, gc_block=gc_block,
                                       placement=pl)
            return (st, clk), out

        (st, clock), outs = lax.scan(
            body, (st, clock),
            (stacked, jnp.arange(1, W + 1, dtype=jnp.int32)))
        return (*st, *outs, clock)

    mapped = jax.shard_map(
        node_fn, mesh=mesh,
        in_specs=(P("node"),) * _N_STORE + (P(),) * (_N_WAVE + 5),
        out_specs=(P("node"),) * _N_STORE + (P(),) * (_N_OUT + 1),
        check_vma=False,
    )
    return jax.jit(mapped)


@functools.lru_cache(maxsize=None)
def _block_fn(mesh: Mesh, sched: str, skew: int, gc_track: bool,
              gc_block: bool, kernels: KernelConfig = KernelConfig("jnp")):
    """Fused block executor on the mesh: lax.scan over a [B]-stacked wave
    block *inside* the shard_map body, resumable (caller-owned wave-index
    origin + GC watermark) — the mesh twin of ``engine._scan_block`` and
    the device program behind the streaming service's sharded data plane.
    ``kernels`` must already be resolved and mesh-degraded."""
    sub = MeshSubstrate("node", kernels)

    def node_fn(*args):
        st = MVStore(*args[:_N_STORE])
        stacked = Wave(*args[_N_STORE:_N_STORE + _N_WAVE])   # [B, ...] leaves
        wave_idx0, clock, n_nodes, hs, wm, p_own, p_slot = \
            args[_N_STORE + _N_WAVE:]
        B = stacked.op_kind.shape[0]
        pl = _denorm_placement(p_own, p_slot)

        def body(carry, xs):
            st, clk = carry
            wave, w_idx = xs
            # wm < 0 is the "no external pin" sentinel (None cannot cross the
            # shard_map leaf boundary): collapse to the wave-entry clock, the
            # same per-wave default the local scan gets from watermark=None
            wm_i = jnp.where(wm < 0, clk, wm)
            st, out, clk = run_wave_on(sub, st, wave, w_idx, clk, n_nodes,
                                       sched=sched, skew=skew, host_skew=hs,
                                       watermark=wm_i, gc_track=gc_track,
                                       gc_block=gc_block, placement=pl)
            return (st, clk), out

        (st, clock), outs = lax.scan(
            body, (st, clock),
            (stacked, wave_idx0 + jnp.arange(B, dtype=jnp.int32)))
        return (*st, *outs, clock)

    mapped = jax.shard_map(
        node_fn, mesh=mesh,
        in_specs=(P("node"),) * _N_STORE + (P(),) * (_N_WAVE + 7),
        out_specs=(P("node"),) * _N_STORE + (P(),) * (_N_OUT + 1),
        check_vma=False,
    )
    return jax.jit(mapped)


def _norm_hs(host_skew) -> jax.Array:
    """None -> zeros: the engine's clocksi path clamp-gathers, so a length-1
    zero vector means 'no skew anywhere' (same as the local default)."""
    if host_skew is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(host_skew, jnp.int32)


def dist_wave_traceable(mesh: Mesh, sched: str = "postsi", skew: int = 0,
                        gc_track: bool = False, gc_block: bool = False,
                        kernels=None):
    """Unjitted traceable single-wave mesh executor over the NamedTuples —
    for callers that lower/compile themselves (repro.launch.dryrun_postsi).
    Returns ``f(store, wave, wave_idx, clock, n_nodes, host_skew=None,
    watermark=None) -> (store', WaveOut, clock')``."""
    fn = _wave_fn(mesh, sched, skew, gc_track, gc_block,
                  mesh_kernels(kernels), jit=False)

    def call(store, wave, wave_idx, clock, n_nodes, host_skew=None,
             watermark=None, placement=None):
        wm = clock if watermark is None else watermark
        out = fn(*store, *wave, jnp.int32(wave_idx), jnp.int32(clock),
                 jnp.int32(n_nodes), _norm_hs(host_skew), jnp.int32(wm),
                 *_norm_placement(placement))
        return (MVStore(*out[:_N_STORE]),
                WaveOut(*out[_N_STORE:_N_STORE + _N_OUT]), out[-1])

    return call


def run_wave_dist(store: MVStore, wave: Wave, wave_idx, clock, mesh: Mesh,
                  n_nodes=None, sched: str = "postsi", skew: int = 0,
                  host_skew=None, watermark=None, gc_track: bool = False,
                  gc_block: bool = False, kernels=None,
                  placement=None) -> Tuple[MVStore, WaveOut, jax.Array]:
    """One wave on the node mesh, any scheduler; mesh twin of
    ``engine.run_wave`` (same contract: (store', WaveOut, clock')).

    ``n_nodes`` is the *logical* cluster model the rules and message
    accounting use (dsi locality, clocksi skew, msgs_cross); it defaults to
    the physical node count of ``mesh`` so a resized mesh cannot silently
    run under a stale cluster model — pass it explicitly to decouple the
    two (e.g. an 8-node logical workload served from 4 physical shards).

    ``kernels`` routes every data-plane hot spot (version scan, potential
    build) per ``repro.kernels.resolve`` — same knob as ``engine.run_wave``."""
    n_nodes = mesh.devices.size if n_nodes is None else n_nodes
    wm = clock if watermark is None else watermark
    _placement_check(store, mesh, placement, np.asarray(wave.op_key))
    out = _wave_fn(mesh, sched, skew, gc_track, gc_block,
                   mesh_kernels(kernels))(
        *store, *wave, jnp.int32(wave_idx), jnp.int32(clock),
        jnp.int32(n_nodes), _norm_hs(host_skew), jnp.int32(wm),
        *_norm_placement(placement))
    return (MVStore(*out[:_N_STORE]),
            WaveOut(*out[_N_STORE:_N_STORE + _N_OUT]), out[-1])


def step_wave_dist(store: MVStore, wave: Wave, wave_idx: int, clock,
                   mesh: Mesh, *, sched: str = "postsi",
                   n_nodes: int | None = None, skew: int = 0, host_skew=None,
                   watermark=None, gc_track: bool = True,
                   gc_block: bool = False, kernels=None, placement=None):
    """Closed-loop step API on the mesh (DESIGN.md §8): one wave in, numpy
    per-txn outcomes out, store/clock kept device-resident (sharded)
    between steps — drop-in for ``engine.step_wave`` so ``TxnService``
    serves an open stream from the whole mesh."""
    store, out, clock = run_wave_dist(
        store, wave, wave_idx, clock, mesh, n_nodes=n_nodes, sched=sched,
        skew=skew, host_skew=host_skew, watermark=watermark,
        gc_track=gc_track, gc_block=gc_block, kernels=kernels,
        placement=placement)
    return store, jax.tree_util.tree_map(np.asarray, out), clock


def run_block_dist(store: MVStore, stacked: Wave, wave_idx0: int, clock,
                   mesh: Mesh, *, sched: str = "postsi",
                   n_nodes: int | None = None, skew: int = 0, host_skew=None,
                   watermark=None, gc_track: bool = True,
                   gc_block: bool = False, kernels=None, placement=None):
    """Dispatch a [B]-stacked wave block as one shard_map device program;
    mesh twin of ``engine.run_block`` (same contract: device-resident
    ``(store', outs[B], clock')``, nothing blocks on the device — the
    streaming driver materializes outcomes when it retires the block)."""
    n_nodes = mesh.devices.size if n_nodes is None else n_nodes
    wm = -1 if watermark is None else watermark
    _placement_check(store, mesh, placement, np.asarray(stacked.op_key))
    out = _block_fn(mesh, sched, skew, gc_track, gc_block,
                    mesh_kernels(kernels))(
        *store, *stacked, jnp.int32(wave_idx0), jnp.int32(clock),
        jnp.int32(n_nodes), _norm_hs(host_skew), jnp.int32(wm),
        *_norm_placement(placement))
    return (MVStore(*out[:_N_STORE]),
            WaveOut(*out[_N_STORE:_N_STORE + _N_OUT]), out[-1])


def step_block_dist(store: MVStore, stacked: Wave, wave_idx0: int, clock,
                    mesh: Mesh, **kw):
    """Synchronous mesh block step: ``run_block_dist`` + host sync of the
    per-wave outcomes (mesh mirror of ``engine.step_block``)."""
    store, outs, clock = run_block_dist(store, stacked, wave_idx0, clock,
                                        mesh, **kw)
    return store, jax.tree_util.tree_map(np.asarray, outs), clock


def run_workload_dist(store: MVStore, waves, mesh: Mesh,
                      sched: str = "postsi", skew: int = 0, host_skew=None,
                      n_nodes: int | None = None, gc_track: bool = False,
                      gc_block: bool = False, kernels=None, placement=None):
    """Per-wave mesh driver (debug/differential twin of
    ``engine.run_workload``): one dispatch + host sync per wave.
    Returns (store, history, stats)."""
    clock = jnp.int32(1)
    history = []
    for w_idx, wave in enumerate(waves):
        store, out, clock = run_wave_dist(
            store, wave, w_idx + 1, clock, mesh, n_nodes=n_nodes, sched=sched,
            skew=skew, host_skew=host_skew, gc_track=gc_track,
            gc_block=gc_block, kernels=kernels, placement=placement)
        history.append((np.asarray(wave.tid),
                        jax.tree_util.tree_map(np.asarray, out)))
    return store, history, _stats_of(history)


def run_workload_fused_dist(store: MVStore, waves, mesh: Mesh,
                            sched: str = "postsi", skew: int = 0,
                            host_skew=None, n_nodes: int | None = None,
                            gc_track: bool = False, gc_block: bool = False,
                            kernels=None, placement=None):
    """Fused mesh driver: the whole workload as a single jitted shard_map
    dispatch (scan-over-waves inside).  Same (store, history, stats)
    contract and bit-identical history to every other driver."""
    from .engine import stack_waves
    n_nodes = mesh.devices.size if n_nodes is None else n_nodes
    stacked = stack_waves(waves)
    _placement_check(store, mesh, placement, np.asarray(stacked.op_key))
    out = _scan_fn(mesh, sched, skew, gc_track, gc_block,
                   mesh_kernels(kernels))(
        *store, *stacked, jnp.int32(1), jnp.int32(n_nodes),
        _norm_hs(host_skew), *_norm_placement(placement))
    store = MVStore(*out[:_N_STORE])
    outs = jax.tree_util.tree_map(
        np.asarray, WaveOut(*out[_N_STORE:_N_STORE + _N_OUT]))
    history = [(np.asarray(w.tid), WaveOut(*(f[i] for f in outs)))
               for i, w in enumerate(waves)]
    return store, history, _stats_of(history)


# ---------------------------------------------------------------------------
# decentralized GC watermark merge (DESIGN.md §8)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pmin_fn(mesh: Mesh):
    return jax.jit(jax.shard_map(
        lambda f: lax.pmin(jnp.min(f), "node"), mesh=mesh,
        in_specs=P("node"), out_specs=P(), check_vma=False))


def mesh_watermark(mesh: Mesh, node_floors) -> int:
    """Merge per-node live-reader snapshot floors into the global GC
    watermark with ``lax.pmin`` on the mesh — the decentralized min the
    paper's visibility argument calls for: each node contributes the lowest
    ``s_lo`` any of its live readers may still take, and no coordinator ever
    owns the result (``service.VisibilityGC.node_floors`` produces the
    per-node inputs)."""
    floors = jnp.asarray(node_floors, jnp.int32).reshape(mesh.devices.size)
    return int(_pmin_fn(mesh)(floors))
