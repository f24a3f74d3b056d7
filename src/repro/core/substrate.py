"""Data-access substrate: the engine/placement seam (DESIGN.md §4).

``engine.run_wave_on`` holds the only copy of the concurrency-control rules
(read-phase visibility, CV rules 5-6, PostSI rules 3/4/5).  Everything that
rule arithmetic needs from the *data plane* — the read-phase lookup, the
commit-phase re-validation read, the version install, the SID bump and the
GC watermark consult — goes through the small interface below, so the same
commit loop runs on any placement:

* ``LocalSubstrate`` — the store is one dense array per field; every access
  is direct indexing / masked scatter.  This is the single-device engine.
* ``MeshSubstrate`` — the store is block-partitioned over a 1-D mesh axis
  (``node = key // keys_per_node``) and the substrate runs *inside* a
  ``shard_map`` body: reads are answered by the owning node from its local
  block (others contribute zeros) and merged with ``lax.psum`` — the
  lockstep equivalent of the paper's work delegation — while installs and
  SID bumps are masked local scatters applied only on the owner.  No
  coordinator exists anywhere: every collective is a peer merge.

Both substrates carry a resolved :class:`repro.kernels.KernelConfig` and
dispatch every compute hot spot through the kernel plane (``kernels.ops``):
the read-phase latest-visible-slot selection via ``ops.version_scan`` (the
paper's §IV-B CID rule — lane padding handled by the op wrapper), the
anti-dependency candidate build via ``commit_phase.build_potential``, and
the batched install / SID-bump scatters via ``ops.masked_install`` /
``ops.masked_sid_bump``.  ``kernels=None`` resolves the process default
once at construction; substrates stay stateless and cheap to construct —
the engines build one per trace with the config baked in.

The mesh one derives its block base from ``lax.axis_index`` at trace time,
so one traced program serves every node (SPMD).

Slot-space contract (DESIGN.md §11): substrates index *physical store
rows*, not logical keys.  Under the default identity placement the two
coincide; under an elastic ``PlacementMap`` the engine translates each
wave's logical keys through ``placement.slot`` ONCE at wave entry and hands
the substrate physical rows only.  Because any placement is an injective
key->row map, key-equality structure (the anti-dependency ``potential``)
and per-row ring semantics are preserved — which is why outcomes are
bit-identical under every placement, including mid-stream moves.
``tests/test_distribution.py`` pins the two substrates bit-identical for
all six schedulers, per-wave and fused; ``tests/test_kernel_backend.py``
pins every backend route bit-identical on both.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp
from jax import lax

from repro.kernels import KernelConfig, can_compile_pallas, ops, resolve
from .commit_phase import build_potential
from .store import INF, MVStore
from . import store as store_ops

# mesh-degrade accounting: how many times a compiled-Mosaic ``pallas``
# request was served by the ``jnp`` reference on the mesh path, surfaced so
# benchmarks can label affected rows honestly (``benchmarks.bench_dist``)
# instead of silently reporting pallas numbers that never ran as pallas
_degrades = 0
_degrade_warned = False


def mesh_degrade_count() -> int:
    """Times ``mesh_kernels`` degraded a ``pallas`` request to ``jnp``."""
    return _degrades


def effective_mesh_backend(kernels: KernelConfig | str | None = None) -> str:
    """Honest label for what the mesh path runs under this request:
    the resolved backend spec, or ``"jnp (degraded from pallas)"`` when
    this process runs on a platform Mosaic does not compile for."""
    cfg = resolve(kernels)
    if cfg.backend == "pallas" and not can_compile_pallas():
        return "jnp (degraded from pallas)" + ("+fused" if cfg.fused else "")
    return cfg.name


def mesh_kernels(kernels: KernelConfig | str | None = None) -> KernelConfig:
    """The config a ``MeshSubstrate`` will actually run.

    Per-shard local block shapes are static under shard_map, so compiled
    Mosaic kernels are legal on the mesh path wherever Mosaic compiles at
    all.  The choice is made by platform (``kernels.can_compile_pallas``):
    on a TPU ``pallas`` passes through, so a kernel Mosaic refuses raises
    where the mesh program compiles; on any other platform ``pallas``
    degrades to the bit-identical ``jnp`` reference.
    ``pallas_interpret``/``jnp`` always pass through.  The mesh drivers
    normalize through this BEFORE using the config as a jit/lru cache key,
    so a degraded ``pallas`` request and a ``jnp`` request share one trace
    instead of compiling identical programs twice.

    The degradation is *not* silent: the first occurrence per process emits
    a ``RuntimeWarning`` and every occurrence bumps ``mesh_degrade_count()``
    so callers (benchmarks, services) can report what actually ran."""
    cfg = resolve(kernels)
    if cfg.backend == "pallas" and not can_compile_pallas():
        global _degrades, _degrade_warned
        _degrades += 1
        if not _degrade_warned:
            _degrade_warned = True
            warnings.warn(
                "KernelConfig('pallas') degrades to the bit-identical 'jnp' "
                "reference on the mesh path: Mosaic compiles only for a "
                "TPU, and this process runs elsewhere; mesh results are "
                "correct but do not measure compiled kernels — request "
                "'pallas_interpret' or 'jnp' explicitly to silence this",
                RuntimeWarning, stacklevel=2)
        return KernelConfig("jnp", fused=cfg.fused)
    return cfg


class LocalSubstrate:
    """Direct-indexing data plane: the whole key space lives in one store."""

    def __init__(self, kernels: KernelConfig | str | None = None):
        self.kernels = resolve(kernels)

    def read_visible(self, store: MVStore, keys, max_cid):
        """Latest version with CID <= max_cid per key (paper §IV-B read rule).
        Returns (val, tid, cid, sid, slot), shaped like ``keys``.

        The ring gather stays here (data movement); slot *selection* — the
        per-request scan the paper's read rule pays on every access — is
        dispatched through ``ops.version_scan`` on the configured backend.
        Masked/NOP keys (possibly negative padding) are clamped so they can
        never wrap to the last key.
        """
        k = jnp.clip(keys, 0, store.n_keys - 1)
        cids = store.cid[k]                          # [..., V]
        tids = store.tid[k]
        V = store.n_versions
        mc = jnp.broadcast_to(max_cid, k.shape)
        slot, _ = ops.version_scan(
            cids.reshape(-1, V), tids.reshape(-1, V), mc.reshape(-1),
            use_pallas=self.kernels.use_pallas,
            interpret=self.kernels.interpret)
        slot = slot.reshape(k.shape)
        take = lambda a: jnp.take_along_axis(a[k], slot[..., None],
                                             axis=-1)[..., 0]
        return take(store.val), take(store.tid), take(store.cid), \
            take(store.sid), slot

    def read_newest(self, store: MVStore, keys):
        """Newest committed version (PostSI reads start with s_hi = +inf)."""
        return self.read_visible(store, keys,
                                 jnp.broadcast_to(INF, keys.shape))

    def read_sid(self, store: MVStore, keys, slots):
        """Re-gather SIDs of previously read (key, slot) pairs — peers may
        have bumped them since the read phase (rule 4(a) input)."""
        return ops.sid_regather(store.sid, keys, slots)

    def key_staleness(self, store: MVStore, keys):
        """Per-key (last-commit wave tag, head CID) — the clocksi stale-read
        cutoff inputs.  NOP/padding keys (possibly negative) are clamped
        like every other gather so they can never wrap to the last key."""
        k = jnp.clip(keys, 0, store.n_keys - 1)
        key_wave = store.wave[k]
        head_cid = jnp.take_along_axis(
            store.cid[k], store.head[k][..., None], axis=-1)[..., 0]
        return key_wave, head_cid

    def evicting_visible(self, store: MVStore, keys, watermark):
        """Would installing into ``keys`` evict a version still visible above
        the GC watermark?  (store.evicting_visible; DESIGN.md §8)."""
        return store_ops.evicting_visible(store, keys, watermark)

    def install(self, store: MVStore, mask, keys, values, tid, cid, wave_idx):
        """Masked version install: push a new ring version for every key with
        ``mask`` set (rule 4(c) CID stamping).  OOB sentinel drops the rest
        (``ops.masked_install``)."""
        val, tid_, cid_, sid, head, wave = ops.masked_install(
            store.val, store.tid, store.cid, store.sid, store.head,
            store.wave, mask=mask, keys=keys, values=values, new_tid=tid,
            new_cid=cid, wave_idx=wave_idx)
        return store._replace(val=val, tid=tid_, cid=cid_, sid=sid,
                              head=head, wave=wave)

    def bump_sid(self, store: MVStore, mask, keys, slots, expect_tid, s_val):
        """Rule 4(c) SID bump: raise SID of read versions to the reader's
        start time, guarded against ring slots recycled since the read
        (``ops.masked_sid_bump``)."""
        return store._replace(sid=ops.masked_sid_bump(
            store.sid, store.tid, mask=mask, keys=keys, slots=slots,
            expect_tid=expect_tid, s_val=s_val))

    def build_potential(self, keys, is_read, is_write):
        """Anti-dependency candidate matrix [T, T] — routed through the
        configured backend (Pallas kernel / interpret / jnp)."""
        return build_potential(keys, is_read, is_write, backend=self.kernels)

    def read_phase(self, store: MVStore, keys, max_cid, is_read, is_write):
        """The whole wave read phase (DESIGN.md §7): latest-visible slot
        selection, the PostSI rule-3 negotiation seed ``s_lo0`` and the
        anti-dependency candidate build.  Returns ``(r_val, r_tid, r_cid,
        r_sid, r_slot, s_lo0 [T], potential [T, T] bool)``.

        With ``kernels.fused`` this is ONE ``ops.wave_commit`` launch over
        the gathered rings — no HBM round-trips between the three bodies;
        otherwise the three separate dispatches.  Bit-identical either way
        (tests/test_kernels.py, tests/test_kernel_backend.py).
        """
        mc = jnp.broadcast_to(max_cid, keys.shape)
        if not self.kernels.fused:
            r_val, r_tid, r_cid, r_sid, r_slot = self.read_visible(
                store, keys, mc)
            s_lo0 = jnp.where(is_read, r_cid, 0).max(axis=1)
            pot = self.build_potential(keys, is_read, is_write)
            return r_val, r_tid, r_cid, r_sid, r_slot, s_lo0, pot
        k = jnp.clip(keys, 0, store.n_keys - 1)
        slot, r_val, r_tid, r_cid, r_sid, s_lo0, pot = ops.wave_commit(
            store.cid[k], store.tid[k], store.sid[k], store.val[k], mc,
            jnp.where(is_read, keys, -1), jnp.where(is_write, keys, -1),
            is_read,
            use_pallas=self.kernels.use_pallas,
            interpret=self.kernels.interpret)
        return r_val, r_tid, r_cid, r_sid, slot, s_lo0, pot.astype(bool)


class MeshSubstrate:
    """Peer-collective data plane for a block-partitioned store.

    Must be used inside a ``shard_map`` body whose store arguments carry the
    per-node block (P(axis) over the key dim); all key arguments are GLOBAL
    ids, replicated on every node.  Reads: masked local answer + psum merge.
    Writes: owner-only masked scatter.

    There is deliberately no second copy of the data-plane logic here:
    every method translates global keys to local block indices and then
    *delegates* to a ``LocalSubstrate`` carrying the same
    :class:`KernelConfig` on the local block (the per-node ``MVStore`` is
    itself a complete store with ``n_keys == n_local``), masking non-owned
    answers to zero before the psum merge and masking non-owned writes off
    entirely.  A rule or kernel-route fix in the local plane therefore
    reaches both placements by construction — including the
    ``ops.version_scan`` dispatch, which runs on each node's local block
    before the merge.
    """

    def __init__(self, axis: str = "node",
                 kernels: KernelConfig | str | None = None):
        self.axis = axis
        self.kernels = mesh_kernels(kernels)
        self._local_sub = LocalSubstrate(self.kernels)

    # ------------------------------------------------------------ helpers
    def _local(self, store: MVStore, keys):
        """(local_idx clipped, mine mask, n_local) for global ``keys``."""
        n_local = store.val.shape[0]
        base = lax.axis_index(self.axis) * n_local
        lk = keys - base
        mine = (lk >= 0) & (lk < n_local)
        return jnp.clip(lk, 0, n_local - 1), mine, n_local

    def _merge(self, mine, *parts):
        """Owner keeps its answer, others contribute 0; psum merges."""
        return tuple(lax.psum(jnp.where(mine, p, 0), self.axis)
                     for p in parts)

    # -------------------------------------------------------------- reads
    def read_visible(self, store: MVStore, keys, max_cid):
        lk, mine, _ = self._local(store, keys)
        return self._merge(mine,
                           *self._local_sub.read_visible(store, lk, max_cid))

    def read_newest(self, store: MVStore, keys):
        return self.read_visible(store, keys,
                                 jnp.broadcast_to(INF, keys.shape))

    def read_sid(self, store: MVStore, keys, slots):
        lk, mine, _ = self._local(store, keys)
        (sid,) = self._merge(mine, self._local_sub.read_sid(store, lk, slots))
        return sid

    def key_staleness(self, store: MVStore, keys):
        lk, mine, _ = self._local(store, keys)
        return self._merge(mine, *self._local_sub.key_staleness(store, lk))

    def evicting_visible(self, store: MVStore, keys, watermark):
        lk, mine, _ = self._local(store, keys)
        ev = self._local_sub.evicting_visible(store, lk,
                                              watermark).astype(jnp.int32)
        (ev,) = self._merge(mine, ev)
        return ev.astype(bool)

    # ------------------------------------------------------------- writes
    def install(self, store: MVStore, mask, keys, values, tid, cid, wave_idx):
        lk, mine, _ = self._local(store, keys)
        return self._local_sub.install(store, mask & mine, lk, values, tid,
                                       cid, wave_idx)

    def bump_sid(self, store: MVStore, mask, keys, slots, expect_tid, s_val):
        lk, mine, _ = self._local(store, keys)
        return self._local_sub.bump_sid(store, mask & mine, lk, slots,
                                        expect_tid, s_val)

    def build_potential(self, keys, is_read, is_write):
        # replicated build: every node computes the same [T, T] matrix,
        # routed through the (possibly probe-degraded) config
        return build_potential(keys, is_read, is_write, backend=self.kernels)

    def read_phase(self, store: MVStore, keys, max_cid, is_read, is_write):
        """Mesh twin of ``LocalSubstrate.read_phase``.

        Fused route: each node runs the ``ops.wave_commit`` megakernel over
        its LOCAL gathered rings with ``rvalid = is_read & mine`` as the
        s_lo0 seed mask, then the scan outputs merge with the usual
        owner-keeps/psum pattern and the per-node partial ``s_lo0`` maxima
        merge with ``lax.pmax`` — equal to the unfused merge-then-reduce
        order because every contribution is a non-negative CID.  The
        potential tile is built from GLOBAL replicated keys, so it is
        replicated-identical on every node with no merge at all.
        """
        mc = jnp.broadcast_to(max_cid, keys.shape)
        if not self.kernels.fused:
            r_val, r_tid, r_cid, r_sid, r_slot = self.read_visible(
                store, keys, mc)
            s_lo0 = jnp.where(is_read, r_cid, 0).max(axis=1)
            pot = self.build_potential(keys, is_read, is_write)
            return r_val, r_tid, r_cid, r_sid, r_slot, s_lo0, pot
        lk, mine, _ = self._local(store, keys)
        slot, r_val, r_tid, r_cid, r_sid, s_lo0, pot = ops.wave_commit(
            store.cid[lk], store.tid[lk], store.sid[lk], store.val[lk], mc,
            jnp.where(is_read, keys, -1), jnp.where(is_write, keys, -1),
            is_read & mine,
            use_pallas=self.kernels.use_pallas,
            interpret=self.kernels.interpret)
        r_val, r_tid, r_cid, r_sid, slot = self._merge(
            mine, r_val, r_tid, r_cid, r_sid, slot)
        s_lo0 = lax.pmax(s_lo0, self.axis)
        return r_val, r_tid, r_cid, r_sid, slot, s_lo0, pot.astype(bool)
