"""Closed-loop transaction service over the fused wave engine (DESIGN.md §8).

The replay drivers in ``repro.core.engine`` execute *static* wave lists:
aborted transactions die silently and nothing ever arrives.  ``TxnService``
closes the loop into the open system the paper describes serving:

    arrivals ──> WaveFormer ──> engine.step_wave ──> outcomes
                   ^  (admission, packing)   │
                   └── RetryPolicy (backoff) ┴──> committed / dropped

Each scheduler *tick* forms at most one ``[T, O]`` wave from due retries
plus fresh arrivals, executes it on-device through ``engine.step_wave``
(any of the six schedulers), and routes per-transaction outcomes: commits
record end-to-end latency (admission tick → commit tick); aborts re-enter
through the retry calendar with a fresh TID and exponential backoff until
the retry budget drops them.  The ``VisibilityGC`` tracker supplies the
version-reclamation watermark to the engine's install path and accumulates
the ``evicted_visible`` accounting.

The full history (including aborted attempts) is kept in the engine's
``(tids, WaveOut)`` format, so the standard verifiers run unchanged on
served traffic: ``service.verify()`` checks SI/CV validity and that the
final store matches a serial replay of the committed history.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import ABORTED, COMMITTED, NOP, Wave, WaveOut, make_store, \
    run_block, step_wave
from repro.core.verify import final_values_ok, verify_cv, verify_si
from repro.core.workloads import SMALLBANK_O, smallbank_txn, ycsb_txn
from repro.placement import (HotKeyReplicas, LoadBalancer, apply_move,
                             logical_store, physical_store)

from .former import TxnRequest, WaveFormer, fold_counts
from .gc import VisibilityGC
from .obs import record, stage
from .retry import RetryPolicy


def _pct(xs: List[int], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


@dataclasses.dataclass
class ServiceReport:
    """End-of-run metrics for one closed-loop session."""
    sched: str
    offered: int           # requests presented to admission
    admitted: int
    rejected: int          # shed at admission (queue full)
    committed: int
    dropped: int           # retry budget exhausted
    retries: int           # re-executions scheduled
    executions: int        # total txn slots executed (incl. retries)
    waves: int
    idle_ticks: int
    wall_s: float          # host seconds in tick, flush and step stages
    txns_per_sec: float    # sustained executed txns/sec (wall)
    goodput_tps: float     # committed txns/sec (wall)
    retry_rate: float      # retries / admitted
    latency_p50: float     # ticks, admission -> commit
    latency_p95: float
    latency_p99: float
    evicted_visible: int   # GC watermark violations observed
    gc: Dict[str, int]
    # streaming plane (DESIGN.md §8): 0 under the per-wave step loop
    blocks: int = 0        # fused block dispatches (>= waves / B)
    # planner plane (DESIGN.md §10): all 0 without a planner knob
    planned_waves: int = 0       # waves served through conflict-free lanes
    planned_lane_waves: int = 0  # lane + spill waves they expanded to
    planned_spilled: int = 0     # txns spilled past the lane budget
    planner_switches: int = 0    # hybrid mode flips (either direction)
    # elastic placement plane (DESIGN.md §11): all 0/empty when static
    replica_commits: int = 0     # read-only txns answered from replicas
    replica_refreshes: int = 0   # replica snapshot refreshes
    placement_moves: int = 0     # executed live range moves
    moved_keys: int = 0          # keys relocated across all moves
    imbalance: float = 0.0       # max/mean per-node committed-txn occupancy
    occupancy: List[int] = dataclasses.field(default_factory=list)
    # tenancy + write-hot mitigation plane (DESIGN.md §12)
    tenants: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    fold_groups: int = 0         # wave rows that carried a same-key RMW fold
    folded_requests: int = 0     # member requests that rode those rows free
    # host stage timers (service/obs.py): seconds and count per stage
    stage_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    stage_n: Dict[str, int] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        return d


class TxnService:
    """Closed-loop transaction service: open stream in, commits out.

    ``mesh`` switches the data plane: ``None`` serves from the single-device
    engine (``engine.step_wave``); a 1-D ``("node",)`` mesh (from
    ``dist_engine.make_node_mesh``) shards the version store over the mesh
    and serves every wave through ``dist_engine.step_wave_dist`` — the same
    commit loop over peer collectives, any scheduler, with the GC watermark
    merged from per-node reader floors by ``lax.pmin`` instead of a host-side
    min.  Outcomes are bit-identical between the two placements.
    """

    def __init__(self, n_keys: int, n_versions: int = 8, T: int = 64,
                 O: int = SMALLBANK_O, sched: str = "postsi",
                 n_nodes: int = 8, retry: Optional[RetryPolicy] = None,
                 gc_block: bool = False, max_queue: Optional[int] = None,
                 host_skew: Optional[np.ndarray] = None, seed: int = 0,
                 mesh=None, kernels=None, durability=None, faults=None,
                 planner=None, placement=None, replicas=None, balancer=None,
                 replica_refresh: int = 1,
                 tenants: Optional[Dict[int, float]] = None,
                 fold_rmw: bool = False, fold_max: int = 256):
        from repro.core.substrate import mesh_kernels
        from repro.kernels import resolve
        from repro.planner import HybridSwitch
        self.sched = sched
        self.n_nodes = n_nodes
        self.host_skew = host_skew
        self.T, self.O = T, O
        self.mesh = mesh
        # kernel-backend plane knob (DESIGN.md §7): resolved once, threaded
        # into every engine step; on the mesh placement it is normalized
        # through the shard_map degrade so it reports what actually runs
        self.kernels = resolve(kernels) if mesh is None else \
            mesh_kernels(kernels)
        # elastic placement plane (DESIGN.md §11): when a PlacementMap is
        # given, rings live at physical rows ``placement.slot[key]`` and
        # every engine dispatch translates logical keys through it; the
        # default (None) is the frozen identity layout
        self.placement = placement
        if placement is not None:
            if placement.n_keys != n_keys:
                raise ValueError(f"placement covers {placement.n_keys} keys, "
                                 f"service has {n_keys}")
            if mesh is not None and placement.n_nodes != mesh.devices.size:
                raise ValueError(f"placement is laid out for "
                                 f"{placement.n_nodes} nodes, mesh has "
                                 f"{mesh.devices.size}")
        base = make_store(n_keys, n_versions)
        if placement is not None:
            base = physical_store(base, placement)
        if mesh is None:
            self.store = base
        else:
            from repro.core.dist_engine import shard_store
            self.store = shard_store(base, mesh)
        self.n_keys = n_keys
        if replicas is not None and not isinstance(replicas, HotKeyReplicas):
            replicas = HotKeyReplicas(replicas)
        self.replicas = replicas
        self.replica_refresh = max(1, int(replica_refresh))
        self.replica_commits = 0
        if balancer is True:
            if placement is None:
                raise ValueError("balancer=True needs an elastic placement")
            balancer = LoadBalancer(n_keys, placement.n_nodes)
        if balancer is not None and placement is None:
            raise ValueError("a balancer needs an elastic placement to move")
        self.balancer = balancer
        self.placement_moves = 0
        self.moved_keys = 0
        self._occupancy = (np.zeros(placement.n_nodes, np.int64)
                          if placement is not None else None)
        self.clock = jnp.int32(1)
        # tenancy + write-hot mitigation plane (DESIGN.md §12): weighted
        # per-tenant admission queues with DRR wave packing, and optional
        # same-key commutative-RMW folding at form time
        self.former = WaveFormer(T, O, max_queue=max_queue, tenants=tenants,
                                 fold_rmw=fold_rmw, fold_max=fold_max)
        self._tenant_stats: Dict[int, Dict] = {}
        self.retry = retry or RetryPolicy()
        self.gc = VisibilityGC(
            block=gc_block,
            n_nodes=None if mesh is None else mesh.devices.size)
        self.rng = np.random.RandomState(seed)       # backoff jitter only
        self.tick = 0
        self.wave_idx = 0
        self.blocks = 0                              # streaming plane only
        self.history: List = []                      # (tids, WaveOut) numpy
        self.requests: List[TxnRequest] = []         # every offered request
        self.committed = 0
        self.dropped = 0
        self.retries = 0
        self.executions = 0
        self.idle_ticks = 0
        self.latencies: List[int] = []
        self._req_ids = itertools.count(1)
        self.stage_s: Dict[str, float] = defaultdict(float)   # obs.stage
        self.stage_n: Dict[str, int] = defaultdict(int)
        self.stream = None                   # StreamingDriver, when serving
        self._last_dispatch = (0, None)      # (wave_idx0, wm) of last block
        self.base_store = None    # snapshot rings when history is a suffix
        # durability & fault-injection planes (DESIGN.md §9): the manager
        # WAL-logs every retired block durable-before-ack and auto-recovers
        # an existing log into this fresh service; the schedule fires at
        # the dispatch/retire/post-log seams
        self.faults = faults
        # planner plane (DESIGN.md §10): ``None`` — always optimistic;
        # ``"hybrid"`` — switch to planned lanes when the trailing abort
        # rate crosses the AIMD ceiling and back when contention drops;
        # ``"planned"`` — plan every wave; or a configured HybridSwitch
        self.planner = (HybridSwitch.from_name(planner)
                        if isinstance(planner, str) else planner)
        self.planned_waves = 0        # waves served through the planner
        self.planned_lane_waves = 0   # lane + spill waves they expanded to
        self.planned_spilled = 0      # txns spilled past the lane budget
        self.durability = durability
        if durability is not None:
            durability.attach(self)
        if self.replicas is not None:
            # bootstrap snapshot at floor 0 so pre-first-tick submits can
            # already be answered (every ring starts with the cid-0 version)
            self._refresh_replicas()

    # ------------------------------------------------------------ intake
    def _tstat(self, tenant: int) -> Dict:
        st = self._tenant_stats.get(tenant)
        if st is None:
            st = {"offered": 0, "committed": 0, "dropped": 0, "retries": 0,
                  "replica_commits": 0, "latencies": []}
            self._tenant_stats[tenant] = st
        return st

    def submit(self, op_kind: np.ndarray, op_key: np.ndarray,
               op_val: np.ndarray, host: int, tenant: int = 0) -> TxnRequest:
        """Offer one transaction to admission control; the returned request
        carries its fate (``rejected`` immediately, else async).  ``tenant``
        selects the admission/fairness class (DESIGN.md §12) — untagged
        submits share the default tenant 0."""
        t0 = time.perf_counter()
        req = self._admit(op_kind, op_key, op_val, host, tenant, t0)
        record(self, "submit", t0)
        return req

    def _admit(self, op_kind, op_key, op_val, host, tenant,
               t_submit: float) -> TxnRequest:
        req = TxnRequest(next(self._req_ids), np.asarray(op_kind, np.int32),
                         np.asarray(op_key, np.int32),
                         np.asarray(op_val, np.int32), int(host),
                         tenant=int(tenant), t_submit=t_submit)
        self.requests.append(req)
        self._tstat(req.tenant)["offered"] += 1
        if (self.replicas is not None
                and self.replicas.can_serve(req.op_kind, req.op_key)):
            # visibility-cheap replica read (DESIGN.md §11.3): a read-only
            # txn over replicated keys commits AT SUBMIT TIME with
            # s = c = the replica's visibility floor — zero coordination,
            # never enters the engine; validity is the watermark-freeze
            # invariant (versions visible at the floor are immutable)
            _, floor = self.replicas.serve(req.op_kind, req.op_key)
            req.status = "committed"
            req.replica = True
            req.arrive_tick = self.tick
            req.commit_tick = self.tick
            req.s = req.c = int(floor)
            req.attempts = 1
            self.committed += 1
            self.replica_commits += 1
            self.latencies.append(req.latency)
            st = self._tstat(req.tenant)
            st["committed"] += 1
            st["replica_commits"] += 1
            st["latencies"].append(req.latency)
            self.gc.observe_replica(
                floor, n_reads=int((req.op_kind != NOP).sum()))
            return req
        self.former.offer(req, self.tick + 1)     # eligible from next tick
        return req

    # ------------------------------------------------------------- loop
    def step(self):
        """One scheduler tick: form a wave, execute it, route outcomes.
        Returns the numpy ``WaveOut`` or ``None`` for an idle tick."""
        self.tick += 1
        with stage(self, "step"):
            return self._step()

    def _step(self):
        if (self.replicas is not None
                and self.tick % self.replica_refresh == 0):
            self._refresh_replicas()
        formed = self.former.form(self.tick)
        if formed is None:
            self.idle_ticks += 1
            return None
        wave, slots = formed
        if self.planner is not None and self.planner.planned:
            return self._step_planned(wave, slots)
        self.wave_idx += 1
        wm = self._watermark()
        if self.faults is not None:
            self.faults.at_dispatch(self)
        t_dispatch = time.perf_counter()
        self.store, out, self.clock = self._step_wave(wave, wm)
        if self.faults is not None:
            self.faults.at_retire(self)
        self.gc.observe(out, int(self.clock))
        self.history.append((np.asarray(wave.tid), out))
        if self.durability is not None:
            # the step loop retires every wave synchronously: log it as a
            # B=1 block, durable BEFORE its outcomes are acked below
            self.durability.log_block(
                Wave(*(np.asarray(getattr(wave, f))[None]
                       for f in Wave._fields)),
                self.wave_idx, wm, WaveOut(*(np.asarray(x)[None]
                                             for x in out)),
                int(self.clock), self.gc.clock,
                fold=fold_counts(slots,
                                 np.asarray(wave.op_kind).shape[0])[None])
            if self.faults is not None:
                self.faults.post_log(self)
        self._route(out, slots, t_dispatch)
        self._observe_placement(wave, out, slots)
        if self.planner is not None:
            self.planner.observe_optimistic(
                len(slots), int((out.status[:len(slots)] == ABORTED).sum()))
        if self.durability is not None:
            self.durability.maybe_snapshot(self, pipeline_empty=True)
        return out

    def _step_planned(self, wave, slots):
        """Planned-mode tick half (DESIGN.md §10): plan the formed wave
        into conflict-free lanes and execute them as ONE pow2 wave block
        through the configured data plane (local or mesh — same engine
        rules per lane), then route the merged per-row outcomes exactly
        like an optimistic wave.  Lane rows commit abort-free; only spilled
        rows can re-enter the retry calendar."""
        from repro.planner.sched import run_wave_planned
        wave_idx0 = self.wave_idx + 1
        wm = self._watermark()
        if self.faults is not None:
            self.faults.at_dispatch(self)
        t_dispatch = time.perf_counter()
        self.store, self.clock, pw = run_wave_planned(
            self.store, wave, self.clock, wave_idx0=wave_idx0,
            next_tid=self.former.next_tid, sched=self.sched,
            n_nodes=self.n_nodes, mesh=self.mesh, kernels=self.kernels,
            watermark=wm, host_skew=self.host_skew, gc_block=self.gc.block,
            max_lanes=self.planner.max_lanes,
            placement=self._placement_arrays())
        if self.faults is not None:
            self.faults.at_retire(self)
        # the planner relabeled every row with fresh contiguous tids (lane
        # waves need their own [tid0, tid0+T) ranges); advance the former's
        # counter past them and point each request at the tid it ran under,
        # so history rows, requests and store versions all agree
        self.wave_idx += pw.waves_consumed
        self.former.next_tid += pw.tids_consumed
        out = pw.merged
        self.gc.observe(out, int(self.clock))
        self.history.append((pw.exec_tid, out))
        self.planned_waves += 1
        self.planned_lane_waves += pw.lane_waves + pw.spill_waves
        self.planned_spilled += pw.plan.n_spilled
        if self.durability is not None:
            # the dispatched block IS an ordinary wave block: logged as-is,
            # recovery replays it through run_block under the base sched.
            # Fold multiplicities ride along at each request's EXECUTED
            # row (the planner relabeled rows into lanes; exec_tid maps a
            # slot to its contiguous position in the stacked block), so
            # RecoveredState.folded_requests accounts planned runs exactly
            # like the step and streaming paths
            fold = np.zeros(pw.stacked.tid.shape, np.int32)
            tid0 = int(pw.stacked.tid[0, 0])
            T_pad = pw.stacked.tid.shape[1]
            for i, req in enumerate(slots):
                off = int(pw.exec_tid[i]) - tid0
                fold[off // T_pad, off % T_pad] = 1 + len(req.folded)
            self.durability.log_block(pw.stacked, wave_idx0, wm, pw.outs,
                                      int(self.clock), self.gc.clock,
                                      fold=fold)
            if self.faults is not None:
                self.faults.post_log(self)
        for i, req in enumerate(slots):
            for r in (req, *req.folded):
                r.tid = int(pw.exec_tid[i])
                r.tids[-1] = r.tid
        self._route(out, slots, t_dispatch)
        self._observe_placement(wave, out, slots)
        self.planner.observe_planned(
            len(slots), pw.plan.conflicted + pw.plan.n_spilled)
        if self.durability is not None:
            self.durability.maybe_snapshot(self, pipeline_empty=True)
        return out

    def _route(self, out, slots, t_dispatch: float):
        """Route one synced wave's per-txn outcomes: commits record latency,
        aborts re-enter the retry calendar or drop.  Shared by the per-wave
        step loop and the streaming driver's block retirement (which calls
        it once per wave of a retired block).  Every committed request is
        stamped with ``t_dispatch``, when the wave's block went to the
        device, and ``t_ack``, now.

        A folded row (DESIGN.md §12.2) fans its outcome out to every member
        request exactly once: on commit all members commit with the row's
        (s, c) — the summed delta IS their serial net effect — and on abort
        each member re-enters the retry calendar individually (it may fold
        into a different group next wave)."""
        t_ack = time.perf_counter()
        for i, req in enumerate(slots):
            group = (req, *req.folded)
            req.folded = []
            self.executions += len(group)
            if out.status[i] == COMMITTED:
                for r in group:
                    r.status = "committed"
                    r.t_dispatch, r.t_ack = t_dispatch, t_ack
                    r.commit_tick = self.tick
                    r.s, r.c = int(out.s[i]), int(out.c[i])
                    self.committed += 1
                    self.latencies.append(r.latency)
                    st = self._tstat(r.tenant)
                    st["committed"] += 1
                    st["latencies"].append(r.latency)
            else:
                for r in group:
                    delay = self.retry.next_delay(r.attempts, self.rng)
                    if delay is None:
                        r.status = "dropped"
                        self.dropped += 1
                        self._tstat(r.tenant)["dropped"] += 1
                    else:
                        self.retries += 1
                        self._tstat(r.tenant)["retries"] += 1
                        self.former.requeue(r, self.tick + delay)

    def _watermark(self):
        """The GC watermark for the next dispatch.  Single-device: the
        tracker's min over pins (or None for the engine's wave-boundary
        collapse).  Mesh: per-node live-reader floors merged by a pmin
        collective — never a host-side reduction; with no pins the engine's
        own collapse applies (None).  Under pipelined streaming the
        tracker's clock is the clock of the *retired* prefix, which can only
        under-estimate the true floor — a lower watermark is conservative,
        never unsafe."""
        if self.mesh is None:
            return self.gc.watermark()
        if not self.gc.pinned:
            return None
        from repro.core.dist_engine import mesh_watermark
        return mesh_watermark(self.mesh,
                              self.gc.node_floors(self.mesh.devices.size))

    def _step_wave(self, wave, wm):
        """Dispatch one formed wave to the configured data plane under the
        given GC watermark (``_watermark()`` at dispatch time — the caller
        computes it once so the WAL can log exactly what ran)."""
        if self.mesh is None:
            return step_wave(
                self.store, wave, self.wave_idx, self.clock, sched=self.sched,
                n_nodes=self.n_nodes, host_skew=self.host_skew,
                watermark=wm, gc_block=self.gc.block,
                kernels=self.kernels, placement=self._placement_arrays())
        from repro.core.dist_engine import step_wave_dist
        return step_wave_dist(
            self.store, wave, self.wave_idx, self.clock, self.mesh,
            sched=self.sched, n_nodes=self.n_nodes, host_skew=self.host_skew,
            watermark=wm, gc_block=self.gc.block,
            kernels=self.kernels, placement=self._placement_arrays())

    def _run_block(self, stacked):
        """Dispatch a [B]-stacked wave block to the configured data plane
        WITHOUT syncing the host (the streaming driver's dispatch half:
        store/clock advance as device futures, outcomes are materialized
        only when the driver retires the block).  Returns (outs, clock);
        ``_last_dispatch`` records the (wave_idx0, watermark) this dispatch
        consumed, so the retirement path can WAL-log a replayable record."""
        B = stacked.op_kind.shape[0]
        wave_idx0 = self.wave_idx + 1
        self.wave_idx += B
        wm = self._watermark()
        self._last_dispatch = (wave_idx0, wm)
        if self.mesh is None:
            self.store, outs, self.clock = run_block(
                self.store, stacked, wave_idx0, self.clock, sched=self.sched,
                n_nodes=self.n_nodes, host_skew=self.host_skew,
                watermark=wm, gc_block=self.gc.block,
                kernels=self.kernels, placement=self._placement_arrays())
        else:
            from repro.core.dist_engine import run_block_dist
            self.store, outs, self.clock = run_block_dist(
                self.store, stacked, wave_idx0, self.clock, self.mesh,
                sched=self.sched, n_nodes=self.n_nodes,
                host_skew=self.host_skew, watermark=wm,
                gc_block=self.gc.block, kernels=self.kernels,
                placement=self._placement_arrays())
        return outs, self.clock

    # ------------------------------------------------- elastic placement
    def _placement_arrays(self):
        """Device-side (owner, slot) arrays of the current placement, or
        ``None`` when static (cached by the PlacementMap until a move)."""
        return (None if self.placement is None
                else self.placement.device_arrays())

    def _refresh_replicas(self):
        """Re-snapshot the hot-key replicas at the current visibility floor
        (the merged GC watermark; the engine's boundary-collapse clock when
        no pins exist).  The floor only moves forward, so no invalidation
        traffic exists — one batched gather IS the replication protocol."""
        wm = self._watermark()
        floor = int(self.gc.clock) if wm is None else int(wm)
        slot_of = None if self.placement is None else self.placement.slot
        self.replicas.refresh(self.store, floor, slot_of=slot_of)

    def _observe_placement(self, wave, out, slots):
        """Fold one retired wave into placement-plane accounting (per-node
        committed-txn occupancy under the CURRENT placement) and let the
        balancer trigger live range moves at its block boundary."""
        if self.placement is None:
            return
        T = len(slots)
        kinds = np.asarray(wave.op_kind)[:T]
        keys = np.asarray(wave.op_key)[:T]
        status = np.asarray(out.status)[:T]
        owner = self.placement.owner
        active = kinds != NOP
        committed = status == COMMITTED
        sel = committed & active.any(axis=1)
        if sel.any():
            first = np.argmax(active, axis=1)
            np.add.at(self._occupancy,
                      owner[keys[np.arange(T), first][sel]], 1)
        if self.balancer is None:
            return
        self.balancer.observe(keys, active, committed, owner)
        if self.balancer.end_block():
            for lo, hi, dst in self.balancer.plan(self.placement):
                self.move_range(lo, hi, dst)

    def move_range(self, lo: int, hi: int, dst: int):
        """Live-repartition logical keys ``[lo, hi)`` onto node ``dst`` at a
        wave boundary: plan slot assignments on the PlacementMap, relocate
        the version rings in one device program (psum gather + owner-masked
        scatter on the mesh), commit the map mutation, and WAL-log the
        explicit record so recovery replays the move bit-identically.
        Between waves no transaction is in flight, every retired outcome is
        durable, and the engine's outcomes are placement-invariant — so the
        move needs no quiescence protocol beyond the boundary itself.
        Returns the applied ``MoveRecord`` (``None`` if nothing moved)."""
        if self.placement is None:
            raise ValueError("move_range needs an elastic placement")
        if self.stream is not None:
            self.stream.flush()          # no dispatched block may be in flight
        rec = self.placement.move(lo, hi, dst)
        if rec.keys.size == 0:
            return None
        self.store = apply_move(self.store, rec, mesh=self.mesh)
        self.placement.apply_record(rec)
        self.placement_moves += 1
        self.moved_keys += int(rec.keys.size)
        if self.durability is not None:
            self.durability.log_move(rec, int(self.clock))
        return rec

    def drain(self, max_ticks: Optional[int] = None) -> int:
        """Run ticks until no request is pending (or the safety cap).
        Returns the number of ticks consumed."""
        if max_ticks is None:
            max_ticks = (self.retry.worst_case_ticks()
                         + self.former.pending() // max(self.T, 1) + 8)
        n = 0
        while self.former.pending() and n < max_ticks:
            self.step()
            n += 1
        return n

    def _submit_tick(self, n_arr, txn_gen):
        """Submit one tick's arrivals.  Scalar ``n_arr``: that many calls of
        ``txn_gen()`` (4-tuples, default tenant).  1-D ``n_arr`` of length
        n_tenants: per-tenant counts, each from ``txn_gen(tenant)`` which
        must return a 5-tuple ending in the tenant tag (see
        ``tenant_txn_gen``)."""
        arr = np.asarray(n_arr)
        if arr.ndim == 0:
            for _ in range(int(arr)):
                self.submit(*txn_gen())
        else:
            for tenant, cnt in enumerate(arr):
                for _ in range(int(cnt)):
                    self.submit(*txn_gen(tenant))

    def run_stream(self, arrivals: Iterable,
                   txn_gen: Callable, drain: bool = True):
        """Feed ``arrivals[t]`` fresh requests per tick (from ``txn_gen``,
        which returns ``(op_kind, op_key, op_val, host)``), stepping once
        per tick; optionally drain the backlog afterwards.  A 2-D arrivals
        array ``[n_ticks, n_tenants]`` feeds a multi-tenant stream: column
        ``t`` arrives via ``txn_gen(t)`` (see ``tenant_txn_gen``)."""
        for n_arr in arrivals:
            self._submit_tick(n_arr, txn_gen)
            self.step()
        if drain:
            self.drain()
        return self.report()

    def run_streaming(self, arrivals: Iterable[int],
                      txn_gen: Callable[[], tuple], B: int = 4, K: int = 2,
                      sizer=None, drain: bool = True):
        """Serve the same open stream through the pipelined streaming plane
        (DESIGN.md §8): waves are batched into blocks of ``B`` and executed
        as ONE fused device program each (``engine.run_block``), with up to
        ``K`` dispatched blocks in flight — the host forms the next block(s)
        while the device runs, and a block's outcomes are synced (and its
        aborts routed to retry) only when it retires.

        ``B=1, K=1`` degenerates to the synchronous ``run_stream`` loop and
        is bit-identical to it; larger B/K trade retry-routing latency for
        dispatch amortization.  ``sizer`` — an
        ``stream.AdaptiveWaveSizer`` (or ``"auto"``) — additionally
        regulates the wave size T (and optionally B) from the trailing
        abort rate, the paper's §V-D contention regulation in open-stream
        form.  Returns the end-of-run ``ServiceReport``."""
        from .stream import AdaptiveWaveSizer, StreamingDriver
        if sizer == "auto":
            sizer = AdaptiveWaveSizer(T0=self.T, B0=B,
                                      t_min=min(8, self.T), adapt_B=True)
        driver = StreamingDriver(self, B=B, K=K, sizer=sizer)
        self.stream = driver                 # expose pipeline state/stats
        for n_arr in arrivals:
            self._submit_tick(n_arr, txn_gen)
            driver.tick()
        if drain:
            driver.drain()
        else:
            driver.flush()
        return self.report()

    # ------------------------------------------------------------ output
    def report(self) -> ServiceReport:
        wall = max(sum(self.stage_s.get(k, 0.0)
                       for k in ("tick", "flush", "step")), 1e-9)
        admitted = self.former.admitted
        return ServiceReport(
            sched=self.sched,
            offered=len(self.requests),
            admitted=admitted,
            rejected=self.former.rejected,
            committed=self.committed,
            dropped=self.dropped,
            retries=self.retries,
            executions=self.executions,
            waves=self.wave_idx,
            idle_ticks=self.idle_ticks,
            wall_s=round(wall, 6),
            txns_per_sec=round(self.executions / wall, 1),
            goodput_tps=round(self.committed / wall, 1),
            retry_rate=round(self.retries / max(admitted, 1), 4),
            latency_p50=_pct(self.latencies, 50),
            latency_p95=_pct(self.latencies, 95),
            latency_p99=_pct(self.latencies, 99),
            evicted_visible=self.gc.evicted_visible,
            gc=self.gc.report(),
            blocks=self.blocks,
            planned_waves=self.planned_waves,
            planned_lane_waves=self.planned_lane_waves,
            planned_spilled=self.planned_spilled,
            planner_switches=(self.planner.switches
                              if self.planner is not None else 0),
            replica_commits=self.replica_commits,
            replica_refreshes=(self.replicas.refreshes
                               if self.replicas is not None else 0),
            placement_moves=self.placement_moves,
            moved_keys=self.moved_keys,
            imbalance=self._imbalance(),
            occupancy=([] if self._occupancy is None
                       else self._occupancy.tolist()),
            tenants=self._tenant_report(),
            fold_groups=self.former.fold_groups,
            folded_requests=self.former.folded_requests,
            stage_s=dict(self.stage_s),
            stage_n=dict(self.stage_n),
        )

    def _tenant_report(self) -> Dict[str, Dict]:
        """Per-tenant rows (keys stringified for JSON): admission counters
        from the former joined with the service-side outcome/latency
        accounting.  Single-tenant runs report one row for tenant \"0\".

        ``replica_commits`` counts reads answered from hot-key replicas AT
        SUBMIT TIME — those never pass admission, so a row's ``committed``
        can exceed ``admitted`` by exactly that amount; fairness analyses
        over engine capacity should use ``committed - replica_commits``."""
        former_stats = self.former.tenant_stats()
        rows: Dict[str, Dict] = {}
        for t in sorted(set(former_stats) | set(self._tenant_stats)):
            fs = former_stats.get(t, {})
            st = self._tenant_stats.get(t, {})
            lat = st.get("latencies", [])
            rows[str(t)] = {
                "weight": float(fs.get("weight", 1.0)),
                "offered": int(st.get("offered", 0)),
                "admitted": int(fs.get("admitted", 0)),
                "rejected": int(fs.get("rejected", 0)),
                "committed": int(st.get("committed", 0)),
                "replica_commits": int(st.get("replica_commits", 0)),
                "dropped": int(st.get("dropped", 0)),
                "retries": int(st.get("retries", 0)),
                "latency_p50": _pct(lat, 50),
                "latency_p95": _pct(lat, 95),
                "latency_p99": _pct(lat, 99),
            }
        return rows

    def _imbalance(self) -> float:
        """Max/mean per-node committed-txn occupancy under the current
        placement (1.0 = perfectly balanced; 0.0 when static or empty)."""
        if self._occupancy is None or self._occupancy.sum() == 0:
            return 0.0
        occ = self._occupancy.astype(np.float64)
        return round(float(occ.max() / occ.mean()), 4)

    def verify(self) -> List[str]:
        """Post-hoc correctness of the served history: SI (or CV) validity
        plus final-store-matches-serial-replay, via ``repro.core.verify``."""
        check = verify_cv if self.sched == "cv" else verify_si
        errors = check(self.history, base_store=self.base_store)
        # the history speaks logical keys; under an elastic placement the
        # final store is in physical slot order — permute it back before
        # the serial-replay comparison (moves don't change ring contents)
        store = (self.store if self.placement is None
                 else logical_store(self.store, self.placement))
        errors += final_values_ok(store, self.history, self.n_keys)
        return errors


def smallbank_txn_gen(rng: np.random.RandomState, n_nodes: int,
                      keys_per_node: int, dist_frac: float = 0.2,
                      hot_frac: float = 0.0, hot_per_node: int = 20):
    """Request factory for ``run_stream``: SmallBank transactions on random
    host nodes (the open-stream analogue of ``workloads.smallbank_waves``)."""
    def gen():
        host = int(rng.randint(0, n_nodes))
        op_kind, op_key, op_val = smallbank_txn(
            rng, host, n_nodes, keys_per_node, dist_frac, hot_frac,
            hot_per_node)
        return op_kind, op_key, op_val, host
    return gen


def ycsb_txn_gen(rng: np.random.RandomState, n_nodes: int,
                 keys_per_node: int, theta: float = 0.9,
                 read_frac: float = 0.8, dist_frac: float = 0.1,
                 n_ops: int = 4):
    """Request factory for the streaming plane: YCSB-style transactions with
    zipfian key skew ``theta`` on random host nodes (paper §V-D's
    skew/contention regime as an open stream — ``theta=0`` is uniform,
    ``theta>=0.9`` concentrates traffic on each node's rank-0 hot keys).
    ``read_frac``/``dist_frac``/``n_ops`` mirror ``workloads.ycsb_txn``."""
    def gen():
        host = int(rng.randint(0, n_nodes))
        op_kind, op_key, op_val = ycsb_txn(
            rng, host, n_nodes, keys_per_node, theta, read_frac, dist_frac,
            n_ops)
        return op_kind, op_key, op_val, host
    return gen


def rmw_txn_gen(rng: np.random.RandomState, n_nodes: int,
                keys_per_node: int, theta: float = 0.99, n_ops: int = 4,
                val_max: int = 8):
    """Request factory for the write-hot regime the fold plane targets
    (DESIGN.md §12.2): every transaction is a SINGLE zipfian RMW (op slot 0
    active, the rest NOP padding) with a small positive delta — θ=0.99
    concentrates the stream on each host's rank-0 key, the workload where
    unfolded same-key RMWs serialize via lost-update retries."""
    from repro.core.workloads import rmw_hot_txn

    def gen():
        host = int(rng.randint(0, n_nodes))
        op_kind, op_key, op_val = rmw_hot_txn(
            rng, host, n_nodes, keys_per_node, theta, n_ops, val_max)
        return op_kind, op_key, op_val, host
    return gen


def tenant_txn_gen(gens):
    """Compose per-tenant request factories for 2-D ``run_stream``
    arrivals: ``gens[t]()`` returns ``(op_kind, op_key, op_val, host)``;
    the returned ``gen(tenant)`` appends the tenant tag that
    ``TxnService.submit`` consumes."""
    def gen(tenant: int):
        return (*gens[tenant](), tenant)
    return gen
