"""One run of one cell: set-up, the measured window, the follow-up of the
window's requests, and the check that decides ``correct``.

Everything that belongs to one configuration, traffic mix, transaction
mix or per-layer metric lives in a file of its own, found by its name
(``chipbench/files.py``):

    chipbench/configs/<config>.json    deployment: sizes, mix, service
    chipbench/traffic/<traffic>.json   arrivals: open rate or closed clients
    chipbench/mixes/<kind>.py          transactions: ``draw``, ``check``, and
                                       optionally ``initial``, a loaded table
    chipbench/metrics/<metric>.py      reader: ``read(ctx) -> float | None``

A configuration's optional ``service`` object is passed to ``TxnService``
as keyword arguments, and a mix's loaded table as the keyword ``initial``.
The harness hands the program each generated row as it is and reads back
every per-row output, every per-key store field and every request's
``read_val``, whatever their trailing shapes, so a mix whose records are
wider than one int32 needs no edit here.  It holds no store of the
program's across a block, so the program may donate its store to the
block program.

The program serves on logical ticks (``TxnService``, ``StreamingDriver``);
this harness supplies the wall clock.  It calls ``TxnService.submit`` and
``StreamingDriver.tick`` in one loop, stamps each request's due time and
submit time, and stamps the end of every tick: a request is acknowledged
at the end of the tick in which the service routed its commit
(``TxnRequest.commit_tick``).
"""
from __future__ import annotations

import contextlib
import gc as _gc
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import gen, reference
from chipbench import trace as tracemod
from chipbench.files import ROOT, BenchError, load_json, load_module
from chipbench.load import ClientPool, OpenLoop

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DONE = ("committed", "dropped", "rejected")
FOLLOW_S = 60.0           # how long past the window's close a request due
                          # in the window is waited for
STREAM_LEN = 1 << 20      # closed-loop stream; wraps past its end
BLOCK_SHAPES = (4, 2, 1)  # power-of-two block sizes the driver dispatches


# ----------------------------------------------------------------- lookup
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str, root: str = ROOT) -> dict:
    return load_json("configs", name, root)


def load_traffic(name: str, root: str = ROOT) -> dict:
    return load_json("traffic", name, root)


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    return load_module("metrics", name, root).read


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if cell in m.get("workloads", [w["name"]
                                           for w in spec["workloads"]])]


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def mix_of(cfg: dict) -> dict:
    """The configuration's transaction mix, with the key layout filled in."""
    return dict(cfg["mix"], n_nodes=cfg["n_nodes"],
                keys_per_node=cfg["n_keys"] // cfg["n_nodes"])


# ------------------------------------------------------------ compile count
class CompileClock:
    """Counts backend compiles, and sums their seconds, while entered."""

    def __init__(self):
        self.n, self.secs = 0, 0.0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += duration


# ------------------------------------------------------------------ serving
class Served:
    """The system under test and the stamps the harness keeps of it."""

    def __init__(self, cfg: dict, seed: int, kernels=None, initial=None):
        """``initial``, a mix's loaded table (``gen.initial``), reaches
        ``TxnService`` as the keyword ``initial`` where given."""
        import jax
        from repro.service import TxnService
        from repro.service.stream import StreamingDriver
        mesh = None
        if cfg["chips"] > 1:
            from repro.core.dist_engine import make_node_mesh
            mesh = make_node_mesh(cfg["chips"])
        self.cfg = cfg
        self.svc = TxnService(
            n_keys=cfg["n_keys"], n_versions=cfg["n_versions"], T=cfg["T"],
            O=cfg["O"], sched=cfg["scheduler"],
            n_nodes=cfg["n_nodes"], seed=seed % 2 ** 32, mesh=mesh,
            kernels=kernels, **cfg.get("service", {}),
            **({} if initial is None else {"initial": initial}))
        self.drv = StreamingDriver(self.svc, B=cfg["B"], K=cfg["K"])
        self.svc.stream = self.drv
        jax.block_until_ready(self.svc.store)
        self.tick_end: List[float] = [time.perf_counter()]

    def nop_block(self, B: int, val_row: tuple):
        """A block of ``B`` NOP waves in the shapes the service dispatches:
        its own (``TxnService.nop_block``) where it offers one, else waves
        stacked from rows shaped as the harness submits them, ``val_row``
        being one row's ``Txns.val`` shape."""
        svc = self.svc
        if hasattr(svc, "nop_block"):
            return svc.nop_block(B)
        from repro.core import Wave
        T = svc.T
        zeros = lambda *shape: np.zeros((B, T) + shape, np.int32)
        return Wave(op_kind=zeros(svc.O), op_key=zeros(svc.O),
                    op_val=zeros(*val_row), host=zeros(),
                    tid=2 ** 30 + np.arange(B * T, dtype=np.int32)
                    .reshape(B, T))

    def warm_shapes(self, val_row: tuple) -> None:
        """Run every block shape the driver can dispatch, on NOP waves and
        with the argument types the service passes (``nop_block``), on the
        service's live store, and keep the store each block returns: the
        program may donate the store it is given, and a NOP block leaves
        every field of it as it was.  The wave index, the block count and
        the clock are put back as they were found, so the program may
        donate its store but not its clock.  On a mesh each shape runs
        twice, first on the service's initial clock and then on the clock
        a block returns, because there the two carry different shardings
        and so are different programs; after the first block the store
        carries a block's output sharding, the one serving uses."""
        import jax
        svc, cfg = self.svc, self.cfg
        wave_idx, blocks, clock = svc.wave_idx, svc.blocks, svc.clock
        for B in BLOCK_SHAPES:
            if B > cfg["B"]:
                continue
            nop = self.nop_block(B, val_row)
            for _ in range(2 if cfg["chips"] > 1 else 1):
                outs, _ = svc._run_block(nop)
                jax.block_until_ready(outs)
                svc.wave_idx = wave_idx
            svc.clock = clock
        svc.blocks = blocks
        jax.block_until_ready(svc.store)

    def tick(self) -> None:
        self.drv.tick()
        self.tick_end.append(time.perf_counter())


class Load:
    """Drives ``Served`` with a traffic mix and records every request."""

    def __init__(self, served: Served, traffic: dict, txns: gen.Txns,
                 due: Optional[np.ndarray]):
        self.s = served
        self.svc = served.svc
        self.traffic = traffic
        self.txns = txns
        self.open = traffic["loop"] == "open"
        self.loop = OpenLoop(due) if self.open else ClientPool(
            traffic["clients"], len(txns))
        self.reqs: List = []           # submitted requests, in order
        self.row: List[int] = []       # their rows in ``txns``
        self.due: List[float] = []     # due time (closed: send time)
        self.sent: List[float] = []    # submit time
        self.spans: List[tuple] = []   # (start, seconds, requests) per batch
        self.t0 = time.perf_counter()  # the due times' origin
        self.ramping = True
        self.annotate = False          # name host spans in a trace
        self._done_mark = -1

    # -- sending
    def _send(self, idx, due_of) -> None:
        svc, x = self.svc, self.txns
        t_b = time.perf_counter()
        for i, extra in idx:
            t = time.perf_counter()
            req = svc.submit(x.kind[i], x.key[i], x.val[i], int(x.host[i]))
            self.reqs.append(req)
            self.row.append(i)
            self.due.append(due_of(i, t))
            self.sent.append(t)
            if extra is not None:
                self.loop.sent(extra, req)
        if idx:
            self.spans.append((t_b, time.perf_counter() - t_b, len(idx)))

    def room(self) -> int:
        """Requests admission takes now without shedding any."""
        former = self.svc.former
        return max(0, former.max_queue - former.pending())

    def feed(self, now: float) -> None:
        if self.open:
            # What falls due is sent as admission has room for it, and the
            # rest waits here with its due time: when the host stands
            # still, the arrivals due meanwhile come as a burst that the
            # users did not send, and its wait counts in the latency.
            rng = self.loop.take(now - self.t0, self.room())
            due = self.loop.due
            self._send([(i, None) for i in rng],
                       lambda i, t: self.t0 + float(due[i]))
            return
        limit = len(self.loop.idle)
        if self.ramping:      # enter no faster than admission takes them
            limit = min(limit, self.room())
        self._send([(i, c) for c, i in self.loop.take(limit)],
                   lambda i, t: t)

    def after_tick(self) -> None:
        if self.open:
            return
        svc = self.svc
        mark = svc.committed + svc.dropped + svc.former.rejected
        if mark != self._done_mark:
            self._done_mark = mark
            self.loop.reap(lambda r: r.status in DONE)

    def idle_wait(self, now: float, until: float) -> None:
        """Sleep to the next due time when nothing is in the system."""
        if not self.open:
            return
        svc = self.svc
        if len(self.reqs) > svc.committed + svc.dropped + svc.former.rejected:
            return
        wait = min(self.loop.until_next(now - self.t0), until - now)
        if wait > 0:
            time.sleep(min(wait, 0.002))

    def pump(self, until: float, sending: bool = True, stop=None) -> None:
        """Submit and tick until the wall clock reaches ``until`` or
        ``stop()`` holds."""
        span = _span if self.annotate else _no_span
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                return
            if sending:
                with span("chipbench.submit"):
                    self.feed(now)
            with span("chipbench.tick"):
                self.s.tick()
            with span("chipbench.harness"):
                self.after_tick()
            with span("chipbench.wait"):
                self.idle_wait(time.perf_counter(), until)


@contextlib.contextmanager
def collector_off():
    """Python's cyclic garbage collector off while the harness builds and
    serves, and everything alive before frozen out of collections.  The
    harness keeps a record of every request it sends, a few hundred
    thousand objects the served system would not hold; a full collection
    over them pauses the serving loop long enough to shed a burst of
    open-loop arrivals."""
    _gc.collect()
    _gc.freeze()
    _gc.disable()
    try:
        yield
    finally:
        _gc.enable()
        _gc.unfreeze()


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


_no_span = lambda name: contextlib.nullcontext()


def counters(svc) -> Dict[str, int]:
    return {"pending": svc.former.pending(),
            "executions": svc.executions, "committed": svc.committed,
            "dropped": svc.dropped, "rejected": svc.former.rejected,
            "waves": svc.wave_idx, "blocks": svc.blocks, "tick": svc.tick}


def stages(svc) -> tuple:
    """The service's stage timers now: seconds and count per stage."""
    return dict(svc.stage_s), dict(svc.stage_n)


def latency_parts(reqs, due, sent, tick_end) -> Dict[str, np.ndarray]:
    """Commit latency of each committed request of ``reqs``, and its five
    parts (``PARTS``) on the harness's clock, from its due and send time
    and the request's own stamps (``TxnRequest.t_submit``,
    ``t_dispatch``, ``t_ack``): they add up to it."""
    ok = [i for i, r in enumerate(reqs) if r.status == "committed"]
    col = lambda f: np.fromiter((f(i) for i in ok), np.float64, len(ok))
    ack = col(lambda i: tick_end[reqs[i].commit_tick])
    t_sub = col(lambda i: reqs[i].t_submit)
    t_dis = col(lambda i: reqs[i].t_dispatch)
    t_ack = col(lambda i: reqs[i].t_ack)
    d, s = col(lambda i: due[i]), col(lambda i: sent[i])
    return {"latency": ack - d, "gen_lag": s - d, "admit": t_sub - s,
            "queue_wait": t_dis - t_sub, "block_turn": t_ack - t_dis,
            "tick_rest": ack - t_ack}


PARTS = ("gen_lag", "admit", "queue_wait", "block_turn", "tick_rest")


# -------------------------------------------------------------------- checks
def history_of(svc) -> reference.History:
    """The served history, wave after wave: every output with one row per
    transaction, the named ones as ``History``'s fields and any other in
    ``History.extra``."""
    h = svc.history
    rows = {}
    for f in h[0][1]._fields:
        parts = [np.asarray(getattr(o, f)) for _, o in h]
        if all(p.ndim and len(p) == len(t) for p, (t, _) in zip(parts, h)):
            rows[f] = np.concatenate(parts)
    named = {f: rows.pop(f) for f in reference.History._fields
             if f not in ("tid", "extra")}
    return reference.History(
        tid=np.concatenate([np.asarray(t) for t, _ in h]), **named,
        extra=rows)


def answers_of(reqs) -> reference.Answers:
    """The program's answer to each request.  Where any request carries a
    ``read_val`` (the values its committed execution read, as the service
    routed them), ``Answers.extra`` holds them stacked, ``read_val`` of
    ``[n, O, ...]`` with zeros where a request carries none, and the
    ``[n]`` mask ``has_read_val`` of those that do."""
    n = len(reqs)
    committed = np.fromiter((r.status == "committed" for r in reqs), bool, n)
    last = np.fromiter((r.tid for r in reqs), np.int64, n)
    counts = np.fromiter((len(r.tids) for r in reqs), np.int64, n)
    exec_tid = np.fromiter((t for r in reqs for t in r.tids), np.int64,
                           int(counts.sum()))
    extra = {}
    vals = [getattr(r, "read_val", None) for r in reqs]
    has = np.fromiter((v is not None for v in vals), bool, n)
    if has.any():
        got = np.stack([v for v in vals if v is not None])
        read_val = np.zeros((n,) + got.shape[1:], got.dtype)
        read_val[has] = got
        extra = {"read_val": read_val, "has_read_val": has}
    return reference.Answers(committed, last,
                             np.repeat(np.arange(n), counts), exec_tid,
                             extra=extra)


def store_of(store, n_keys: int) -> tuple:
    """Host copies of the final store, read from each field's owning
    shards (one shard on one chip); also the devices that hold them.
    Every field with one row per key (as many as ``val``, a mesh's padding
    rows cut off) is read: the named ones as ``Store``'s fields and any
    other in ``Store.extra``.  Each field reaches the host once, as one
    read-only array that JAX fills shard by shard."""
    rows = {f: np.asarray(a)[:n_keys] for f, a in store._asdict().items()
            if a.ndim and a.shape[0] == store.val.shape[0]}
    named = {f: rows.pop(f) for f in reference.Store._fields
             if f != "extra"}
    devices = sorted({str(s.device) for s in store.val.addressable_shards})
    return reference.Store(**named, extra=rows), devices


# ---------------------------------------------------------------------- run
def percentile(x: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failures) sort last."""
    if len(x) == 0:
        return float("nan")
    s = np.sort(x)
    return float(s[max(0, int(np.ceil(q / 100.0 * len(s))) - 1)])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, spec: dict, kernels=None,
             traffic: Optional[dict] = None,
             root: str = ROOT) -> dict:
    """Run one cell; returns ``{"line": result line, "info": what the
    run saw besides}`` (compiles, window counters, check time) and the
    ``ctx`` its per-layer readers read.  Beside the harness's own
    readings, ``ctx`` carries what the program records of the window: the
    deltas of its stage timers (``stage_s``, ``stage_n``, at the marks of
    ``window``), each committed request's latency parts from its own
    stamps (``latency_parts``, and ``queue_wait_s``, ``block_turn_s``),
    and in a traced run its device time by named scope (``scoped``,
    ``trace.Scoped``; None untraced).

    ``kernels`` and ``traffic`` override the kernel backend and the cell's
    traffic mix (for the CPU tests and the knee sweep); ``root`` is the
    checkout whose ``chipbench/`` holds the cell's files."""
    import jax
    cfg = dict(load_config(cell["config"], root), chips=cell["chips"])
    traffic = traffic or load_traffic(cell["traffic"], root)
    mix = gen.load_mix(cfg["mix"]["kind"], root)
    if traffic["loop"] == "open":
        segments = (traffic["warmup_s"], seconds, FOLLOW_S)
        due = gen.arrivals(seed, traffic["rate_txn_s"], segments)
        txns = gen.draw(mix_of(cfg), seed, len(due), root)
    else:
        due = None
        txns = gen.draw(mix_of(cfg), seed, STREAM_LEN, root)
    table = gen.initial(mix_of(cfg), seed, cfg["n_keys"], root)

    with CompileClock() as clk, collector_off():
        served = Served(cfg, seed, kernels=kernels, initial=table)
        served.warm_shapes(txns.val.shape[1:])
        load = Load(served, traffic, txns, due)
        tw0 = load.t0 + traffic["warmup_s"]
        load.pump(tw0)                            # load settles
        load.ramping = False
        compiles_setup = clk.n
        c0, st0 = counters(served.svc), stages(served.svc)
        span0 = len(load.spans)
        tw0 = time.perf_counter()
        setup_s = tw0 - t_start
        tw1 = tw0 + seconds
        load.pump(tw1)
        c1, st1 = counters(served.svc), stages(served.svc)
        span1 = len(load.spans)
        compiles_window = clk.n - compiles_setup
        unsent = held_back = 0
        if load.open:       # send all that fell due in the window
            n_due = load.loop.due_before(tw1 - load.t0)
            held_back = max(0, n_due - load.loop.next)
            load.pump(tw1 + FOLLOW_S, stop=lambda: load.loop.next >= n_due)
            n_before = load.loop.due_before(tw0 - load.t0)
            unsent = max(0, n_due - max(n_before, load.loop.next))
        due_arr = np.asarray(load.due)
        in_win = np.nonzero((due_arr >= tw0) & (due_arr < tw1))[0]
        win_reqs = [load.reqs[i] for i in in_win]
        tr = None
        if trace:           # a few seconds more, traced, after the window
            tr = tracemod.Tracer(os.path.join(root, ".chipbench_trace"))
            served.drv.flush()
            w_tr0 = served.svc.wave_idx
            load.annotate = True
            tr.start()
            load.pump(time.perf_counter() + tracemod.TRACE_S)
            served.drv.flush()
            tr.stop()
            load.annotate = False
            w_tr = served.svc.wave_idx - w_tr0
        # follow the window's requests to their end under continued load
        if load.open:
            load.pump(tw1 + FOLLOW_S, stop=Unanswered(win_reqs))
        served.drv.drain()      # the rest, with no more arrivals
        t_end = time.perf_counter()
        served.tick_end += [t_end] * (served.svc.tick + 1
                                      - len(served.tick_end))
        compiles_after = clk.n - compiles_setup - compiles_window

    svc = served.svc
    devices = jax.devices()[:cfg["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # ---- end-to-end and per-layer readings --------------------------------
    ticks = served.tick_end
    acked = np.array([ticks[r.commit_tick] for r in load.reqs
                      if r.status == "committed"])
    goodput = float(((acked >= tw0) & (acked < tw1)).sum()) / seconds
    lat = np.array([(ticks[r.commit_tick] - load.due[i])
                    if r.status == "committed" else np.inf
                    for i, r in zip(in_win, win_reqs)] + [np.inf] * unsent)
    lag = np.asarray(load.sent)[in_win] - due_arr[in_win]
    spans = load.spans[span0:span1]
    failed = int(sum(r.status != "committed" for r in win_reqs)) + unsent
    unanswered = int(sum(r.status not in DONE for r in win_reqs)) + unsent
    parts = latency_parts(win_reqs, due_arr[in_win],
                          np.asarray(load.sent)[in_win], ticks)
    ctx = Context(cell=cell, cfg=cfg, traffic=traffic, seconds=seconds,
                  window={k: c1[k] - c0[k] for k in c0},
                  stage_s={k: v - st0[0].get(k, 0.0)
                           for k, v in st1[0].items()},
                  stage_n={k: v - st0[1].get(k, 0)
                           for k, v in st1[1].items()},
                  latency_s=lat, gen_lag_s=lag, latency_parts=parts,
                  queue_wait_s=parts["queue_wait"],
                  block_turn_s=parts["block_turn"],
                  submit_s=sum(s[1] for s in spans),
                  submitted=sum(s[2] for s in spans),
                  goodput_txn_s=goodput, trace=None, scoped=None,
                  device_kind=devices[0].device_kind)
    trace_info = {}
    if tr is not None:
        ctx.trace = tr.reduce(n_devices=cfg["chips"], waves=w_tr)
        t_scoped = time.perf_counter()
        ctx.scoped = tracemod.reduce_scoped(tr.raw, w_tr)
        trace_info = {"trace_waves": w_tr, "trace_bytes": tr.file_bytes,
                      "trace_reduce_s": tr.reduce_s,
                      "scoped_reduce_s": time.perf_counter() - t_scoped}

    # ---- the check that decides ``correct`` --------------------------------
    t_ref = time.perf_counter()
    hist = history_of(svc)
    ans = answers_of(load.reqs)
    store, store_devs = store_of(svc.store, cfg["n_keys"])
    rows = np.asarray(load.row)
    sub = gen.Txns(*(a[rows] for a in txns))
    n_keys = cfg["n_keys"]
    del served, load, svc
    _gc.collect()
    checks = mix.check(sub, ans, hist, store,
                       **({} if table is None else {"initial": table}))
    checks["never_answered"] = unanswered
    if cfg["chips"] > 1:
        checks["shard_devices"] = abs(len(store_devs) - cfg["chips"])
    check_s = time.perf_counter() - t_ref
    correct = all(v == 0 for v in checks.values())

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(spec, cell["name"], kind):
        if kind == "end_to_end":
            v = {"setup_s": setup_s, "goodput_txn_s": goodput,
                 "commit_p50_ms": percentile(lat, 50) * 1e3,
                 "commit_p95_ms": percentile(lat, 95) * 1e3}[m["name"]]
            if not np.isfinite(v):
                v = FOLLOW_S * 1e3    # the percentile fell on a failure
        else:
            v = metric_reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": len(win_reqs) + unsent,
            "failed": failed, "metrics": metrics, "device": device}
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        b = ctx.scoped.breakdown()
        line["breakdown"] = {k: b[k] for k in ("device_ops", "idle_gaps")}
    line["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    info = {"compiles_setup": compiles_setup,
            "compiles_window": compiles_window,
            "compiles_after": compiles_after, "compile_s": clk.secs,
            "held_back": held_back,
            "check_s": check_s, "window": ctx.window,
            "requests": len(ans.committed), "history_rows": len(hist.tid),
            "n_keys": n_keys, **trace_info}
    return {"line": line, "info": info, "ctx": ctx}


class Unanswered:
    """True once every request of ``reqs`` has its answer; looks at most
    every 50 ms, so the follow-up's loop stays the serving loop."""

    def __init__(self, reqs):
        self.reqs, self.i, self.t = reqs, 0, 0.0

    def __call__(self) -> bool:
        now = time.perf_counter()
        if now - self.t < 0.05:
            return False
        self.t = now
        while self.i < len(self.reqs) and self.reqs[self.i].status in DONE:
            self.i += 1
        return self.i == len(self.reqs)


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
