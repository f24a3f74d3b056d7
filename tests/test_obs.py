"""Host stage timers, request stamps and the block program's named scopes
(``repro.service.obs``, ``TxnRequest.t_*``, ``engine.run_wave_on``)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, make_store
from repro.core.engine import Wave
from repro.kernels import KernelConfig
from repro.service import StreamingDriver, TxnService, obs, ycsb_txn_gen

T, N_NODES, KPN = 16, 4, 40
SCOPES = ("read_phase", "commit_loop", "message_stats")
INNER = ("newest", "validate", "install", "bump_sid", "push_bounds",
         "record")


def _served(n_ticks=12, rate=20, B=2, K=2, seed=5):
    """A tiny streaming session, driven tick by tick and flushed."""
    svc = TxnService(n_keys=N_NODES * KPN, T=T, n_nodes=N_NODES, seed=seed)
    drv = StreamingDriver(svc, B=B, K=K)
    gen = ycsb_txn_gen(np.random.RandomState(seed), N_NODES, KPN,
                       theta=0.5, read_frac=0.5, dist_frac=0.3)
    for _ in range(n_ticks):
        for _ in range(rate):
            svc.submit(*gen())
        drv.tick()
    drv.flush()
    return svc, drv


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts what is
    built and says whether a trace is being taken."""
    built = []
    tracing = False

    def __init__(self, name):
        self.built.append(name)

    @classmethod
    def is_enabled(cls):
        return cls.tracing

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def annotation(monkeypatch):
    cls = type("Annotation", (_CountingAnnotation,), {"built": []})
    monkeypatch.setattr(obs, "TraceAnnotation", cls)
    return cls


def test_stage_counts_follow_ticks_and_blocks(annotation):
    svc, drv = _served(n_ticks=12)
    n = svc.stage_n
    assert n["tick"] == 12 == svc.tick
    assert n["dispatch"] == n["retire_wait"] == svc.blocks > 0
    assert n["route"] == n["retire_wait"]
    assert n["flush"] == 1 and n["form"] == 12
    assert n["submit"] == len(svc.requests) == 12 * 20
    rep = svc.report()
    assert rep.wall_s == round(svc.stage_s["tick"] + svc.stage_s["flush"], 6)
    assert rep.stage_n == dict(n) and set(rep.stage_s) == set(n)
    assert rep.stage_s["form"] <= rep.stage_s["tick"]
    assert annotation.built == []          # no trace, no span built


def test_a_trace_gets_one_span_per_stage_but_submit(annotation):
    annotation.tracing = True
    svc, _ = _served(n_ticks=6)
    spans = sorted(set(annotation.built))
    assert spans == ["repro." + s for s in ("dispatch", "flush", "form",
                                            "retire_wait", "route", "tick")]
    assert len(annotation.built) == sum(
        v for k, v in svc.stage_n.items() if k not in ("submit", "commit_step"))


def test_stages_land_on_the_profilers_host_plane(tmp_path):
    import glob

    from jax.profiler import ProfileData
    _served(n_ticks=2)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _served(n_ticks=4)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"repro.tick", "repro.form", "repro.dispatch",
            "repro.retire_wait", "repro.route", "repro.flush"} <= names
    assert "repro.submit" not in names


@pytest.mark.parametrize("mode", ["step", "stream"])
def test_committed_requests_carry_ordered_stamps(mode):
    svc = TxnService(n_keys=N_NODES * KPN, T=T, n_nodes=N_NODES, seed=2)
    gen = ycsb_txn_gen(np.random.RandomState(2), N_NODES, KPN, theta=0.9,
                       read_frac=0.5, dist_frac=0.3)
    arrivals = [12] * 8
    rep = (svc.run_stream(arrivals, gen) if mode == "step"
           else svc.run_streaming(arrivals, gen, B=2, K=2))
    done = [r for r in svc.requests if r.status == "committed"]
    assert len(done) == rep.committed > 0
    for r in done:
        assert 0 < r.t_submit <= r.t_dispatch <= r.t_ack
    assert all(r.t_dispatch == r.t_ack == -1.0 for r in svc.requests
               if r.status != "committed")
    if mode == "step":
        assert rep.stage_n["step"] == svc.tick
        assert rep.wall_s == round(rep.stage_s["step"], 6)


def _block_program_text():
    B, O = 2, 4
    stacked = Wave(*(jnp.zeros((B, T, O), jnp.int32) for _ in range(3)),
                   jnp.zeros((B, T), jnp.int32),
                   jnp.arange(1, B * T + 1, dtype=jnp.int32).reshape(B, T))
    return engine._scan_block.lower(
        make_store(N_NODES * KPN, 4), stacked, jnp.int32(1), jnp.int32(1),
        jnp.int32(N_NODES), None, None, sched="postsi", gc_track=True,
        kernels=KernelConfig("jnp")).compile().as_text()


def test_every_op_of_the_wave_body_carries_a_named_scope():
    """Inside the block program's wave body (the ``closed_call`` the scan
    runs), every operation but a broadcast constant is under one of the
    named scopes; inside the commit loop's body, under one of its own."""
    body = "jit(_scan_block)/while/body/closed_call"
    loop = body + "/commit_loop/while/body/closed_call"
    seen = set()
    for line in _block_program_text().splitlines():
        m = re.search(r'%([\w.-]+) = .*op_name="([^"]*)"', line)
        if m is None or not m.group(2).startswith(body):
            continue
        name, stack = m.group(1), m.group(2).split("/")
        if re.match(r"(constant|broadcast|wrapped_broadcast)\b", name):
            continue
        assert set(stack) & set(SCOPES), (name, m.group(2))
        if m.group(2).startswith(loop):
            assert set(stack) & set(INNER), (name, m.group(2))
        seen.update(stack)
    assert set(SCOPES + INNER) <= seen
