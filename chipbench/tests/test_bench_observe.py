"""The wire reader of ``tf_op`` stats, the reduction by named scope and
innermost span, the five readers of what the program records, and a tiny
open cell observed end to end on the CPU."""
import gzip
import os

import numpy as np
import pytest

from chipbench import harness, observe, trace, xspace
from chipbench.tests.test_bench_harness import tiny_root  # noqa: F401

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "t32.xplane.pb.gz")


@pytest.fixture(scope="module")
def raw():
    with gzip.open(FIXTURE) as f:
        return f.read()


def ev(rows):
    return trace.Events([r[0] for r in rows], [r[1] for r in rows],
                        [r[2] - r[1] for r in rows])


def test_wire_reader_reads_each_ops_name_stack(raw):
    stacks = xspace.tf_ops(raw)
    op, = [n for n in stacks if n.startswith("%compare_select_fusion.13 ")]
    assert stacks[op] == "jit(_scan_block)/while/body/closed_call/select_n"
    assert all(s.startswith("jit(_scan_block)/") for s in stacks.values())
    carry = [n for n in stacks if n.split(" = ")[0] in
             ("%copy.263", "%copy.264", "%copy.268")]
    assert len(carry) == 3 and {stacks[n] for n in carry} == {
        "jit(_scan_block)/while/body/closed_call/while"}


def test_existing_readers_read_what_they_read_before(raw):
    """``trace.py`` and the accepted readers are untouched: pinned values
    on the recorded trace, next to the scoped reduction of the same file."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(raw)
    devices, spans = trace.read_profile(prof)
    s = trace.Summary(devices, spans, window_s=1.0, waves=8, n_devices=1)
    ctx = harness.Context(trace=s, cfg={"T": 32, "O": 4, "n_versions": 8},
                          device_kind="TPU v5 lite")
    read = lambda name: harness.metric_reader(name)(ctx)
    assert read("block_ms_per_wave") == pytest.approx(3.256451875, rel=1e-12)
    assert read("read_phase_roofline") == pytest.approx(3.254975801520452,
                                                        rel=1e-12)
    assert read("device_idle_share") == pytest.approx(97.3953018, rel=1e-12)
    scoped = trace.reduce_scoped(raw, waves=8)
    assert scoped.busy_s == pytest.approx(s.busy_s, rel=1e-12)
    assert scoped.block_s == pytest.approx(s.module_s(["jit__scan_block"]))
    assert 0 < scoped.leaf_s <= scoped.busy_s
    assert scoped.harness_gaps == s.gaps
    assert scoped.scoped_s() == 0        # recorded before the scopes


@pytest.mark.parametrize("stack, scope", [
    ("jit(_scan_block)/while/body/closed_call/commit_loop/while/body/"
     "closed_call/install/scatter", "commit_loop/install"),
    ("jit(_scan_block)/while/body/closed_call/commit_loop/while",
     "commit_loop"),
    ("jit(_scan_block)/while/body/closed_call/read_phase/jit(version_scan)/"
     "pallas_call", "read_phase"),
    ("jit(_scan_block)/while/body/dynamic_update_slice", ""),
])
def test_scope_of_keeps_the_named_scopes(stack, scope):
    assert trace.scope_of(stack) == scope


def test_leaves_leave_out_loops_that_hold_operations():
    ops = ev([("%while.1", 0, 10), ("%while.2", 1, 9), ("%fusion.3", 2, 5),
              ("%copy.4", 5, 8), ("%fusion.5", 12, 14)])
    assert trace.leaves(ops).tolist() == [False, False, True, True, True]


def test_scopes_claim_leaf_time_inside_the_block_program():
    devices = {"/device:TPU:0": {
        trace.OPS_LINE: ev([("%while.1 = w", 0, 10), ("%fusion.2 = f", 1, 4),
                            ("%copy.3 = c", 4, 6), ("%slice.4 = s", 6, 7)]),
        trace.MODULES_LINE: ev([("jit__scan_block(1)", 0, 10)])}}
    stacks = {"%while.1 = w": "jit(_scan_block)/while",
              "%fusion.2 = f": "jit(_scan_block)/while/body/closed_call/"
                               "commit_loop/while/body/closed_call/install/x",
              "%copy.3 = c": "jit(_scan_block)/while/body/closed_call/"
                             "commit_loop/while"}
    s = trace.Scoped(devices, ev([]), stacks, waves=2,
                       summary=trace.Summary(devices, ev([]), 1.0, 2, 1))
    assert s.scope_s == pytest.approx({"commit_loop/install": 3e-9,
                                       "commit_loop": 2e-9, "": 1e-9})
    assert s.summary()["claimed_share"] == pytest.approx(0.5)
    assert s.breakdown()["device_ops"][1][0] == \
        "%fusion.2 [commit_loop/install]"
    assert s.breakdown()["unscoped_ops"] == [["%slice.4", pytest.approx(1e-9)]]
    ctx = harness.Context(scoped=s)
    assert harness.metric_reader("commit_loop_ms_per_wave")(ctx) == \
        pytest.approx(5e-9 / 2 * 1e3)


def test_an_idle_gap_is_named_by_the_innermost_span():
    busy = np.array([[0.0, 10.0], [30.0, 40.0], [50.0, 60.0]])
    spans = ev([("chipbench.tick", 5, 45), ("repro.tick", 6, 44),
                ("repro.retire_wait", 8, 20), ("repro.route", 20, 28)])
    gaps = trace.innermost_gaps(busy, spans)
    assert gaps == pytest.approx({"repro.retire_wait": 10e-9,
                                  "repro.route": 8e-9, "repro.tick": 6e-9,
                                  "chipbench.tick": 1e-9, "no span": 5e-9})
    # the harness's own naming puts both gaps whole under its tick span
    assert trace.Summary._gaps(busy, ev([("tick", 5, 45)])) == \
        pytest.approx({"tick": 30e-9})


def test_the_new_readers_read_nothing_from_the_harness_context():
    ctx = harness.Context(window={"executions": 10, "committed": 10},
                          trace=None)
    for name in observe.READERS:
        assert harness.metric_reader(name)(ctx) is None, name


def test_stage_cost_is_measured_with_and_without_a_trace(tmp_path):
    cost = observe.stage_cost(n=200, trace_dir=str(tmp_path / "t"))
    assert set(cost) == {"off", "on"}
    assert all(0 < v < 1e-3 for c in cost.values() for v in c.values())
    assert set(cost["on"]) == {"stage_s", "submit_s"}
    assert not (tmp_path / "t").exists()


def test_a_tiny_open_cell_splits_its_latency_on_one_clock(tiny_root,
                                                         monkeypatch):
    """Every committed request of the window was submitted, dispatched and
    acknowledged in that order, before the end of the tick that routed
    it, and the five parts add up to the latency the harness reports."""
    seen = {}
    load_init = harness.Load.__init__

    def keep(self, *a, **kw):
        load_init(self, *a, **kw)
        seen["load"] = self

    monkeypatch.setattr(harness.Load, "__init__", keep)
    out = observe.observe("tiny.open", 2 ** 31 + 11, 0.6, False,
                          root=tiny_root, kernels="jnp")
    assert out["line"]["correct"] is True
    load = seen["load"]
    ends = load.s.tick_end
    done = [(i, r) for i, r in enumerate(load.reqs)
            if r.status == "committed"]
    assert done
    for i, r in done:
        assert load.sent[i] <= r.t_submit <= r.t_dispatch <= r.t_ack \
            <= ends[r.commit_tick]
    parts = out["latency_parts"]
    assert parts["n"] > 0
    mean = parts["mean_ms"]
    assert sum(mean[p] for p in harness.PARTS) == pytest.approx(
        mean["latency"], rel=1e-9)
    at95 = parts["at_p95_request_ms"]
    assert sum(at95[p] for p in harness.PARTS) == pytest.approx(
        at95["latency"], rel=1e-9)
    assert parts["largest_at_p95"] in harness.PARTS
    assert out["stage_n"]["tick"] == out["info"]["window"]["tick"]
    assert out["stage_n"]["dispatch"] == out["info"]["window"]["blocks"]
    r = out["readers"]
    for name in ("queue_wait_p95_ms", "block_turn_p95_ms",
                 "route_us_per_txn", "admit_us_per_txn"):
        assert r[name] is not None and r[name] > 0, name
    assert r["commit_loop_ms_per_wave"] is None     # no trace on the CPU
    assert r["queue_wait_p95_ms"] == pytest.approx(parts["p95_ms"][
        "queue_wait"])


SCOPED = os.path.join(os.path.dirname(__file__), "data",
                      "scoped_t32.xplane.pb.gz")


def test_a_traced_run_hands_the_readers_what_the_program_records(
        tiny_root, monkeypatch):
    """A traced run of a tiny open cell through ``run_cell``.  The CPU's
    trace holds no device plane, so a trace recorded on one v5e chip, with
    the program's named scopes, takes its place as the profiler stops; the
    rest is the run's own.  Its context carries the window's stage timers,
    each committed request's queue wait and block turn, and device time by
    named scope, and the five readers read them."""
    import glob

    with gzip.open(SCOPED) as f:
        recorded = f.read()
    stop = trace.Tracer.stop

    def stop_and_swap(self):
        stop(self)
        path, = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        with open(path, "wb") as f:
            f.write(recorded)

    monkeypatch.setattr(trace.Tracer, "stop", stop_and_swap)
    spec = harness.load_spec(tiny_root)
    out = harness.run_cell(harness.find_cell(spec, "tiny.open"),
                           2 ** 31 + 13, 0.6, True, 0.0, spec,
                           kernels="jnp", root=tiny_root)
    line, ctx = out["line"], out["ctx"]
    assert line["correct"] is True
    assert ctx.stage_n["tick"] == ctx.window["tick"]
    assert ctx.stage_n["submit"] == ctx.submitted > 0
    assert ctx.stage_s["route"] > 0
    committed = line["attempted"] - line["failed"]
    assert len(ctx.queue_wait_s) == len(ctx.block_turn_s) == committed
    assert (ctx.queue_wait_s >= 0).all() and (ctx.block_turn_s > 0).all()
    assert isinstance(ctx.scoped, trace.Scoped)
    assert ctx.scoped.waves == out["info"]["trace_waves"] > 0
    assert ctx.scoped.scoped_s("commit_loop") > 0
    for name in observe.READERS:
        v = harness.metric_reader(name)(ctx)
        assert v is not None and v > 0, name
    assert any("[commit_loop" in op for op, _ in
               line["breakdown"]["device_ops"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
