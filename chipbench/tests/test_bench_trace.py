"""The trace reduction, on synthetic events and on a small trace recorded
on one TPU v5e chip (``chipbench/tests/data/t32.xplane.pb.gz``)."""
import gzip
import os

import pytest

from chipbench import harness, trace, work

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "t32.xplane.pb.gz")


def ev(rows):
    names = [r[0] for r in rows]
    return trace.Events(names, [r[1] for r in rows],
                        [r[2] - r[1] for r in rows])


def test_union_merges_nested_and_overlapping_intervals():
    u = trace.union(ev([("a", 0, 10), ("b", 2, 4), ("c", 8, 15),
                        ("d", 20, 30), ("e", 30, 31)]))
    assert u.tolist() == [[0, 15], [20, 31]]
    assert trace.union(ev([])).shape == (0, 2)


def test_summary_busy_ops_modules_and_gaps():
    dev = {"/device:TPU:0": {
        trace.OPS_LINE: ev([("%while.1 = x", 0, 6e6), ("%fusion.2 = y",
                                                      1e6, 2e6),
                            ("%while.1 = x", 8e6, 9e6)]),
        trace.MODULES_LINE: ev([("jit__scan_block(1)", 0, 9e6)])},
        "/device:TPU:1": {trace.OPS_LINE: ev([("%while.1 = x", 0, 1e6)])}}
    spans = ev([("tick", 5e6, 7.5e6), ("submit", 7.5e6, 8e6)])
    s = trace.Summary(dev, spans, window_s=0.01, waves=3, n_devices=1)
    assert s.busy_s == pytest.approx(7e-3)
    assert s.ops["%while.1 = x"] == pytest.approx(7e-3)
    assert s.module_s(["jit__scan_block"]) == pytest.approx(9e-3)
    assert s.gaps == pytest.approx({"tick": 2e-3})
    two = trace.Summary(dev, spans, window_s=0.01, waves=3, n_devices=2)
    assert two.busy_s == pytest.approx(4e-3)      # mean over two chips
    b = s.breakdown()
    assert b["device_ops"][0][0] == "%while.1" and len(b) == 2


def test_read_phase_bytes_do_not_depend_on_the_kernels():
    # one count from the shapes alone: the fused kernel and the unfused
    # pair are judged against the same bytes
    assert work.read_phase_bytes(256, 4, 8) == (
        256 * 4 * 8 * 16 + 256 * 4 * 8 + 256 * 4 * 20 + 256 * 4 + 256 * 256)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(FIXTURE) as f:
        prof = ProfileData.from_serialized_xspace(f.read())
    return trace.read_profile(prof)


def test_recorded_trace_reduces(recorded):
    devices, spans = recorded
    s = trace.Summary(devices, spans, window_s=1.0, waves=8, n_devices=1)
    assert 0 < s.busy_s < 1.0
    assert s.module_s(["jit__scan_block"]) > 0
    assert set(s.gaps) <= {"submit", "tick", "harness", "wait",
                           "no harness span"}
    ctx = harness.Context(
        trace=s, cfg={"T": 32, "O": 4, "n_versions": 8},
        device_kind="TPU v5 lite")
    for name in ("block_ms_per_wave", "read_phase_roofline",
                 "device_idle_share"):
        v = harness.metric_reader(name)(ctx)
        assert v is not None and v > 0, name
    assert harness.metric_reader("read_phase_roofline")(ctx) < 100
    assert harness.metric_reader("collective_ms_per_wave")(ctx) is None
