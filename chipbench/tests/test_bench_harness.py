"""The harness: its files, its refusals, and runs at a tiny size on the
CPU, whole and with the timed path broken underneath."""
import contextlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import faults, gen, harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TINY = {"scheduler": "postsi", "n_keys": 3072, "n_nodes": 8,
        "n_versions": 8, "T": 16, "O": 4, "B": 4, "K": 2,
        "mix": {"kind": "ycsb", "theta": 0.99, "read_frac": 0.5,
                "dist_frac": 0.1, "n_ops": 4}}


def test_benchmark_json_names_files_that_exist():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
        cfg = harness.load_config(c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        harness.load_config(w["config"])
        harness.load_traffic(w["traffic"])
        reported = [m["name"] for m in harness.cell_metrics(
            spec, w["name"], "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(spec, w["name"], "per_layer")
    assert sum(w["chips"] == 4 for w in cells.values()) <= 1
    for m in spec["per_layer"]:
        assert NAME.match(m["name"])
        harness.metric_reader(m["name"])
        for w in m["workloads"]:
            assert m["moves"] in [x["name"] for x in harness.cell_metrics(
                spec, w, "end_to_end")]
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


# A mix of a test's own: SmallBank's draw and check, and one count more,
# ``audit_forced``, which reads what ``BANK_AUDIT_FORCED`` says (0 unset).
BANK_AUDIT = """
import os

from chipbench import gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
bank = gen.load_mix("smallbank", ROOT)
draw = bank.draw


def check(txns, answers, history, store):
    out = bank.check(txns, answers, history, store)
    out["audit_forced"] = int(os.environ.get("BANK_AUDIT_FORCED", "0"))
    return out
"""


# A mix of a test's own that brings its loaded table: SmallBank's draw, a
# table drawn from the seed, and a check that replays the committed
# requests from that table.  ``initial_lost`` counts untouched keys whose
# newest version does not hold their loaded value.
BANK_LOADED = """
import os

import numpy as np

from chipbench import gen, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
bank = gen.load_mix("smallbank", ROOT)
draw = bank.draw


def initial(seed, *, n_keys, n_nodes, keys_per_node, **mix):
    rng = np.random.default_rng([seed, 7])
    return rng.integers(1, 1000, n_nodes * keys_per_node).astype(np.int32)


def check(txns, answers, history, store, initial):
    newest = store.val[np.arange(len(initial)), store.head]
    untouched = store.cid[np.arange(len(initial)), store.head] == 0
    out = reference.check(txns, answers, history,
                          store._replace(val=store.val - initial[:, None]))
    out["initial_lost"] = int((newest != initial)[untouched].sum())
    return out
"""


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with tiny cells added as new files only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = harness.load_spec()
    for name, mix in (("tiny_ycsb", TINY["mix"]),
                      ("tiny_bank", {"kind": "smallbank",
                                     "weights": [0, 15, 15, 25, 15, 15],
                                     "n_ops": 4})):
        (root / "chipbench/configs" / f"{name}.json").write_text(
            json.dumps(dict(TINY, name=name, mix=mix)))
    (root / "chipbench/traffic/tiny_closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 128, "warmup_s": 0.3}))
    (root / "chipbench/traffic/tiny_open.json").write_text(json.dumps(
        {"loop": "open", "rate_txn_s": 400, "warmup_s": 0.3}))
    # a mix, a configuration and service settings that are all new files
    (root / "chipbench/mixes/bank_audit.py").write_text(BANK_AUDIT)
    (root / "chipbench/configs/tiny_audit.json").write_text(json.dumps(
        dict(TINY, name="tiny_audit", service={"max_queue": 96},
             mix={"kind": "bank_audit", "weights": [0, 15, 15, 25, 15, 15],
                  "n_ops": 4})))
    (root / "chipbench/mixes/bank_loaded.py").write_text(BANK_LOADED)
    (root / "chipbench/configs/tiny_loaded.json").write_text(json.dumps(
        dict(TINY, name="tiny_loaded",
             mix={"kind": "bank_loaded", "weights": [0, 15, 15, 25, 15, 15],
                  "n_ops": 4})))
    (root / "chipbench/configs/tiny_bad_service.json").write_text(json.dumps(
        dict(TINY, name="tiny_bad_service", service={"no_such_setting": 1})))
    (root / "chipbench/metrics/commits_per_wave.py").write_text(
        "def read(ctx):\n"
        "    w = ctx.window\n"
        "    return w['committed'] / w['waves'] if w['waves'] else None\n")
    spec["workloads"] += [
        {"name": "tiny.closed", "config": "tiny_ycsb",
         "traffic": "tiny_closed", "chips": 1, "why": "test"},
        {"name": "tiny_bank.closed", "config": "tiny_bank",
         "traffic": "tiny_closed", "chips": 1, "why": "test"},
        {"name": "tiny.open", "config": "tiny_ycsb",
         "traffic": "tiny_open", "chips": 1, "why": "test"},
        {"name": "tiny_audit.closed", "config": "tiny_audit",
         "traffic": "tiny_closed", "chips": 1, "why": "test"},
        {"name": "tiny_loaded.closed", "config": "tiny_loaded",
         "traffic": "tiny_closed", "chips": 1, "why": "test"}]
    spec["end_to_end"][0]["workloads"] = [w["name"]
                                          for w in spec["workloads"]]
    for m in spec["end_to_end"]:
        if m["name"].startswith("commit_p"):
            m["workloads"] = m["workloads"] + ["tiny.open"]
    spec["per_layer"].append(
        {"name": "commits_per_wave", "unit": "ratio", "better": "higher",
         "source": "program_counter", "layer": "block program",
         "moves": "goodput_txn_s",
         "workloads": ["tiny.closed", "tiny_bank.closed", "tiny.open",
                       "tiny_audit.closed", "tiny_loaded.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def run(root, cell, trace=False, seconds=0.6, seed=2 ** 31 + 5):
    spec = harness.load_spec(root)
    return harness.run_cell(harness.find_cell(spec, cell), seed, seconds,
                            trace, time.perf_counter(), spec,
                            kernels="jnp", root=root)["line"]


@pytest.mark.parametrize("cell", ["tiny.open", "tiny_bank.closed"])
def test_new_files_are_picked_up_by_name(tiny_root, cell):
    line = run(tiny_root, cell)
    assert line["correct"] is True
    assert line["metrics"]["goodput_txn_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert all(c["limit"] == 0 for c in line["checks"].values())
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("forced", [0, 1])
def test_a_new_mix_file_checks_its_own_count(tiny_root, monkeypatch,
                                             forced):
    """A cell whose mix, configuration and service settings are all new
    files runs through ``run_cell``; the mix's own count stands under
    ``checks`` with limit 0 and decides ``correct``."""
    monkeypatch.setenv("BANK_AUDIT_FORCED", str(forced))
    line = run(tiny_root, "tiny_audit.closed")
    checks = line["checks"]
    assert checks["audit_forced"] == {"value": forced, "limit": 0}
    assert {"exactly_once", "final_value", "ring_version",
            "never_answered"} <= set(checks)
    assert all(c["value"] == 0 for k, c in checks.items()
               if k != "audit_forced")
    assert line["correct"] is (forced == 0)


def test_service_settings_come_from_the_configuration(tiny_root):
    cfg = dict(harness.load_config("tiny_audit", tiny_root), chips=1)
    assert cfg["service"] == {"max_queue": 96}
    served = harness.Served(cfg, 3, kernels="jnp")
    assert served.svc.former.max_queue == 96       # 4 * T = 64 unset
    txns = gen.draw(dict(harness.mix_of(cfg), kind="smallbank"), 1, 8)
    load = harness.Load(served, {"loop": "closed", "clients": 8}, txns,
                        None)
    assert load.room() == 96
    bad = {"name": "bad", "config": "tiny_bad_service",
           "traffic": "tiny_closed", "chips": 1}
    with pytest.raises(TypeError, match="no_such_setting"):
        harness.run_cell(bad, 1, 0.5, False, time.perf_counter(),
                         harness.load_spec(tiny_root), kernels="jnp",
                         root=tiny_root)


def test_warm_up_blocks_take_the_submitted_rows_shape(tiny_root):
    cfg = dict(harness.load_config("tiny_ycsb", tiny_root), chips=1)
    served = harness.Served(cfg, 3, kernels="jnp")
    nop = served.nop_block(2, (4,))
    assert nop.op_val.shape == nop.op_kind.shape == (2, 16, 4)
    assert nop.host.shape == nop.tid.shape == (2, 16)
    assert not nop.op_kind.any() and (nop.tid > 0).all()
    assert served.nop_block(1, (4, 25)).op_val.shape == (1, 16, 4, 25)
    served.svc.nop_block = lambda B: ("the service's own", B)
    assert served.nop_block(4, (4,)) == ("the service's own", 4)


def test_warm_up_leaves_the_store_bit_for_bit(tiny_root):
    """Every NOP block of the warm-up, run on a store that has served
    transactions, leaves each store field as it was, and the wave index,
    block count and clock are put back."""
    import jax
    import numpy as np
    cfg = dict(harness.load_config("tiny_ycsb", tiny_root), chips=1)
    served = harness.Served(cfg, 3, kernels="jnp")
    svc = served.svc
    txns = gen.draw(harness.mix_of(cfg), 5, 200, tiny_root)
    for i in range(len(txns)):
        svc.submit(txns.kind[i], txns.key[i], txns.val[i],
                   int(txns.host[i]))
        if i % 50 == 49:      # within what admission holds (4 * T)
            served.drv.drain()
    assert svc.committed == 200
    host = lambda: {f: np.array(a) for f, a in svc.store._asdict().items()}
    before, marks = host(), (svc.wave_idx, svc.blocks)
    clock = np.array(svc.clock)
    served.warm_shapes(txns.val.shape[1:])
    after = host()
    assert before.keys() == after.keys()
    for f in before:
        assert before[f].dtype == after[f].dtype
        np.testing.assert_array_equal(before[f], after[f], err_msg=f)
    assert (svc.wave_idx, svc.blocks) == marks
    np.testing.assert_array_equal(np.array(svc.clock), clock)
    jax.block_until_ready(svc.store)


def _loading_service(loads):
    """A ``TxnService`` that takes the keyword ``initial`` and, if
    ``loads``, writes it into every key's version 0."""
    from repro.service import TxnService

    class Loading(TxnService):
        def __init__(self, *a, initial, **kw):
            super().__init__(*a, **kw)
            if loads:
                self.store = self.store._replace(
                    val=self.store.val.at[:, 0].set(initial))
    return Loading


@pytest.mark.parametrize("program", ["loads", "ignores", "lacks"])
def test_a_mix_brings_its_loaded_table(tiny_root, monkeypatch, program):
    """A mix that exports ``initial`` has its table reach the program as
    the keyword ``initial`` and its check as ``initial=``: a program that
    loads it serves correct, one that ignores it fails the mix's own
    count, and one without the keyword fails at set-up."""
    import repro.service
    if program != "lacks":
        monkeypatch.setattr(repro.service, "TxnService",
                            _loading_service(program == "loads"))
        line = run(tiny_root, "tiny_loaded.closed")
        checks = line["checks"]
        assert "initial_lost" in checks
        assert line["correct"] is (program == "loads")
        assert (checks["initial_lost"]["value"] > 0) is (program == "ignores")
        return
    with pytest.raises(TypeError, match="initial"):
        run(tiny_root, "tiny_loaded.closed")


def test_a_loaded_table_must_have_a_row_per_key(tiny_root):
    mix = {"kind": "bank_loaded", "n_nodes": 8, "keys_per_node": 384,
           "weights": [0, 1, 1, 1, 1, 1]}
    table = gen.initial(mix, 2 ** 31 + 9, 3072, tiny_root)
    assert table.shape == (3072,) and table.dtype == "int32"
    assert (table == gen.initial(mix, 2 ** 31 + 9, 3072, tiny_root)).all()
    with pytest.raises(harness.BenchError, match="initial"):
        gen.initial(mix, 1, 3000, tiny_root)
    assert gen.initial(dict(mix, kind="smallbank"), 1, 3072) is None


def test_answers_carry_each_requests_read_values():
    """Requests that carry ``read_val`` have it stacked in
    ``Answers.extra``, beside the mask of those that do; where none does,
    ``extra`` stays empty."""
    import numpy as np
    from types import SimpleNamespace
    req = lambda tids, **kw: SimpleNamespace(
        status="committed", tid=tids[-1], tids=tids, **kw)
    rec = lambda k: np.arange(8, dtype=np.int32).reshape(4, 2) + 100 * k
    reqs = [req([5], read_val=rec(1)), req([6, 9]),
            req([7], read_val=None), req([8], read_val=rec(3))]
    ans = harness.answers_of(reqs)
    assert ans.exec_req.tolist() == [0, 1, 1, 2, 3]
    assert ans.extra["has_read_val"].tolist() == [True, False, False, True]
    got = ans.extra["read_val"]
    assert got.shape == (4, 4, 2) and got.dtype == np.int32
    np.testing.assert_array_equal(got[0], rec(1))
    np.testing.assert_array_equal(got[3], rec(3))
    assert not got[1].any() and not got[2].any()
    assert harness.answers_of(reqs[1:3]).extra == {}


def test_every_row_output_and_store_field_reaches_the_check():
    """A program whose waves return a value per read op and whose store
    keeps a record per version: both reach the check in ``extra``."""
    import jax.numpy as jnp
    import numpy as np
    from typing import NamedTuple
    from types import SimpleNamespace

    class Out(NamedTuple):
        status: np.ndarray
        s: np.ndarray
        c: np.ndarray
        read_key: np.ndarray
        read_cid: np.ndarray
        read_rec: np.ndarray     # [T, O, W]: the record each read saw
        write_key: np.ndarray
        write_cid: np.ndarray
        msgs: np.ndarray         # scalar: not a row

    T, O, W, V, n_keys = 4, 2, 3, 2, 5
    waves = []
    for w in range(2):
        r = np.arange(T) + 10 * w
        waves.append((r + 100, Out(
            r, r + 1, r + 2, np.stack([r] * O, 1), np.stack([r + 3] * O, 1),
            np.broadcast_to(r[:, None, None], (T, O, W)) * 7,
            np.stack([r + 4] * O, 1), np.stack([r + 5] * O, 1),
            np.int32(w))))
    h = harness.history_of(SimpleNamespace(history=waves))
    assert h.tid.tolist() == [100, 101, 102, 103, 110, 111, 112, 113]
    assert h.write_cid[:, 0].tolist() == (h.tid - 95).tolist()
    assert list(h.extra) == ["read_rec"]
    assert h.extra["read_rec"].shape == (2 * T, O, W)
    assert h.extra["read_rec"][5, 1, 2] == 11 * 7

    class Rings(NamedTuple):
        val: jnp.ndarray
        tid: jnp.ndarray
        cid: jnp.ndarray
        rec: jnp.ndarray         # [n_keys, V, W]: a record per version
        head: jnp.ndarray
        clock: jnp.ndarray       # not a per-key field

    k = jnp.arange(n_keys, dtype=jnp.int32)
    rings = Rings(jnp.stack([k] * V, 1), jnp.stack([k + 1] * V, 1),
                  jnp.stack([k + 2] * V, 1),
                  jnp.broadcast_to(k[:, None, None], (n_keys, V, W)) * 3,
                  k % V, jnp.zeros(2, jnp.int32))
    store, devices = harness.store_of(rings, n_keys)
    assert store.cid[:, 0].tolist() == [2, 3, 4, 5, 6]
    assert list(store.extra) == ["rec"]
    assert store.extra["rec"].shape == (n_keys, V, W)
    assert int(store.extra["rec"][4, 1, 2]) == 12
    assert len(devices) == 1


def test_a_host_stall_delays_open_arrivals_and_sheds_none(tiny_root,
                                                          monkeypatch):
    """The host stands still for 0.5 s as the window opens: 200 arrivals
    fall due meanwhile, three times what admission holds.  None is shed;
    their wait shows in the tail."""
    pump, calls = harness.Load.pump, []

    def stalled(self, until, sending=True, stop=None):
        calls.append(until)
        if len(calls) == 2:
            time.sleep(0.5)
        return pump(self, until, sending, stop)

    monkeypatch.setattr(harness.Load, "pump", stalled)
    line = run(tiny_root, "tiny.open")
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 200
    assert line["metrics"]["commit_p95_ms"]["value"] > 250


def test_a_new_per_layer_reader_is_read(tiny_root, monkeypatch):
    spec = harness.load_spec(tiny_root)
    names = [m["name"] for m in harness.cell_metrics(
        spec, "tiny.closed", "per_layer")]
    assert names == ["commits_per_wave"]
    ctx = harness.Context(window={"committed": 30, "waves": 3})
    assert harness.metric_reader("commits_per_wave", tiny_root)(ctx) == 10


BROKEN = pytest.mark.parametrize("broken", [
    None, faults.no_validation, faults.frozen_state, faults.half_batch,
    faults.altered_value],
    ids=["sound", "control", "frozen_state", "half_batch",
         "altered_value"])


def sees_the_fault(root, cell, broken):
    with (broken() if broken else contextlib.nullcontext()):
        line = run(root, cell)
    assert line["correct"] is (broken is None)
    failing = [k for k, c in line["checks"].items() if c["value"] > 0]
    assert bool(failing) is (broken is not None)


@BROKEN
def test_correct_comes_out_false_on_a_broken_path(tiny_root, broken):
    sees_the_fault(tiny_root, "tiny.closed", broken)


@BROKEN
def test_smallbank_comes_out_false_on_a_broken_path(tiny_root, broken):
    sees_the_fault(tiny_root, "tiny_bank.closed", broken)


@pytest.fixture
def donating(monkeypatch):
    """The block program donates the store it is given, as a program whose
    store fills most of the chip must."""
    import jax
    from repro.core import engine
    monkeypatch.setattr(engine, "_scan_block", jax.jit(
        engine._scan_block.__wrapped__, donate_argnums=(0,),
        static_argnames=("sched", "skew", "gc_track", "gc_block",
                         "kernels")))


@pytest.mark.parametrize("cell,broken", [
    ("tiny.open", None), ("tiny.closed", None),
    ("tiny.closed", faults.no_validation),
    ("tiny.closed", faults.frozen_state),
    ("tiny.closed", faults.half_batch),
    ("tiny.closed", faults.altered_value)],
    ids=["open", "closed", "control", "frozen_state", "half_batch",
         "altered_value"])
def test_a_program_that_donates_its_store(tiny_root, donating, cell,
                                          broken):
    sees_the_fault(tiny_root, cell, broken)


MESH = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from chipbench import faults, harness
import contextlib
spec = harness.load_spec(sys.argv[2])
cell = dict(harness.find_cell(spec, "tiny.closed"), chips=4)
for name in ("sound", "no_exchange"):
    ctx = faults.no_exchange() if name != "sound" else contextlib.nullcontext()
    with ctx:
        line = harness.run_cell(cell, 11, 0.5, False, time.perf_counter(),
                                spec, kernels="jnp", root=sys.argv[2])["line"]
    print(json.dumps({name: [line["correct"], line["checks"]]}), flush=True)
"""


def test_mesh_exchange_left_out_is_caught(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", MESH, ROOT, tiny_root],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = {}
    for ln in out.stdout.splitlines():
        res.update(json.loads(ln))
    assert res["sound"][0] is True
    assert res["sound"][1]["shard_devices"]["value"] == 0
    assert res["no_exchange"][0] is False


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "smallbank.closed",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_py(ROOT, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_py(str(tmp_path), dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
