"""``chip_smoke.py`` rehearsed on the CPU, and the compile-cache placement.

The smoke itself needs a TPU and refuses to report success anywhere else;
these tests pin that refusal, and run its phases at a tiny size with the
interpreted kernels, so a wrong path, argument or comparison shows up here
and not on the chip.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env_extra=None, cwd=ROOT, drop=()):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(env_extra or {})
    for k in drop:
        env.pop(k, None)
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("flag", [[], ["--four-chips"]])
def test_refuses_without_a_chip(flag):
    out = _run([SMOKE, *flag], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""                      # no result line at all
    assert "no TPU" in out.stderr


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], {"JAX_PLATFORMS": "cpu"},
               cwd=tmp_path, drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_one_chip_phases_tiny(capsys):
    """Default kernels (warm-up + warm run), the jnp reference and the
    fused route over one tiny stream: verified, and equal bit for bit."""
    cs = _load_smoke()
    tiny = cs.Size(n_keys=2 ** 10, n_nodes=8, T=16, O=4, B=2, K=2, ticks=4)
    cs.one_chip(tiny, default="pallas_interpret",
                fused="pallas_interpret+fused")
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    phases = [r["phase"] for r in rows]
    assert phases.count("compare default vs jnp") == 1
    assert "compare pallas_interpret+fused vs jnp" in phases
    served = [r for r in rows if "committed" in r and "kernels" in r]
    assert len(served) == 4 and all(r["verify_errors"] == 0 for r in served)
    assert len({(r["committed"], r["aborted"]) for r in served}) == 1


def test_mismatch_is_reported():
    """Two different streams must not compare equal."""
    cs = _load_smoke()
    tiny = cs.Size(n_keys=2 ** 10, n_nodes=8, T=16, O=4, B=2, K=2, ticks=3)
    a, _ = cs.serve(tiny, "jnp")
    b, _ = cs.serve(tiny._replace(ticks=4), "jnp")
    errs = cs.mismatches(a, b)
    assert any("commit sets" in e for e in errs)
    with pytest.raises(cs.SmokeFailure):
        cs.same(a, b, "different streams")


def test_four_chip_phases_tiny():
    """The sharded-store path on four virtual CPU devices against one."""
    code = (
        "import importlib.util as u, sys\n"
        f"spec = u.spec_from_file_location('chip_smoke', {SMOKE!r})\n"
        "cs = u.module_from_spec(spec); spec.loader.exec_module(cs)\n"
        "tiny = cs.Size(n_keys=2**10, n_nodes=4, T=16, O=4, B=2, K=2,"
        " ticks=4)\n"
        "cs.four_chips(tiny, kernels='pallas_interpret')\n")
    out = _run(["-c", code], {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    assert rows[-1] == {"phase": "compare mesh vs single device",
                        "equal": True, "committed": rows[-1]["committed"],
                        "waves": rows[-1]["waves"]}
    layout = next(r for r in rows if r["phase"] == "mesh layout")
    assert len({dev for dev, _ in layout["shards"]}) == 4
    assert all(n == 2 ** 10 // 4 for _, n in layout["shards"])


_CACHE_CHILD = """
import jax, jax.numpy as jnp, os, sys
from repro.jaxenv import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
"""


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_compile_cache_lands(tmp_path, where):
    """``JAX_COMPILATION_CACHE_DIR`` wins where set; else the cache lives
    at the fixed ``.jax_cache`` in the checkout."""
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    drop = ()
    if where == "env":
        expect = str(tmp_path / "cc")
        env["JAX_COMPILATION_CACHE_DIR"] = expect
    else:
        expect = os.path.join(ROOT, ".jax_cache")
        drop = ("JAX_COMPILATION_CACHE_DIR",)
    out = _run(["-c", _CACHE_CHILD], env, drop=drop)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == expect
    assert os.listdir(expect)
