"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracle, swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.models.ssm import ssd_chunked


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("S,D,dtype", [
    (128, 128, jnp.float32),
    (256, 128, jnp.float32),
    (512, 128, jnp.bfloat16),
    (256, 64, jnp.float32),      # D padded to 128 inside the wrapper
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(S, D, dtype, causal):
    rng = np.random.RandomState(0)
    B, H, KH = 2, 4, 2
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, dtype)
    k = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, dtype)
    v = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, dtype)
    out_p = ops.flash_attention(q, k, v, causal=causal, use_pallas=True,
                                interpret=True)
    out_r = ops.flash_attention(q, k, v, causal=causal, use_pallas=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32), atol=tol, rtol=tol)


def test_flash_attention_matches_model_layer():
    """The kernel, its oracle and the model's chunked-XLA path must agree."""
    from repro.models.layers import attention
    rng = np.random.RandomState(1)
    B, S, H, KH, D = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KH, D) * 0.3, jnp.float32)
    out_model = attention(q, k, v, causal=True, chunk=64)
    out_kernel = ops.flash_attention(q, k, v, causal=True, use_pallas=True,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_kernel),
                               atol=3e-3, rtol=3e-3)


# ---------------------------------------------------------------------- ssd
@pytest.mark.parametrize("S,P,N,chunk", [
    (256, 64, 128, 128),
    (256, 32, 64, 64),
    (512, 64, 128, 128),
])
def test_ssd_kernel_vs_ref(S, P, N, chunk):
    rng = np.random.RandomState(2)
    B, H = 2, 3
    BH = B * H
    x = jnp.asarray(rng.randn(BH, S, P) * 0.5, jnp.float32)
    dA = -jnp.asarray(np.abs(rng.rand(BH, S)) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
    y_p, h_p = ops.ssd(x, dA, Bm, Cm, n_heads_per_group=H, chunk=chunk,
                       use_pallas=True, interpret=True)
    y_r, h_r = ops.ssd(x, dA, Bm, Cm, n_heads_per_group=H)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r), atol=1e-3, rtol=1e-3)


def test_ssd_kernel_vs_model_chunked():
    """Kernel agrees with the model's ssd_chunked (different layouts)."""
    rng = np.random.RandomState(3)
    B, S, H, P, N = 2, 256, 4, 32, 64
    x = jnp.asarray(rng.randn(B, S, H, P) * 0.5, jnp.float32)
    dA = -jnp.asarray(np.abs(rng.rand(B, S, H)) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, 1, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, 1, N) * 0.3, jnp.float32)
    y_m, h_m = ssd_chunked(x, dA, Bm, Cm, chunk=64)
    xk = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dk = dA.transpose(0, 2, 1).reshape(B * H, S)
    y_k, h_k = ops.ssd(xk, dk, Bm[:, :, 0], Cm[:, :, 0], n_heads_per_group=H,
                       chunk=64, use_pallas=True, interpret=True)
    y_k = y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    h_k = h_k.reshape(B, H, N, P).transpose(0, 1, 3, 2)   # model: [B,H,P,N]
    np.testing.assert_allclose(np.asarray(y_m), np.asarray(y_k), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h_m), np.asarray(h_k), atol=2e-3, rtol=2e-3)


# -------------------------------------------------------------- version scan
@pytest.mark.parametrize("M,V", [(256, 4), (512, 8), (1000, 6)])
def test_version_scan_vs_ref(M, V):
    rng = np.random.RandomState(4)
    cids = jnp.asarray(np.sort(rng.randint(0, 1000, (M, V)), axis=1), jnp.int32)
    tids = jnp.asarray(rng.randint(-1, 50, (M, V)), jnp.int32)
    max_cid = jnp.asarray(rng.randint(0, 1200, (M,)), jnp.int32)
    s_p, c_p = ops.version_scan(cids, tids, max_cid, use_pallas=True,
                                interpret=True)
    s_r, c_r = ops.version_scan(cids, tids, max_cid, use_pallas=False)
    # selected cid must match exactly; slots may differ only on duplicate cids
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))
    dup = np.asarray(jnp.take_along_axis(cids, s_r[:, None], 1)[:, 0]) == np.asarray(c_r)
    np.testing.assert_array_equal(np.asarray(s_p)[dup], np.asarray(s_r)[dup])


def test_version_scan_matches_store():
    """Kernel equals the engine's read_visible on a live store."""
    from repro.core import make_store, read_visible
    import jax.numpy as jnp
    store = make_store(512, 4)
    store = store._replace(
        cid=store.cid.at[:, 1].set(5), tid=store.tid.at[:, 1].set(3))
    keys = jnp.arange(512, dtype=jnp.int32)
    max_cid = jnp.full((512,), 4, jnp.int32)
    _, _, cid_ref2, _, slot_ref = read_visible(store, keys, max_cid)
    s_p, c_p = ops.version_scan(store.cid[keys], store.tid[keys], max_cid,
                                use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(cid_ref2))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(slot_ref))


# --------------------------------------------------------- potential matrix
@pytest.mark.parametrize("T,O", [(64, 4), (128, 8), (200, 12)])
def test_potential_matrix_vs_ref(T, O):
    rng = np.random.RandomState(5)
    rk = jnp.asarray(rng.randint(-1, 40, (T, O)), jnp.int32)
    wk = jnp.asarray(rng.randint(-1, 40, (T, O)), jnp.int32)
    p_p = ops.potential_matrix(rk, wk, use_pallas=True, interpret=True,
                               block_t=64)
    p_r = ops.potential_matrix(rk, wk, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(p_p), np.asarray(p_r))


def test_potential_matrix_matches_engine():
    """The engine's build route (``commit_phase.build_potential`` on the jnp
    leg — which is just ``ref.potential_matrix_ref``, the only jnp copy) must
    equal the kernel."""
    from repro.core.commit_phase import build_potential
    rng = np.random.RandomState(6)
    T, O = 64, 4
    keys = jnp.asarray(rng.randint(0, 30, (T, O)), jnp.int32)
    is_r = jnp.asarray(rng.rand(T, O) < 0.5)
    is_w = jnp.asarray(rng.rand(T, O) < 0.5)
    eng = build_potential(keys, is_r, is_w, backend="jnp")
    rk = jnp.where(is_r, keys, -1)
    wk = jnp.where(is_w, keys, -1)
    krn = ops.potential_matrix(rk, wk, use_pallas=True, interpret=True,
                               block_t=64)
    np.testing.assert_array_equal(np.asarray(eng), np.asarray(krn).astype(bool))


# ---------------------------------------------------- fused wave-commit kernel
def _ring_inputs(seed, T, O, V, n_keys=64):
    """Random gathered-ring inputs with the store invariants the kernel
    relies on: per-ring CIDs unique and >= 0, empty slots tid = -1."""
    rng = np.random.RandomState(seed)
    # unique cids per (t, o) ring via a shuffled base sequence
    cids = np.argsort(rng.rand(T, O, V), axis=2) * 3 + \
        rng.randint(0, 3, (T, O, 1))
    tids = np.where(rng.rand(T, O, V) < 0.3, -1, rng.randint(1, 99, (T, O, V)))
    sids = rng.randint(0, 40, (T, O, V))
    vals = rng.randint(-100, 100, (T, O, V))
    mc = rng.randint(-1, 3 * V, (T, O))     # includes all-invisible ceilings
    keys = rng.randint(0, n_keys, (T, O))
    is_r = rng.rand(T, O) < 0.5
    is_w = rng.rand(T, O) < 0.4
    to = lambda a: jnp.asarray(a, jnp.int32)
    return (to(cids), to(tids), to(sids), to(vals), to(mc),
            jnp.where(jnp.asarray(is_r), to(keys), -1),
            jnp.where(jnp.asarray(is_w), to(keys), -1), jnp.asarray(is_r))


@pytest.mark.parametrize("T,O,V", [(16, 3, 4), (64, 8, 8), (130, 5, 6)])
def test_wave_commit_vs_ref(T, O, V):
    """Fused megakernel (interpret) == the jnp oracle composition, every
    output, including non-aligned T/O shapes the wrapper pads."""
    args = _ring_inputs(7, T, O, V)
    out_p = ops.wave_commit(*args, use_pallas=True, interpret=True)
    out_r = ops.wave_commit(*args, use_pallas=False)
    names = ("slot", "r_val", "r_tid", "r_cid", "r_sid", "s_lo0", "potential")
    for name, a, b in zip(names, out_p, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_wave_commit_vs_unfused_composition():
    """Fused == the exact three-op route it replaces (same backend): the
    version_scan slots, the slot gathers, the rule-3 seed reduction and the
    potential tile, dispatched separately."""
    T, O, V = 48, 4, 6
    cids, tids, sids, vals, mc, rk, wk, rvalid = _ring_inputs(11, T, O, V)
    (slot, r_val, r_tid, r_cid, r_sid, s_lo0, pot) = ops.wave_commit(
        cids, tids, sids, vals, mc, rk, wk, rvalid,
        use_pallas=True, interpret=True)
    slot_u, _ = ops.version_scan(cids.reshape(-1, V), tids.reshape(-1, V),
                                 mc.reshape(-1), use_pallas=True,
                                 interpret=True)
    slot_u = slot_u.reshape(T, O)
    take = lambda a: jnp.take_along_axis(a, slot_u[..., None], -1)[..., 0]
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(slot_u))
    np.testing.assert_array_equal(np.asarray(r_cid), np.asarray(take(cids)))
    np.testing.assert_array_equal(np.asarray(r_val), np.asarray(take(vals)))
    np.testing.assert_array_equal(np.asarray(r_tid), np.asarray(take(tids)))
    np.testing.assert_array_equal(np.asarray(r_sid), np.asarray(take(sids)))
    s_lo0_u = jnp.where(rvalid, take(cids), 0).max(axis=1)
    np.testing.assert_array_equal(np.asarray(s_lo0), np.asarray(s_lo0_u))
    pot_u = ops.potential_matrix(rk, wk, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(pot), np.asarray(pot_u))


def test_wave_commit_hypothesis_random_waves():
    """Property sweep: for random live waves on a live store, the fused and
    unfused read phases agree on every substrate output (the satellite-4
    random-wave differential at the kernel seam)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core import make_store
    from repro.core.engine import run_wave
    from repro.core.substrate import LocalSubstrate
    from repro.core.workloads import micro_waves
    from repro.kernels import KernelConfig

    n_nodes, kpn, T = 4, 16, 12

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 2 ** 16),
           read_ratio=st.sampled_from([0.2, 0.7]),
           ceiling=st.sampled_from([0, 2, 1 << 30]))
    def check(seed, read_ratio, ceiling):
        waves = micro_waves(np.random.RandomState(seed), 2, T, n_nodes, kpn,
                            n_ops=3, read_ratio=read_ratio, dist_frac=0.5,
                            hot_frac=0.6, hot_per_node=2)
        # a populated store: run the first wave through the engine
        store = make_store(n_nodes * kpn, 4)
        store, _, _ = run_wave(store, waves[0], jnp.int32(1), jnp.int32(1),
                               jnp.int32(n_nodes), kernels="jnp")
        wave = waves[1]
        is_r = (wave.op_kind == 1) | (wave.op_kind == 3)
        is_w = (wave.op_kind == 2) | (wave.op_kind == 3)
        mc = jnp.broadcast_to(jnp.int32(ceiling), wave.op_key.shape)
        outs = [LocalSubstrate(cfg).read_phase(store, wave.op_key, mc,
                                               is_r, is_w)
                for cfg in (KernelConfig("pallas_interpret"),
                            KernelConfig("pallas_interpret", fused=True),
                            KernelConfig("jnp", fused=True))]
        names = ("r_val", "r_tid", "r_cid", "r_sid", "r_slot", "s_lo0",
                 "potential")
        for got in outs[1:]:
            for name, a, b in zip(names, outs[0], got):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f"{seed}.{name}")

    check()


@pytest.mark.parametrize("pad_key", [0, -1, 5])
def test_wave_commit_nop_padding_no_false_edges(pad_key):
    """Satellite audit: two NOP-padded txns sharing the clamp sentinel key
    (0, -1, or a HOT real key) must not grow a false anti-dependency edge in
    any of the three fused bodies — adversarial placement interleaves the
    NOP rows with real txns instead of suffix-padding them."""
    T, O, V = 16, 3, 4
    cids, tids, sids, vals, mc, rk, wk, rvalid = _ring_inputs(13, T, O, V,
                                                              n_keys=8)
    # interleaved NOP rows: every third txn is padding, all ops masked off
    # but the raw key column set to the adversarial pad_key
    nop_rows = np.arange(0, T, 3)
    rk = rk.at[nop_rows].set(-1)          # NOP => not a read
    wk = wk.at[nop_rows].set(-1)          # NOP => not a write
    rvalid = rvalid.at[nop_rows].set(False)
    # real txn 1 reads AND writes pad_key's clamped target to maximize the
    # chance a sentinel mixup would connect it to the padding rows
    hot = max(pad_key, 0)
    rk = rk.at[1, 0].set(hot)
    wk = wk.at[1, 1].set(hot)
    rvalid = rvalid.at[1, 0].set(True)
    for use_pallas in (False, True):
        _, _, _, _, _, s_lo0, pot = ops.wave_commit(
            cids, tids, sids, vals, mc, rk, wk, rvalid,
            use_pallas=use_pallas, interpret=use_pallas)
        pot = np.asarray(pot).astype(bool)
        assert not pot[nop_rows].any(), "NOP row grew outgoing rw edges"
        assert not pot[:, nop_rows].any(), "NOP row grew incoming rw edges"
        # the three bodies separately: version scan and potential directly,
        # the seed via the rvalid mask — NOP rows contribute exactly 0
        assert (np.asarray(s_lo0)[nop_rows] == 0).all()
        pot_u = np.asarray(ops.potential_matrix(
            rk, wk, use_pallas=use_pallas,
            interpret=use_pallas)).astype(bool)
        np.testing.assert_array_equal(pot, pot_u)


# ------------------------------------------------------------ masked install
def _scatter_install(val, tid, cid, sid, head, wave, *, mask, keys, values,
                     new_tid, new_cid, wave_idx):
    """The install as six scatters, ``head`` and ``wave`` included: the
    reference the in-place ``ops.masked_install`` must match bit for bit."""
    n_keys, n_versions = val.shape
    k_install = jnp.where(mask, keys, n_keys)
    h_new = (head[jnp.clip(keys, 0, n_keys - 1)] + 1) % n_versions
    return (val.at[k_install, h_new].set(values, mode="drop"),
            tid.at[k_install, h_new].set(new_tid, mode="drop"),
            cid.at[k_install, h_new].set(new_cid, mode="drop"),
            sid.at[k_install, h_new].set(0, mode="drop"),
            head.at[k_install].set(h_new, mode="drop"),
            wave.at[k_install].set(wave_idx, mode="drop"))


def _install_batch(rng, O, n_keys, V):
    """One random transaction's install inputs over a small store: keys
    drawn so that the ends 0 and n_keys-1, duplicates and negative NOP
    padding (always masked off, as the engine's NOP ops are) are common."""
    store = [rng.integers(-5, 50, (n_keys, V)) for _ in range(4)] + [
        rng.integers(0, V, n_keys), rng.integers(-1, 9, n_keys)]
    pool = np.array([0, n_keys - 1, 1, 2, -1, -3])
    keys = np.where(rng.random(O) < 0.6, rng.choice(pool, O),
                    rng.integers(0, n_keys, O))
    mask = (rng.random(O) < 0.6) & (keys >= 0)
    args = dict(mask=mask, keys=keys, values=rng.integers(-9, 99, O),
                new_tid=rng.integers(0, 999), new_cid=rng.integers(0, 999),
                wave_idx=rng.integers(0, 99))
    return ([jnp.asarray(a, jnp.int32) for a in store],
            {n: jnp.asarray(a, jnp.bool_ if n == "mask" else jnp.int32)
             for n, a in args.items()})


_INSTALL_NAMES = ("val", "tid", "cid", "sid", "head", "wave")


def _assert_installs_equal(store, args, tag):
    got = jax.jit(ops.masked_install)(*store, **args)
    want = jax.jit(_scatter_install)(*store, **args)
    for name, a, b in zip(_INSTALL_NAMES, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{tag}.{name}")


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("O", [1, 4, 8])
def test_masked_install_matches_scatter(O, seed):
    """``head``/``wave`` written in place, op by op, end as the 1-D scatters
    left them: random batches with masked-off ops, NOP keys, the key range's
    ends and duplicate keys."""
    rng = np.random.default_rng(1000 * O + seed)
    for b in range(25):
        _assert_installs_equal(*_install_batch(rng, O, n_keys=6, V=4),
                               f"O={O}.batch{b}")


@pytest.mark.parametrize("masks", [(True, True), (True, False),
                                   (False, True), (False, False)])
@pytest.mark.parametrize("key", [0, 3, 5])
def test_masked_install_duplicate_keys(key, masks):
    """Two ops of one transaction on one key, under every pair of masks,
    beside a masked-off NOP key: the key ends as the scatter left it."""
    n_keys, V = 6, 4
    rng = np.random.default_rng(key)
    store, _ = _install_batch(rng, 4, n_keys, V)
    args = dict(mask=jnp.array(masks + (False, True)),
                keys=jnp.array([key, key, -1, (key + 1) % n_keys], jnp.int32),
                values=jnp.array([7, 8, 9, 10], jnp.int32),
                new_tid=jnp.int32(41), new_cid=jnp.int32(42),
                wave_idx=jnp.int32(43))
    _assert_installs_equal(store, args, f"key={key}.masks={masks}")
