"""The mixes, the arrival processes, the load loops and the peak table."""
import numpy as np
import pytest

from chipbench import gen, load, peaks
from chipbench.files import BenchError

ycsb, smallbank = gen.load_mix("ycsb"), gen.load_mix("smallbank")

YCSB = dict(n_nodes=8, keys_per_node=1 << 20, theta=0.99, read_frac=0.5,
            dist_frac=0.1, n_ops=4)
SB = dict(n_nodes=8, keys_per_node=375_000,
          weights=[0, 15, 15, 25, 15, 15])
ACCOUNTS = 1_000_000


@pytest.mark.parametrize("draw", [
    lambda s: ycsb.draw(s, 5000, **YCSB),
    lambda s: smallbank.draw(s, 5000, **SB),
    lambda s: (gen.poisson_times(s, 3000.0, 2.0),),
], ids=["ycsb", "smallbank", "poisson"])
def test_seed_reproduces_its_stream(draw):
    a, b, c = draw(2 ** 31 + 12345), draw(2 ** 31 + 12345), draw(7)
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x, y)
    assert any(not np.array_equal(x, z) for x, z in zip(a, c))


def active_nodes(x, n_nodes):
    act = x.kind != gen.NOP
    return [set((x.key[i][act[i]] % n_nodes).tolist())
            for i in range(len(x))]


def test_ycsb_read_rmw_split_and_distributed_share():
    x = ycsb.draw(3, 40000, **YCSB)
    act = x.kind != gen.NOP
    reads = (x.kind == gen.READ).sum() / act.sum()
    assert abs(reads - 0.5) < 0.01
    assert set(np.unique(x.kind[act])) == {gen.READ, gen.RMW}
    assert (x.val[x.kind == gen.RMW] >= 1).all()
    assert (x.val[x.kind == gen.RMW] < 100).all()
    assert (x.val[x.kind == gen.READ] == 0).all()
    nodes = active_nodes(x, 8)
    multi = np.mean([len(s) > 1 for s in nodes])
    # a distributed txn puts its 4 ops on 2-3 nodes; all of them land on
    # the host with probability (1/2)^4 or (1/3)^4
    assert 0.085 < multi < 0.105
    on_host = np.mean([s == {h} for s, h in zip(nodes, x.host)])
    assert 0.895 < on_host < 0.915


def test_ycsb_rank0_zipf_mass():
    n = 1 << 20
    x = ycsb.draw(5, 200000, **dict(YCSB, n_ops=1))
    share = np.mean(x.key[:, 0] // 8 == 0)
    want = ycsb.zipf_cdf(n, 0.99)[0]
    sigma = np.sqrt(want * (1 - want) / len(x))
    assert abs(share - want) < 4 * sigma
    assert ycsb.zipf_cdf(1000, 0.0)[0] == pytest.approx(1e-3)


@pytest.mark.parametrize("draw", [
    lambda: ycsb.draw(9, 20000, **YCSB),
    lambda: smallbank.draw(9, 20000, **dict(SB, keys_per_node=3)),
], ids=["ycsb", "smallbank"])
def test_nop_dedup(draw):
    x = draw()
    act = x.kind != gen.NOP
    for i in range(len(x)):
        ks = x.key[i][act[i]]
        assert len(ks) == len(set(ks.tolist()))
    assert (x.key[~act] == 0).all() and (x.val[~act] == 0).all()
    assert (~act).any()


def test_smallbank_procedures():
    x = smallbank.draw(11, 85000, **SB)
    table, cust = x.key // ACCOUNTS, x.key % ACCOUNTS
    act = x.kind != gen.NOP
    reads = (x.kind == gen.READ).sum(axis=1)
    rmws = (x.kind == gen.RMW).sum(axis=1)
    # every procedure looks its customer up in the account table first
    assert (x.kind[:, 0] == gen.READ).all()
    assert (table[:, 0] == smallbank.ACCOUNT).all()
    balance = (reads == 3) & (rmws == 0)
    deposit = (reads == 1) & (rmws == 1) & (table[:, 1] == smallbank.CHECKING)
    savings = (reads == 1) & (rmws == 1) & (table[:, 1] == smallbank.SAVINGS)
    payment = (reads == 2) & (rmws == 2)
    check = (reads == 2) & (rmws == 1)
    # amalgamate is left out; the other five keep their weights, over 85
    for mask, w in ((balance, 15), (deposit, 15), (payment, 25),
                    (savings, 15), (check, 15)):
        assert abs(mask.mean() - w / 85) < 0.01
    assert (balance | deposit | savings | payment | check).all()
    assert (x.val[payment].sum(axis=1) == 0).all()
    assert (x.val[check][:, 2] < 0).all() and (x.val[deposit][:, 1] > 0).all()
    assert (x.val[~act] == 0).all() and (x.val[x.kind == gen.READ] == 0).all()
    # one customer per procedure but send_payment, whose two differ; a
    # customer's rows share its node, the host
    one = ~payment[:, None] & act
    assert (cust[one] == np.broadcast_to(cust[:, :1], cust.shape)[one]).all()
    assert (cust[payment, 0] != cust[payment, 1]).all()
    assert ((cust[:, 0] % 8) == x.host).all()
    assert ((x.key % 8)[one] == np.broadcast_to(x.host[:, None],
                                                x.key.shape)[one]).all()
    with pytest.raises(ValueError):
        smallbank.draw(1, 10, **dict(SB, weights=[1, 2]))
    with pytest.raises(ValueError, match="amalgamate"):
        smallbank.draw(1, 10, **dict(SB, weights=[15, 15, 15, 25, 15, 15]))
    with pytest.raises(ValueError):
        smallbank.draw(1, 10, **dict(SB, keys_per_node=1000))


def test_every_seed_draws_its_own_stream():
    mix = dict(SB, kind="smallbank")
    a = gen.draw(mix, 2 ** 32 + 3, 1000)
    b = gen.draw(mix, 5, 1000)
    assert len(a) == 1000 and not np.array_equal(a.key, b.key)
    ta = gen.arrivals(2 ** 32 + 3, 1000.0, (1.0, 2.0))
    tb = gen.arrivals(5, 1000.0, (1.0, 2.0))
    # each segment offers the same count, at times of the seed's own
    assert len(ta) == len(tb) == 3000
    assert (np.diff(ta) >= 0).all() and ta[999] < 1.0 <= ta[1000] < 3.0
    assert tb[999] < 1.0 <= tb[1000] and not np.allclose(ta, tb)
    assert np.array_equal(ta, gen.arrivals(2 ** 32 + 3, 1000.0, (1.0, 2.0)))
    with pytest.raises(BenchError, match="mixes/tpcc.py"):
        gen.draw(dict(mix, kind="tpcc"), 1, 5)


def test_poisson_times_offer_a_fixed_count():
    t = gen.poisson_times(2 ** 33 + 1, 2500.0, 4.0)
    assert len(t) == 10000
    assert (np.diff(t) >= 0).all() and t[0] >= 0 and t[-1] < 4.0
    gaps = np.diff(t)
    assert abs(gaps.mean() - 1 / 2500.0) < 0.05 / 2500.0


def test_open_loop_takes_what_is_due():
    loop = load.OpenLoop(np.array([0.1, 0.2, 0.2, 0.5]))
    assert list(loop.take(0.05)) == []
    assert list(loop.take(0.2)) == [0, 1, 2]
    assert loop.until_next(0.3) == pytest.approx(0.2)
    assert list(loop.take(1.0)) == [3]
    assert loop.until_next(1.0) == float("inf")


def test_open_loop_holds_back_what_it_may_not_send_yet():
    loop = load.OpenLoop(np.array([0.1, 0.2, 0.2, 0.5]))
    assert loop.due_before(0.2) == 1 and loop.due_before(0.6) == 4
    assert list(loop.take(0.3, 2)) == [0, 1]
    assert list(loop.take(0.3, 0)) == []
    assert loop.until_next(0.3) == 0.0
    assert list(loop.take(0.3, 5)) == [2]
    assert list(loop.take(1.0, 5)) == [3]


def test_client_pool_keeps_one_request_per_client():
    pool = load.ClientPool(3, stream_len=5)
    first = pool.take(2)
    assert first == [(0, 0), (1, 1)]
    for c, i in first:
        pool.sent(c, {"done": False, "i": i})
    assert pool.take(10) == [(2, 2)]
    pool.sent(2, {"done": False, "i": 2})
    assert pool.take(10) == []
    pool.outstanding[1][1]["done"] = True
    assert pool.reap(lambda h: h["done"]) == 1
    assert pool.take(10) == [(1, 3)]
    assert sorted(c for c, _ in pool.outstanding) == [0, 2]
    pool.sent(1, {"done": True})
    for _, h in pool.outstanding:
        h["done"] = True
    assert pool.reap(lambda h: h["done"]) == 3
    assert [i for _, i in pool.take(10)] == [4, 0, 1]    # the stream wraps
    with pytest.raises(ValueError):
        load.ClientPool(0, 5)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


# sha256 over ``kind``, ``key``, ``val`` and ``host`` of 4,096 transactions,
# as ``gen.draw`` drew them while both mixes still lived in ``gen.py``
DRAWN = {
    ("smallbank_1m", 7):
        "e36b35b2c6358131cf8024e81704d42a5f6570b65e73a47ed9a30b88805f96e0",
    ("smallbank_1m", 2 ** 31 + 5):
        "45028008f3baa5f048708b1465f1cd261c4a7f0e4b131dc4e5259e597577aaf8",
    ("smallbank_1m", 2 ** 32 + 3):
        "81e5d6e5bb568a028a288819f86b18b3e4d8b9ce736318ca9ed4497ec366b467",
    ("tiny_ycsb", 7):
        "215417d724df6176edece54e65b658e4429cb9ff6ca720086db161fdf73da7ea",
    ("tiny_ycsb", 2 ** 31 + 5):
        "0a21879185dedb77d530a8de33f4a67a67d7fa52ae80abba334a789af8c1fb61",
    ("tiny_ycsb", 2 ** 32 + 3):
        "b0878c52e54a769a6b270326b91201ddf25064a40068c3931f6e48be9eba8e9d",
}


@pytest.mark.parametrize("name, seed", sorted(DRAWN))
def test_a_mix_file_draws_the_stream_gen_drew(name, seed):
    import hashlib

    from chipbench import harness
    mix = harness.mix_of(harness.load_config(name)) if name != "tiny_ycsb" \
        else dict(kind="ycsb", theta=0.99, read_frac=0.5, dist_frac=0.1,
                  n_ops=4, n_nodes=8, keys_per_node=384)
    x = gen.draw(mix, seed, 4096)
    h = hashlib.sha256()
    for a in (x.kind, x.key, x.val, x.host):
        assert a.dtype == np.int32
        h.update(a.tobytes())
    assert h.hexdigest() == DRAWN[name, seed]
