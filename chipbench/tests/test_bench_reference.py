"""The plain reference passes served histories and fails broken ones."""
import numpy as np
import pytest

from chipbench import gen, harness, reference

N_KEYS, T, O = 512, 16, 4


def serve(kernels, n=600, seed=3):
    """Serve ``n`` zipfian read/RMW transactions through the streaming
    plane; returns what the reference compares."""
    from repro.service import TxnService
    from repro.service.stream import StreamingDriver
    x = gen.load_mix("ycsb").draw(
        seed, n, n_nodes=8, keys_per_node=N_KEYS // 8, theta=0.99,
        read_frac=0.5, dist_frac=0.1, n_ops=O)
    svc = TxnService(n_keys=N_KEYS, n_versions=4, T=T, O=O, sched="postsi",
                     n_nodes=8, seed=seed, kernels=kernels)
    drv = StreamingDriver(svc, B=2, K=2)
    reqs = []
    for i in range(n):
        reqs.append(svc.submit(x.kind[i], x.key[i], x.val[i], int(x.host[i])))
        if i % (2 * T) == 2 * T - 1:
            drv.tick()
    drv.drain()
    store, _ = harness.store_of(svc.store, N_KEYS)
    return x, harness.answers_of(reqs), harness.history_of(svc), store


@pytest.fixture(scope="module")
def served():
    return serve("jnp")


def test_passes_a_history_served_through_jnp(served):
    x, ans, hist, store = served
    assert ans.committed.sum() > 300
    assert (hist.status == 2).sum() > 0        # contention: some aborts
    assert reference.check(x, ans, hist, store) == dict.fromkeys(
        ["exactly_once", "op_mismatch", "ww_overlap", "snapshot_read",
         "final_value", "ring_version"], 0)


def test_passes_a_history_served_through_interpret_kernels():
    checks = reference.check(*serve("pallas_interpret", n=160))
    assert all(v == 0 for v in checks.values()), checks


def committed_write(ans, hist):
    """(key, ring slot) of the newest version of some key that an
    acknowledged request wrote."""
    com = np.nonzero(hist.status == 1)[0]
    for r in com[::-1]:
        ks = hist.write_key[r][hist.write_key[r] >= 0]
        if len(ks):
            return int(ks[0]), int(hist.write_cid[r][hist.write_key[r] >= 0][0])
    raise AssertionError("no committed write")


def test_fails_an_acknowledged_value_changed(served):
    x, ans, hist, store = served
    k, cid = committed_write(ans, hist)
    val = store.val.copy()
    slot = int(np.nonzero(store.cid[k] == cid)[0][0])
    val[k, slot] += 1
    checks = reference.check(x, ans, hist, store._replace(val=val))
    assert checks["ring_version"] > 0
    assert sum(checks.values()) > 0


def test_fails_an_acknowledged_write_dropped_from_the_store(served):
    x, ans, hist, store = served
    k, cid = committed_write(ans, hist)
    tid, c, head = store.tid.copy(), store.cid.copy(), store.head.copy()
    slot = int(np.nonzero(store.cid[k] == cid)[0][0])
    tid[k, slot], c[k, slot] = -1, 0
    head[k] = (slot - 1) % store.val.shape[1]
    checks = reference.check(x, ans, hist,
                             store._replace(tid=tid, cid=c, head=head))
    assert checks["final_value"] > 0 and checks["ring_version"] > 0


def test_fails_an_acknowledged_write_missing_from_the_history(served):
    x, ans, hist, store = served
    rows = np.nonzero((hist.status == 1)
                      & (hist.write_key >= 0).any(axis=1))[0]
    status = hist.status.copy()
    status[rows[-1]] = 2                     # the execution now aborted
    checks = reference.check(x, ans, hist._replace(status=status), store)
    assert checks["exactly_once"] > 0


def test_fails_a_stale_read(served):
    x, ans, hist, store = served
    rc = hist.read_cid.copy()
    r, o = np.argwhere((hist.status[:, None] == 1) & (rc > 0))[0]
    rc[r, o] -= 1
    checks = reference.check(x, ans, hist._replace(read_cid=rc), store)
    assert checks["snapshot_read"] == 1
