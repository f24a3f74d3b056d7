"""The plain reference that decides ``correct``: snapshot isolation and a
serial replay of the committed values, vectorised with numpy.

It imports nothing of the program.  Its inputs are what the benchmark
itself generated and submitted (``Txns``), the program's answers to those
requests (status and the TIDs each execution ran under), the served
history (per-row status, start/commit times, read and write keys with the
CIDs read and stamped) and the final store, read back shard by shard.
Every other per-row output of the program, every other per-key field of
its store and the values each request was answered with arrive in
``History.extra``, ``Store.extra`` and ``Answers.extra``, for a mix's own
check (``chipbench/mixes/``).  ``check`` counts, each with limit 0:

* ``exactly_once`` — every acknowledged request owns exactly one committed
  history row (the one of its last execution) and every other request
  owns none; no committed row with ops belongs to no request;
* ``op_mismatch`` — each committed row read and wrote exactly the keys its
  request asked for;
* ``ww_overlap`` — committed writers of one key have disjoint intervals
  (SI's first rule), and no two committed versions of a key share a CID;
* ``snapshot_read`` — every committed read returned the newest committed
  version of its key with ``CID <= s`` (SI's second rule);
* ``final_value`` — each key's newest version in the store holds the value
  and CID that a serial replay of the committed requests, in commit order,
  gives it (an untouched key still holds its bootstrap version 0);
* ``ring_version`` — the store's rings hold exactly the newest ``V``
  committed versions of each key, each with its creator's TID and the
  replayed value, so an aborted or dropped execution left nothing behind.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np

from chipbench.gen import READ, RMW, WRITE, Txns

COMMITTED = 1            # history row status, as the engine numbers it


class History(NamedTuple):
    """Served history rows, concatenated over every executed wave."""
    tid: np.ndarray        # [N]
    status: np.ndarray     # [N]
    s: np.ndarray          # [N]
    c: np.ndarray          # [N]
    read_key: np.ndarray   # [N, O] (-1 where not a read)
    read_cid: np.ndarray   # [N, O]
    write_key: np.ndarray  # [N, O] (-1 where not a write)
    write_cid: np.ndarray  # [N, O]
    extra: Dict[str, np.ndarray] = {}   # every other [N, ...] output


class Answers(NamedTuple):
    """The program's answers, one row per submitted request (aligned with
    the benchmark's own ``Txns`` record of what it submitted)."""
    committed: np.ndarray  # [n] bool
    last_tid: np.ndarray   # [n] TID of the last execution (-1: never ran)
    exec_req: np.ndarray   # [E] request index of every execution
    exec_tid: np.ndarray   # [E] TID of that execution
    extra: Dict[str, np.ndarray] = {}   # every other [n, ...] answer


class Store(NamedTuple):
    """Final store, host copies of the rings: [n_keys, V] and [n_keys]."""
    val: np.ndarray
    tid: np.ndarray
    cid: np.ndarray
    head: np.ndarray
    extra: Dict[str, np.ndarray] = {}   # every other [n_keys, ...] field


def _code(key, cid) -> np.ndarray:
    """One sortable int64 per (key, cid) pair."""
    return (np.asarray(key, np.int64) << 32) | np.asarray(cid, np.int64)


def check(txns: Txns, ans: Answers, hist: History, store: Store
          ) -> Dict[str, int]:
    """Count violations of each guarantee (see the module docstring)."""
    if (txns.kind == WRITE).any():
        raise ValueError("the replay covers READ and RMW ops; no "
                         "configured mix issues blind writes")
    n = len(txns)
    n_keys, V = store.val.shape
    out: Dict[str, int] = {}

    # ---- exactly once: which request owns each history row -----------------
    order = np.argsort(ans.exec_tid, kind="stable")
    et, er = ans.exec_tid[order], ans.exec_req[order]
    pos = np.clip(np.searchsorted(et, hist.tid), 0, max(len(et) - 1, 0))
    owned = (et[pos] == hist.tid) if len(et) else np.zeros(len(hist.tid),
                                                           bool)
    owner = np.where(owned, er[pos] if len(et) else 0, -1)
    has_ops = ((hist.read_key >= 0) | (hist.write_key >= 0)).any(axis=1)
    com = hist.status == COMMITTED
    per_req = np.bincount(owner[com & owned], minlength=n)[:n]
    last_ok = np.zeros(n, bool)
    rows = np.nonzero(com & owned)[0]
    last_ok[owner[rows]] = hist.tid[rows] == ans.last_tid[owner[rows]]
    out["exactly_once"] = int(
        (ans.committed & ((per_req != 1) | ~last_ok)).sum()
        + (~ans.committed & (per_req > 0)).sum()
        + (com & has_ops & ~owned).sum())

    # ---- each committed row did what its request asked ---------------------
    r = owner[rows]
    want_rk = np.where((txns.kind[r] == READ) | (txns.kind[r] == RMW),
                       txns.key[r], -1)
    want_wk = np.where((txns.kind[r] == WRITE) | (txns.kind[r] == RMW),
                       txns.key[r], -1)
    out["op_mismatch"] = int(((hist.read_key[rows] != want_rk)
                              | (hist.write_key[rows] != want_wk))
                             .any(axis=1).sum())

    # ---- committed versions, in (key, cid) order ---------------------------
    crow = np.nonzero(com)[0]
    wk, wc = hist.write_key[crow], hist.write_cid[crow]
    wm = wk >= 0
    w_key, w_cid = wk[wm], wc[wm]
    w_row = np.broadcast_to(crow[:, None], wk.shape)[wm]
    # deltas come from the benchmark's own record of each request
    ow = owner[w_row]
    o_idx = np.broadcast_to(np.arange(wk.shape[1]), wk.shape)[wm]
    w_delta = np.where(ow >= 0, txns.val[np.maximum(ow, 0), o_idx], 0)
    vo = np.lexsort((w_cid, w_key))
    w_key, w_cid, w_row, w_delta = (w_key[vo], w_cid[vo], w_row[vo],
                                    w_delta[vo].astype(np.int64))
    same = w_key[1:] == w_key[:-1]
    ww = same & (hist.s[w_row[1:]] < hist.c[w_row[:-1]])
    dup = same & (w_cid[1:] == w_cid[:-1])
    out["ww_overlap"] = int(ww.sum() + dup.sum())

    # ---- snapshot reads ----------------------------------------------------
    rk, rc = hist.read_key[crow], hist.read_cid[crow]
    rm = rk >= 0
    r_key, r_cid = rk[rm], rc[rm]
    r_s = np.broadcast_to(hist.s[crow][:, None], rk.shape)[rm]
    vcode = _code(w_key, w_cid)
    p = np.searchsorted(vcode, _code(r_key, r_s), side="right") - 1
    hit = (p >= 0) & (w_key[np.maximum(p, 0)] == r_key) if len(vcode) \
        else np.zeros(len(r_key), bool)
    expect = np.where(hit, w_cid[np.maximum(p, 0)] if len(vcode) else 0, 0)
    out["snapshot_read"] = int((r_cid != expect).sum())

    # ---- serial replay: the value after each committed version -------------
    start = np.ones(len(w_key), bool)
    start[1:] = ~same
    csum = np.cumsum(w_delta)
    seg0 = np.maximum.accumulate(np.where(start, np.arange(len(w_key)), 0))
    base = csum[seg0] - w_delta[seg0]
    w_val = csum - base                      # running sum within each key
    last = np.ones(len(w_key), bool)
    last[:-1] = ~same
    exp_val = np.zeros(n_keys, np.int64)
    exp_cid = np.zeros(n_keys, np.int64)
    exp_val[w_key[last]] = w_val[last]
    exp_cid[w_key[last]] = w_cid[last]
    ar = np.arange(n_keys)
    out["final_value"] = int(
        ((store.val[ar, store.head].astype(np.int64) != exp_val)
         | (store.cid[ar, store.head] != exp_cid)).sum())

    # ---- rings: exactly the newest V committed versions of each key --------
    from_end = np.zeros(len(w_key), np.int64)   # 0 for a key's newest
    if len(w_key):
        seg_end = np.minimum.accumulate(
            np.where(last, np.arange(len(w_key)), len(w_key))[::-1])[::-1]
        from_end = seg_end - np.arange(len(w_key))
    keep = from_end < V
    k_code = _code(w_key[keep], w_cid[keep])
    k_tid = hist.tid[w_row[keep]]
    k_val = w_val[keep]
    sk, sv = np.nonzero((store.cid > 0) & (store.tid >= 0))
    s_code = _code(sk, store.cid[sk, sv])
    so = np.argsort(s_code)
    s_code, sk, sv = s_code[so], sk[so], sv[so]
    if len(s_code) == len(k_code) and (s_code == k_code).all():
        bad = ((store.tid[sk, sv] != k_tid)
               | (store.val[sk, sv].astype(np.int64) != k_val)).sum()
    else:
        bad = len(np.setxor1d(s_code, k_code)) or 1
    out["ring_version"] = int(bad)
    return out
