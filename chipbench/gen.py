"""Seeded, vectorised request generators and arrival processes.

Every stream is drawn from ``numpy.random.default_rng(seed)``, so one seed
always gives the same transactions and the same arrival times.  The
YCSB-style shape follows the program's own generator
(``repro.core.workloads.ycsb_txn``), drawn for a whole stream at once
instead of one Python call per transaction; SmallBank follows its
published schema and procedures (see ``smallbank``).  In YCSB:

* keys are interleaved across nodes: local index ``i`` of node ``h`` is
  key ``i * n_nodes + h``;
* a distributed transaction (probability ``dist_frac``) spreads its ops
  over its host plus one or two other nodes, a local one stays on its host;
* a key repeated inside one transaction leaves the later op as canonical
  NOP padding (kind, key and value 0), because the engine assumes distinct
  write keys per transaction.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

# op kinds, as the engine numbers them (repro.core.commit_phase)
NOP, READ, WRITE, RMW = 0, 1, 2, 3


class Txns(NamedTuple):
    """A stream of ``n`` transactions of ``O`` ops each."""
    kind: np.ndarray     # [n, O] int32
    key: np.ndarray      # [n, O] int32
    val: np.ndarray      # [n, O] int32
    host: np.ndarray     # [n] int32

    def __len__(self) -> int:
        return self.kind.shape[0]


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """CDF of the bounded zipfian over ranks ``0..n-1``,
    ``P(rank=k) ∝ 1/(k+1)^theta``; rank 0 is the hottest key and
    ``theta=0`` is uniform."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf


def _nodes(rng, n: int, n_nodes: int, dist_frac: float) -> np.ndarray:
    """[n, 3] node choices per transaction and [n] count of usable ones:
    column 0 is the host; a distributed transaction adds one or two
    distinct other nodes."""
    host = rng.integers(0, n_nodes, n)
    dist = rng.random(n) < dist_frac
    n_extra = np.where(dist, rng.integers(1, 3, n), 0)
    n_extra = np.minimum(n_extra, n_nodes - 1)
    off1 = rng.integers(1, max(n_nodes, 2), n)
    off2 = rng.integers(1, max(n_nodes - 1, 2), n)
    off2 = np.where(off2 >= off1, off2 + 1, off2)
    nodes = np.stack([host, (host + off1) % n_nodes,
                      (host + off2) % n_nodes], axis=1)
    return nodes, 1 + n_extra


def _dedup(kind, key, val) -> None:
    """NOP out (in place) every op whose key an earlier active op of the
    same transaction already holds."""
    for o in range(1, kind.shape[1]):
        dup = ((key[:, :o] == key[:, o:o + 1])
               & (kind[:, :o] != NOP)).any(axis=1) & (kind[:, o] != NOP)
        kind[dup, o] = NOP
        key[dup, o] = 0
        val[dup, o] = 0


def ycsb(seed: int, n: int, *, n_nodes: int, keys_per_node: int,
         theta: float, read_frac: float, dist_frac: float,
         n_ops: int) -> Txns:
    """YCSB-style transactions: ``n_ops`` ops each, READ with probability
    ``read_frac`` else an RMW adding 1..99, on zipfian(``theta``) ranks of
    the chosen node's partition (every partition shares the popularity
    curve, so rank 0 of each node is hot)."""
    rng = np.random.default_rng(seed)
    nodes, n_use = _nodes(rng, n, n_nodes, dist_frac)
    pick = (rng.random((n, n_ops)) * n_use[:, None]).astype(np.int64)
    node = np.take_along_axis(nodes, pick, axis=1)
    rank = np.searchsorted(zipf_cdf(keys_per_node, theta),
                           rng.random((n, n_ops)), side="right")
    key = (rank * n_nodes + node).astype(np.int32)
    kind = np.where(rng.random((n, n_ops)) < read_frac, READ, RMW)
    kind = kind.astype(np.int32)
    val = np.where(kind == RMW, rng.integers(1, 100, (n, n_ops)), 0)
    val = val.astype(np.int32)
    _dedup(kind, key, val)
    return Txns(kind, key, val, nodes[:, 0].astype(np.int32))


# SmallBank procedures, in OLTP-Bench's order (the order of a mix's weights)
SMALLBANK_PROCS = ("amalgamate", "balance", "deposit_checking",
                   "send_payment", "transact_savings", "write_check")
ACCOUNT, SAVINGS, CHECKING = 0, 1, 2     # SmallBank's tables


def smallbank(seed: int, n: int, *, n_nodes: int, keys_per_node: int,
              weights, n_ops: int = 4) -> Txns:
    """SmallBank transactions over its three tables, one row per customer
    in each: ``account`` (name to customer id), ``savings`` and
    ``checking`` (a balance each).  Table ``t``'s row of customer ``c`` is
    key ``t * accounts + c``, so all three rows of a customer live on node
    ``c % n_nodes``, the transaction's host.  Customers are uniform over
    all accounts; a procedure on two customers draws two distinct ones.

    * balance — READ the customer's account, savings and checking rows;
    * deposit_checking — READ account, RMW checking by +amount;
    * send_payment — READ both accounts, RMW the first checking by
      -amount and the second by +amount;
    * transact_savings — READ account, RMW savings by +amount;
    * write_check — READ account and savings, RMW checking by -amount.

    Amounts are 1..99.  ``weights`` gives each procedure's share, in
    ``SMALLBANK_PROCS`` order; amalgamate's must be 0, because it moves a
    balance it has read and the program's writes only add constants."""
    rng = np.random.default_rng(seed)
    w = np.asarray(weights, np.float64)
    if w.shape != (len(SMALLBANK_PROCS),) or (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"smallbank: need {len(SMALLBANK_PROCS)} "
                         f"non-negative weights, got {weights!r}")
    if w[0] > 0:
        raise ValueError("smallbank: amalgamate writes what it reads; "
                         "give it weight 0")
    accounts = n_nodes * keys_per_node // 3
    if n_ops < 4 or accounts * 3 != n_nodes * keys_per_node \
            or accounts % n_nodes:
        raise ValueError(f"smallbank: need 4 ops and three tables of a "
                         f"multiple of {n_nodes} rows each")
    proc = np.searchsorted(np.cumsum(w / w.sum()), rng.random(n),
                           side="right")
    proc = np.minimum(proc, len(SMALLBANK_PROCS) - 1)
    c1 = rng.integers(0, accounts, n)
    c2 = (c1 + rng.integers(1, accounts, n)) % accounts
    amt = rng.integers(1, 100, n)
    kind = np.zeros((n, n_ops), np.int32)
    key = np.zeros((n, n_ops), np.int32)
    val = np.zeros((n, n_ops), np.int32)

    def put(p, o, op, table, cust, v=0):
        m = proc == SMALLBANK_PROCS.index(p)
        kind[m, o] = op
        key[m, o] = table * accounts + cust[m]
        val[m, o] = v[m] if isinstance(v, np.ndarray) else v

    put("balance", 0, READ, ACCOUNT, c1)
    put("balance", 1, READ, SAVINGS, c1)
    put("balance", 2, READ, CHECKING, c1)
    put("deposit_checking", 0, READ, ACCOUNT, c1)
    put("deposit_checking", 1, RMW, CHECKING, c1, amt)
    put("send_payment", 0, READ, ACCOUNT, c1)
    put("send_payment", 1, READ, ACCOUNT, c2)
    put("send_payment", 2, RMW, CHECKING, c1, -amt)
    put("send_payment", 3, RMW, CHECKING, c2, amt)
    put("transact_savings", 0, READ, ACCOUNT, c1)
    put("transact_savings", 1, RMW, SAVINGS, c1, amt)
    put("write_check", 0, READ, ACCOUNT, c1)
    put("write_check", 1, READ, SAVINGS, c1)
    put("write_check", 2, RMW, CHECKING, c1, -amt)
    return Txns(kind, key, val, (c1 % n_nodes).astype(np.int32))


def poisson_times(seed, rate: float, duration: float) -> np.ndarray:
    """Due times in seconds of a Poisson process at ``rate`` per second
    over ``[0, duration)``, conditioned on its mean count: exactly
    ``round(rate * duration)`` arrivals at sorted uniform times."""
    rng = np.random.default_rng(seed)
    return np.sort(rng.uniform(0.0, duration, int(round(rate * duration))))


def arrivals(seed: int, rate: float, segments) -> np.ndarray:
    """The due times of one run, over consecutive ``segments`` (seconds:
    warm-up, window, follow-up), each a ``poisson_times`` drawn from
    ``seed`` and the segment's index: every seed offers each segment the
    same number of arrivals."""
    out, t0 = [], 0.0
    for k, d in enumerate(segments):
        out.append(t0 + poisson_times([seed, k], rate, d))
        t0 += d
    return np.concatenate(out)


def draw(mix: dict, seed: int, n: int) -> Txns:
    """Draw ``n`` transactions of the configuration's ``mix``."""
    kw = {k: v for k, v in mix.items() if k != "kind"}
    if mix["kind"] == "ycsb":
        return ycsb(seed, n, **kw)
    if mix["kind"] == "smallbank":
        return smallbank(seed, n, **kw)
    raise ValueError(f"unknown transaction mix {mix['kind']!r}")
