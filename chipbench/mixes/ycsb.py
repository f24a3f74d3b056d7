"""YCSB-style transactions over interleaved per-node partitions.

The shape follows the program's own generator
(``repro.core.workloads.ycsb_txn``), drawn for a whole stream at once
instead of one Python call per transaction:

* keys are interleaved across nodes: local index ``i`` of node ``h`` is
  key ``i * n_nodes + h``;
* a distributed transaction (probability ``dist_frac``) spreads its ops
  over its host plus one or two other nodes, a local one stays on its host;
* a key repeated inside one transaction leaves the later op as canonical
  NOP padding (kind, key and value 0), because the engine assumes distinct
  write keys per transaction.

Its values are one int32 per op, which the program adds to the key's
value; the check is the shared snapshot-isolation check with its
additive replay (``reference.check``).
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.gen import NOP, READ, RMW, Txns

check = reference.check


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


def draw(seed: int, n: int, *, n_nodes: int, keys_per_node: int,
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
