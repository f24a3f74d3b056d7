"""SmallBank (Alomari, Cahill, Fekete, Roehm, ICDE 2008): its three tables
and its procedures, in the mix its configuration weights.

Its writes add a constant fixed when the request is formed, so the check is
the shared snapshot-isolation check with its additive replay
(``reference.check``).
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.gen import READ, RMW, Txns

check = reference.check

# SmallBank procedures, in OLTP-Bench's order (the order of a mix's weights)
SMALLBANK_PROCS = ("amalgamate", "balance", "deposit_checking",
                   "send_payment", "transact_savings", "write_check")
ACCOUNT, SAVINGS, CHECKING = 0, 1, 2     # SmallBank's tables


def draw(seed: int, n: int, *, n_nodes: int, keys_per_node: int,
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
