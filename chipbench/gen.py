"""Seeded, vectorised arrival processes, and the transaction mixes found
by name.

Every stream is drawn from ``numpy.random.default_rng(seed)``, so one seed
always gives the same transactions and the same arrival times.  A
configuration's ``mix`` names its kind; ``chipbench/mixes/<kind>.py`` in the
checkout draws that mix's transactions, optionally its loaded table, and
checks what the program served of them (see ``draw``, ``initial`` and
``load_mix``), so a new mix comes as a new file.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from chipbench import files

# op kinds, as the engine numbers them (repro.core.commit_phase)
NOP, READ, WRITE, RMW = 0, 1, 2, 3


class Txns(NamedTuple):
    """A stream of ``n`` transactions of ``O`` ops each.  ``val`` may carry
    trailing dimensions (a record's words per op); the harness hands each
    row to the program as it is."""
    kind: np.ndarray     # [n, O] int32
    key: np.ndarray      # [n, O] int32
    val: np.ndarray      # [n, O, ...] int32
    host: np.ndarray     # [n] int32

    def __len__(self) -> int:
        return self.kind.shape[0]


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


def load_mix(kind: str, root: str = files.ROOT):
    """The mix module ``chipbench/mixes/<kind>.py`` of the checkout
    ``root``.  It exports ``draw(seed, n, *, n_nodes, keys_per_node,
    **mix) -> Txns`` and ``check(txns, answers, history, store) ->
    Dict[str, int]``, the counts that decide ``correct``, each with limit
    0.  It may export ``initial(seed, *, n_keys, n_nodes, keys_per_node,
    **mix) -> np.ndarray``, its loaded table: ``[n_keys, ...]`` int32, the
    value of each key's version 0.  The harness draws that table in
    set-up, as a load phase would, hands it to ``TxnService`` as the
    keyword ``initial`` and to the check as ``check(txns, answers,
    history, store, initial=table)``; where a mix exports no ``initial``,
    the program boots every key itself and ``check`` gets no table.  A
    mix imports nothing of the program.  ``files.BenchError`` where there
    is no such file."""
    return files.load_module("mixes", kind, root)


def _kw(mix: dict) -> dict:
    return {k: v for k, v in mix.items() if k != "kind"}


def draw(mix: dict, seed: int, n: int, root: str = files.ROOT) -> Txns:
    """Draw ``n`` transactions of the configuration's ``mix``."""
    return load_mix(mix["kind"], root).draw(seed, n, **_kw(mix))


def initial(mix: dict, seed: int, n_keys: int, root: str = files.ROOT):
    """The loaded table of the configuration's ``mix`` (see ``load_mix``),
    or None where the mix brings none; ``files.BenchError`` where it is
    not ``[n_keys, ...]`` int32."""
    mod = load_mix(mix["kind"], root)
    if not hasattr(mod, "initial"):
        return None
    table = mod.initial(seed, n_keys=n_keys, **_kw(mix))
    if not (isinstance(table, np.ndarray) and table.dtype == np.int32
            and table.ndim and len(table) == n_keys):
        raise files.BenchError(
            f"mix {mix['kind']!r}: initial must give [{n_keys}, ...] "
            f"int32, not {getattr(table, 'dtype', type(table))} "
            f"{getattr(table, 'shape', '')}")
    return table
