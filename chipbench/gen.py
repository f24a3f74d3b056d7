"""Seeded, vectorised arrival processes, and the transaction mixes found
by name.

Every stream is drawn from ``numpy.random.default_rng(seed)``, so one seed
always gives the same transactions and the same arrival times.  A
configuration's ``mix`` names its kind; ``chipbench/mixes/<kind>.py`` in the
checkout draws that mix's transactions and checks what the program served
of them (see ``draw`` and ``load_mix``), so a new mix comes as a new file.
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
    0; it imports nothing of the program.  ``files.BenchError`` where there
    is no such file."""
    return files.load_module("mixes", kind, root)


def draw(mix: dict, seed: int, n: int, root: str = files.ROOT) -> Txns:
    """Draw ``n`` transactions of the configuration's ``mix``."""
    kw = {k: v for k, v in mix.items() if k != "kind"}
    return load_mix(mix["kind"], root).draw(seed, n, **kw)
