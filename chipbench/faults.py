"""Deliberately broken variants of the served path: the control that the
correctness check must fail, and the faults the harness tests plant
underneath a run.

Each is a context manager that patches the program in this process only,
for the duration of one run.  JAX's caches are cleared on entry and exit,
so no program traced under a patch outlives it."""
from __future__ import annotations

import contextlib
import importlib

import numpy as np


@contextlib.contextmanager
def _patch(module: str, attr: str, make):
    """Replace ``module.attr`` by ``make(original)`` while entered."""
    import jax
    mod = importlib.import_module(module)
    owner_name, _, name = attr.rpartition(".")
    owner = getattr(mod, owner_name) if owner_name else mod
    old = getattr(owner, name)
    jax.clear_caches()
    setattr(owner, name, make(old))
    try:
        yield
    finally:
        setattr(owner, name, old)
        jax.clear_caches()


@contextlib.contextmanager
def no_validation():
    """The control: commit without validation, i.e. without CV rule 5 (the
    lost-update check and the rw-edge check against a key's newest
    creator) and without PostSI rule 5 (abort when no start time is left).
    Two same-wave read-modify-writes of one key then both commit, which
    breaks snapshot isolation (first committer wins) and loses an update:
    the step a later change might take to save commit-loop work."""
    import jax.numpy as jnp
    never = lambda old: (lambda *a, **k: jnp.array(False))

    def no_rule5(old):
        def bounds(*a, **k):
            s_i, c_i, _ = old(*a, **k)
            return s_i, c_i, jnp.array(False)
        return bounds
    with _patch("repro.core.engine", "lost_update", never), \
            _patch("repro.core.engine", "rw_edge_to_creator", never), \
            _patch("repro.core.engine", "postsi_bounds", no_rule5):
        yield


def frozen_state():
    """A block program that acknowledges its waves and leaves the store as
    it was.  The store is copied on the device before each block and the
    copy put back after it, since the program may donate the store it is
    given.  The copy doubles the store's memory, which a store of
    gigabytes does not have room for: this fault is a tool for the CPU and
    small stores."""
    def make(old):
        def run_block(self, stacked):
            import jax
            import jax.numpy as jnp
            store = jax.tree.map(jnp.copy, self.store)
            out = old(self, stacked)
            self.store = store
            return out
        return run_block
    return _patch("repro.service.service", "TxnService._run_block", make)


def half_batch():
    """Only the first half of each wave's rows is executed; the rest are
    run as empty rows, which commit and are acknowledged."""
    def make(old):
        def run_block(self, stacked):
            T = stacked.op_kind.shape[1]
            keep = (np.arange(T) < T // 2)[None, :, None]
            return old(self, stacked._replace(
                op_kind=np.where(keep, stacked.op_kind, 0)))
        return run_block
    return _patch("repro.service.service", "TxnService._run_block", make)


def altered_value():
    """Every version the block program installs holds its value plus 1."""
    def make(old):
        def install(self, store, mask, keys, values, tid, cid, wave_idx):
            return old(self, store, mask, keys, values + 1, tid, cid,
                       wave_idx)
        return install
    return _patch("repro.core.substrate", "LocalSubstrate.install", make)


def no_exchange():
    """The mesh reads each key only on the chip that holds it and merges
    nothing: the other chips see zeros."""
    def make(old):
        def merge(self, mine, *parts):
            import jax.numpy as jnp
            return tuple(jnp.where(mine, p, 0) for p in parts)
        return merge
    return _patch("repro.core.substrate", "MeshSubstrate._merge", make)

