"""Where the benchmark finds what belongs to one cell.

Everything that belongs to one configuration, traffic mix, transaction mix
or per-layer metric is a file of its own under ``chipbench/`` in the
checkout that runs, found by the name ``BENCHMARK.json`` or a
configuration gives it, so that a new cell comes as new files only:

    chipbench/configs/<config>.json    deployment: sizes, mix, service
    chipbench/traffic/<traffic>.json   arrivals: open rate or closed clients
    chipbench/mixes/<kind>.py          transactions: ``draw``, ``check``, and
                                       optionally ``initial``, a loaded table
    chipbench/metrics/<metric>.py      reader: ``read(ctx) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(Exception):
    """The run cannot measure what the cell asks for."""


def path_of(kind: str, name: str, ext: str, root: str = ROOT) -> str:
    """``chipbench/<kind>/<name><ext>`` in ``root``; ``BenchError`` where
    there is no such file."""
    path = os.path.join(root, "chipbench", kind, name + ext)
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    return path


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    with open(path_of(kind, name, ".json", root)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """The Python file ``chipbench/<kind>/<name>.py``, loaded from its
    path: a file that a later cell adds needs no package to list it."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_"),
        path_of(kind, name, ".py", root))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
