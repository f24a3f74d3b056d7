"""Where JAX runs: the platform a process is held to, and the compile cache.

Entry points that drive the device (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` before their first compile.  Library
modules never call it: importing ``repro`` changes no JAX configuration.
"""
from __future__ import annotations

import os

# the checkout root: src/repro/jaxenv.py -> three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def held_to_cpu() -> bool:
    """True when ``JAX_PLATFORMS=cpu`` holds this process to the CPU.

    Decided from the environment alone, so a caller can ask before JAX
    initializes its backends: the CPU device count, forced through
    ``XLA_FLAGS``, locks at that point."""
    return os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and no
    other path is set here.  Otherwise the cache lives at ``.jax_cache`` in
    the checkout: a fixed path, because the path is part of what a later
    process must find again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
