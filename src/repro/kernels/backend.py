"""The kernel-backend plane: one resolved config for every data-plane op.

Every compute hot spot the engine dispatches — the read-phase
latest-visible-version selection (``ops.version_scan``, paper §IV-B CID
rule), the anti-dependency candidate build (``ops.potential_matrix``, CV
rule 6 / PostSI negotiation input) — routes through a single
:class:`KernelConfig` instead of a per-op module global.  The config is
resolved ONCE (``auto`` never survives resolution) and then *threaded as a
field of the data-access substrate* (``core.substrate``), so a jitted
engine has its backend baked in at trace time and two engines with
different backends coexist in one process.

Backends:

  ``pallas``           Mosaic-compiled kernels (TPU only).
  ``pallas_interpret`` the same kernel bodies, interpreted (the CPU route;
                       how the tests exercise the kernels — bit-identical to
                       ``pallas`` by construction).
  ``jnp``              pure-jnp references (``kernels.ref``) — the escape
                       hatch and the differential-test oracle.
  ``auto``             resolves to ``pallas`` on TPU, ``pallas_interpret``
                       elsewhere.  Only accepted as *input*; a resolved
                       :class:`KernelConfig` never carries it.

Fusion: orthogonally to the backend, ``KernelConfig(fused=True)`` routes
the whole wave read phase (slot selection + rule-3 interval seed +
anti-dependency build) through the single-launch ``ops.wave_commit``
megakernel instead of three separate dispatches — bit-identical by
construction (DESIGN.md §7).  A backend spec string may carry it as a
``"+fused"`` suffix (``"pallas_interpret+fused"``) so the knob threads
through every name-typed seam (env var, CLI, bench labels) unchanged.

Process default: ``default_backend()`` reads env ``REPRO_KERNEL_BACKEND``
(falling back to the pre-refactor ``REPRO_POTENTIAL_BACKEND`` name, then
``auto``), with env ``REPRO_KERNEL_FUSED=1`` forcing the fused route;
``set_default_backend`` switches it and clears every jit cache
registered via :func:`register_cache_clear`, because engines that defaulted
to the process config baked it in at trace time.  Explicitly-threaded
configs need no cache clearing: a different resolved config is a different
static jit argument.
"""
from __future__ import annotations

import dataclasses
import os

import jax

BACKENDS = ("pallas", "pallas_interpret", "jnp")
_INPUT_BACKENDS = BACKENDS + ("auto",)
_FUSED_SUFFIX = "+fused"


def _parse_spec(name: str):
    """Split an input spec into (base backend name, fused flag)."""
    fused = name.endswith(_FUSED_SUFFIX)
    return (name[:-len(_FUSED_SUFFIX)] if fused else name), fused


def _resolve_name(name: str) -> str:
    assert name in _INPUT_BACKENDS, (name, _INPUT_BACKENDS)
    if name != "auto":
        return name
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Resolved kernel-backend choice for one substrate/engine instance.

    Frozen + hashable so it can ride as a static jit argument and as an
    ``lru_cache`` key for the shard_map executors.  ``backend`` is always a
    concrete member of :data:`BACKENDS` — construct via :func:`resolve` (or
    pass ``"auto"`` to ``KernelConfig`` itself, which resolves eagerly; a
    ``"+fused"`` suffix on the name sets ``fused``).

    ``fused`` selects the single-launch ``ops.wave_commit`` read-phase
    megakernel over the three-dispatch route; it composes with any backend
    (the jnp leg runs the fused reference composition in ``kernels.ref``).
    """
    backend: str = "auto"
    fused: bool = False

    def __post_init__(self):
        base, fused = _parse_spec(self.backend)
        object.__setattr__(self, "backend", _resolve_name(base))
        if fused:
            object.__setattr__(self, "fused", True)

    @property
    def use_pallas(self) -> bool:
        """The ``use_pallas`` flag of the ``kernels.ops`` wrappers."""
        return self.backend != "jnp"

    @property
    def interpret(self) -> bool:
        """The ``interpret`` flag of the ``kernels.ops`` wrappers."""
        return self.backend == "pallas_interpret"

    @property
    def name(self) -> str:
        """Round-trippable spec string (``resolve(cfg.name) == cfg``)."""
        return self.backend + (_FUSED_SUFFIX if self.fused else "")


def resolve(spec=None) -> KernelConfig:
    """Normalize ``None`` (process default) / backend name / config into a
    resolved :class:`KernelConfig`."""
    if spec is None:
        spec = default_backend()
    if isinstance(spec, KernelConfig):
        return spec
    return KernelConfig(spec)


def can_compile_pallas() -> bool:
    """True iff this process runs on a TPU, the only target Mosaic compiles
    these kernels for.

    Decided by platform, not by a trial compile: on a TPU a kernel that
    Mosaic refuses raises where it is compiled, and is never mistaken for
    a platform without Mosaic (``substrate.mesh_kernels`` degrades
    ``pallas`` to ``jnp`` only where this is False)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# process default + jit-cache invalidation for engines that bake it in
# ---------------------------------------------------------------------------

_default = os.environ.get(
    "REPRO_KERNEL_BACKEND",
    os.environ.get("REPRO_POTENTIAL_BACKEND", "auto"))
if os.environ.get("REPRO_KERNEL_FUSED", "") not in ("", "0") \
        and not _default.endswith(_FUSED_SUFFIX):
    _default = _default + _FUSED_SUFFIX
_clear_hooks: list = []


def register_cache_clear(jitted) -> None:
    """Engines whose traces read the *process default* register their jitted
    entry points here; :func:`set_default_backend` clears them so a switch
    takes effect on the next dispatch."""
    _clear_hooks.append(jitted)


def set_default_backend(name: str) -> None:
    """Switch the process-default backend (accepts ``auto`` and a
    ``"+fused"`` suffix) and clear the registered jit caches."""
    global _default
    base, _ = _parse_spec(name)
    assert base in _INPUT_BACKENDS, (name, _INPUT_BACKENDS)
    _default = name
    for fn in _clear_hooks:
        try:
            fn.clear_cache()
        except Exception:
            pass


def default_backend() -> str:
    """The resolved (never ``auto``) process-default backend spec — the
    backend name plus an optional ``"+fused"`` suffix."""
    base, fused = _parse_spec(_default)
    return _resolve_name(base) + (_FUSED_SUFFIX if fused else "")
