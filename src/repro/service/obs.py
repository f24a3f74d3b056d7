"""Host stage timers of the served path (DESIGN.md §8).

``stage(svc, name)`` times one stage of serving on ``time.perf_counter``
and adds its seconds and count to ``svc.stage_s[name]`` and
``svc.stage_n[name]``, which ``ServiceReport`` exports.  While a JAX
profiler trace is being taken, and only then, the stage also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``: the span lands on
the trace's host plane, on the same clock as the device's operations, so
an idle gap on the device can be put down to the stage the host was in.

Stages of ``StreamingDriver`` and ``TxnService``:

    tick         one pipelined tick (a planned tick is its flush and step)
    form         the tick's wave-forming loop
    dispatch     stacking a block and handing it to the device
    retire_wait  the host blocked on the oldest block's outcomes
    route        GC, history, WAL and outcome routing of a retired block
    flush        shipping the open block and retiring every block in flight
    step         one tick of the synchronous step loop
    submit       ``TxnService.submit``; ``record`` only, never a span, as
                 it runs once per request
"""
from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

SPAN_PREFIX = "repro."


def record(svc, name: str, t0: float) -> None:
    """Add the seconds since ``t0`` and one count to stage ``name``."""
    svc.stage_s[name] += perf_counter() - t0
    svc.stage_n[name] += 1


class stage:
    """Context manager timing one stage of ``svc``, and naming it in a
    profiler trace while one is being taken."""

    __slots__ = ("svc", "name", "t0", "_ann")

    def __init__(self, svc, name: str):
        self.svc, self.name = svc, name

    def __enter__(self) -> "stage":
        self._ann = None
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
            self._ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        record(self.svc, self.name, self.t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
