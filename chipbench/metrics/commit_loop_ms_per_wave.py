"""Commit loop: device ms per traced wave of the operations under the
block program's ``commit_loop`` scope (``engine.run_wave_on``), leaf
operations only, from each operation's ``tf_op`` in the trace.  Reads
``ctx.scoped`` (``chipbench.trace.Scoped``); None where the context
does not carry it or no operation carries the scope."""


def read(ctx):
    s = getattr(ctx, "scoped", None)
    if s is None or not s.waves:
        return None
    v = s.scoped_s("commit_loop")
    return v / s.waves * 1e3 if v > 0 else None
