"""Mesh collectives: device ms of cross-chip collective operations per
wave executed in the traced window, averaged over the chips."""
import re

# collective operations, as the trace names them
COLLECTIVE = re.compile(r"%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def read(ctx):
    t = ctx.trace
    if t is None or not t.waves:
        return None
    s = t.op_s(lambda n: COLLECTIVE.match(n) is not None)
    return s / t.waves * 1e3 if s > 0 else None
