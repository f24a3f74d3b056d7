"""Load generator: how late the benchmark sent the window's requests,
the 95th percentile of submit time minus due time, in ms."""
from chipbench.harness import percentile


def read(ctx):
    if ctx.traffic["loop"] != "open" or len(ctx.gen_lag_s) == 0:
        return None
    return percentile(ctx.gen_lag_s, 95) * 1e3
