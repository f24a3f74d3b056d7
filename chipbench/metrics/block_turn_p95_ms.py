"""Pipeline: from a block's dispatch to the routing of its commits, the
95th percentile of ``t_ack - t_dispatch`` over the window's committed
requests (``TxnRequest`` stamps), in ms.  Reads ``ctx.block_turn_s``;
None where the context does not carry it."""
from chipbench.harness import percentile


def read(ctx):
    x = getattr(ctx, "block_turn_s", None)
    if x is None or len(x) == 0:
        return None
    return percentile(x, 95) * 1e3
