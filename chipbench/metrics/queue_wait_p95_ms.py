"""Admission and forming: how long the window's committed requests waited
to be dispatched, the 95th percentile of ``t_dispatch - t_submit`` (the
requests' own stamps, ``TxnRequest``), in ms.  Reads
``ctx.queue_wait_s``; None where the context does not carry it."""
from chipbench.harness import percentile


def read(ctx):
    x = getattr(ctx, "queue_wait_s", None)
    if x is None or len(x) == 0:
        return None
    return percentile(x, 95) * 1e3
