"""Admission: host microseconds per ``TxnService.submit`` in the window,
from the service's own ``submit`` stage timer (``ctx.stage_s``,
``ctx.stage_n``); None where the context does not carry them."""


def read(ctx):
    stage_s = getattr(ctx, "stage_s", None)
    if stage_s is None or not ctx.stage_n.get("submit"):
        return None
    return stage_s["submit"] / ctx.stage_n["submit"] * 1e6
