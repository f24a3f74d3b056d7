"""Retry and routing under the open loop: executions per committed
transaction over the window, from the service's own counters."""


def read(ctx):
    w = ctx.window
    return w["executions"] / w["committed"] if w["committed"] else None
