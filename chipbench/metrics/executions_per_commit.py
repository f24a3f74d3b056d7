"""Retry and routing: executions (first tries and retries) per committed
transaction over the window, from the service's own counters."""


def read(ctx):
    w = ctx.window
    return w["executions"] / w["committed"] if w["committed"] else None
