"""Retry and routing: host microseconds in the service's ``route`` stage
(GC, history and outcome routing of retired blocks) per execution routed
in the window.  Reads the window's stage timers, ``ctx.stage_s``; None
where the context does not carry them."""


def read(ctx):
    stage_s = getattr(ctx, "stage_s", None)
    n = ctx.window["executions"]
    if stage_s is None or "route" not in stage_s or not n:
        return None
    return stage_s["route"] / n * 1e6
