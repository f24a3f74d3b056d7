"""Commit loop under the open loop: steps of the commit loop per wave over
the window, from the service's own ``commit_step`` count (``ctx.stage_n``:
one step per segment of key-disjoint transactions,
``WaveOut.commit_steps``) over the window's waves; None where the program
does not count its steps."""


def read(ctx):
    n = (getattr(ctx, "stage_n", None) or {}).get("commit_step")
    waves = ctx.window["waves"]
    return n / waves if n and waves else None
