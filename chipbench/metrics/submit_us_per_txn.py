"""Admission: host microseconds per request in ``TxnService.submit``,
from the benchmark's spans around its submit calls in the window."""


def read(ctx):
    return ctx.submit_s / ctx.submitted * 1e6 if ctx.submitted else None
