"""Shared arithmetic of the ``device_idle.*`` readers."""
from benchmarks.chip import trace as T


def idle_percent(ctx):
    tr = ctx.trace
    share = T.idle_share(tr.evs, tr.lo, tr.hi)
    return None if share is None else 100.0 * share
