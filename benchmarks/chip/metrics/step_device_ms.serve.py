"""Device time of the jitted train step's own program per served round,
in ms: the summed ``XLA Modules`` events of the step over the rounds
whose step ended in the traced window."""
from benchmarks.chip import trace as T


def read(ctx):
    tr = ctx.trace
    secs, n = T.modules(tr.evs, ctx.out["step_module"], tr.lo, tr.hi)
    return secs / n * 1e3 if n else None
