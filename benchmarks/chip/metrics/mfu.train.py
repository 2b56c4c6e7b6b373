"""Model FLOP/s utilization of the training step, in %: the steps whose
program ended in the traced window times the FLOPs a step needs by
design (``counts.py``: every matmul of forward and backward, the tied
head, attention scores, and the probe forward where the trigger has
one; no recomputation) over the window's seconds, chips and the bf16
peak of the device (``peaks.py``)."""
from benchmarks.chip import trace as T
from benchmarks.chip.peaks import peaks_for


def read(ctx):
    tr = ctx.trace
    _, steps = T.modules(tr.evs, ctx.out["step_module"], tr.lo, tr.hi)
    if not steps:
        return None
    flops = steps * ctx.out["tokens_per_step"] * ctx.out["flops_per_token"]
    peak = peaks_for(ctx.device["kind"]).bf16_flops * ctx.device["count"]
    return 100.0 * flops / ((tr.hi - tr.lo) / 1e9) / peak
