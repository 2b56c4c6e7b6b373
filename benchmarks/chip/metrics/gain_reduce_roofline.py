"""The gain-reduce kernel's share of its roofline, in %.  The kernel is
memory-bound (2 FLOPs per 8 bytes read), so its least time is the bytes
of its two f32 inputs, read once (``counts.gain_reduce_bytes`` from the
gradient's element count), over the HBM peak; the share is that over the
summed device time of the kernel's events in the traced window."""
from benchmarks.chip import trace as T
from benchmarks.chip.counts import gain_reduce_bytes
from benchmarks.chip.peaks import peaks_for

KERNEL = r"gain_reduce_kernel"


def read(ctx):
    kernel = ctx.out.get("gain_reduce")
    if not kernel:
        return None
    tr = ctx.trace
    secs, calls = T.op_time(tr.evs, KERNEL, tr.lo, tr.hi)
    if not calls:
        return None
    least = calls * gain_reduce_bytes(kernel["elements"], kernel["agents"]) / (
        peaks_for(ctx.device["kind"]).hbm_bytes_per_s)
    return 100.0 * least / secs
