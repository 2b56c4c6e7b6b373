"""Staleness of the served model: the 95th percentile of the intervals
between consecutive round completions in the window (the first measured
from the window's start), in ms."""
import numpy as np


def read(ctx):
    stamps = [ctx.out["t0"]] + list(ctx.out["completions"])
    if len(stamps) < 21:
        return None
    return float(np.percentile(np.diff(stamps), 95) * 1e3)
