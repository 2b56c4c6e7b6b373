"""Share of the traced window in which no op ran on the device: 1 minus
the union of the ``XLA Ops`` intervals over the window, in %."""
from benchmarks.chip.metrics import _device_idle


def read(ctx):
    return _device_idle.idle_percent(ctx)
