"""Mean host time of one ``fleet.wait`` span of the serving loop, in ms:
waiting for the round's metrics on the device (``block_until_ready``)."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.mean_ms(ctx, "fleet.wait")
