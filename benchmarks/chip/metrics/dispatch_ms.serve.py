"""Mean host time of one ``fleet.dispatch`` span of the serving loop, in ms:
dispatching the jitted train step."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.mean_ms(ctx, "fleet.dispatch")
