"""Mean host time of one ``fleet.sample`` span of the serving loop, in ms:
sampling the next round's observations (the session's ``batch_fn``)."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.mean_ms(ctx, "fleet.sample")
