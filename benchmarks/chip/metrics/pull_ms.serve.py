"""Mean host time of one ``fleet.pull`` span of the serving loop, in ms:
copying the round's metrics to the host (``jax.device_get``)."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.mean_ms(ctx, "fleet.pull")
