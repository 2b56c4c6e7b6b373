"""Mean host time of one ``fleet.rollup`` span of the serving loop, in ms:
folding the round's metrics into the rollup (``CommRollup.update``)."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.mean_ms(ctx, "fleet.rollup")
