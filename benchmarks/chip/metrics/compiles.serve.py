"""Programs compiled (or loaded from the persistent compile cache) inside
the measured window, counted by the program's compile record
(``repro.launch.compile_cache.compile_events``)."""
from benchmarks.chip.metrics import _host_span


def read(ctx):
    return _host_span.compiles_in_window(ctx)
