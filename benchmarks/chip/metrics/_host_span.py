"""Shared arithmetic of the readers of the serving loop's host spans
(``fleet.<stage>``, one ``TraceAnnotation`` per stage of a round, opened
by ``FleetSession.run``) and of the compile record."""
from benchmarks.chip import trace as T


def mean_ms(ctx, span):
    """Mean duration, in ms, of the host spans named ``span`` that ended
    in the traced window; ``None`` where the trace holds no device or no
    such span.  A ``#...#`` metadata suffix on the name is ignored."""
    tr = ctx.trace
    if not T.device_planes(tr.evs):
        return None
    hit = [e.end - e.start for e in tr.evs
           if e.plane == T.HOST_PLANE and e.name.split("#", 1)[0] == span
           and tr.lo <= e.end <= tr.hi]
    return sum(hit) / len(hit) / 1e6 if hit else None


def compiles_in_window(ctx):
    """Compiles (or loads from the persistent cache) that finished inside
    the measured window ``[t0, t0 + window_s]`` on the host clock;
    ``None`` where the trace holds no device or the program keeps no
    compile record."""
    if not T.device_planes(ctx.trace.evs):
        return None
    try:
        from repro.launch.compile_cache import compile_events
    except ImportError:
        return None
    t0 = ctx.out["t0"]
    t1 = t0 + ctx.out["window_s"]
    return sum(1 for e in compile_events() if t0 <= e.end <= t1)
