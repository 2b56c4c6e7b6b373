"""The reduction from a profiler trace to device metrics.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` flattens it to
:class:`Ev` records (plane, line, name, start and end in ns).  Everything
else here works on those records, so the arithmetic is tested on
synthetic events and on a small trace recorded on the chip.

Planes and lines of a TPU trace (jax 0.9): each chip is a plane named
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per executed
HLO op (a Pallas kernel is one such op) and its ``XLA Modules`` line one
event per executed program, named ``jit_<function>(<id>)``.  Host
threads are lines of the ``/host:CPU`` plane.  The benchmark marks its
measured window on the host with a ``TraceAnnotation`` named
:data:`WINDOW`.
"""
from __future__ import annotations

import contextlib
import glob
import re
from pathlib import Path
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
# ops whose event spans the ops they run (loops, branches, calls): left
# out of the per-op ranking, which would count their bodies twice
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: int  # ns
    end: int    # ns


def short(name: str) -> str:
    """An op event's name is its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``); the part before `` = `` names
    it without matching the operands it reads."""
    return name.split(" = ", 1)[0]


@contextlib.contextmanager
def recording(trace_dir):
    """Profile the block into ``trace_dir``; ``None`` profiles nothing.
    Python function tracing stays off (it slows a host-bound loop several
    fold); the runtime's own host events stay on."""
    if trace_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def window_mark():
    """The host annotation :func:`window` finds."""
    import jax

    return jax.profiler.TraceAnnotation(WINDOW)


def find_xplane(trace_dir) -> Path:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(files[-1])


def load(path) -> List[Ev]:
    """Every event of an ``.xplane.pb`` file (or of its gzip)."""
    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                out.append(Ev(plane.name, line.name, e.name, s,
                              s + int(e.duration_ns)))
    return out


def window(evs: Sequence[Ev], name: str = WINDOW) -> Tuple[int, int]:
    """The measured window: the host annotation the benchmark put round it."""
    marks = [e for e in evs if e.plane == HOST_PLANE and e.name == name]
    if not marks:
        raise ValueError(f"trace holds no {name!r} annotation")
    m = max(marks, key=lambda e: e.end - e.start)
    return m.start, m.end


def device_planes(evs: Iterable[Ev]) -> List[str]:
    return sorted({e.plane for e in evs if DEVICE_PLANE.match(e.plane)})


def _clip(evs: Iterable[Ev], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in evs
            if e.end > lo and e.start < hi]


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of half-open intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def ops(evs: Sequence[Ev], plane: str) -> List[Ev]:
    return [e for e in evs if e.plane == plane and e.line == OPS_LINE]


def busy_s(evs: Sequence[Ev], lo: int, hi: int) -> Optional[float]:
    """Seconds in which an op ran, averaged over the chips in the trace;
    ``None`` when the trace holds no device."""
    planes = device_planes(evs)
    if not planes:
        return None
    return sum(union_ns(_clip(ops(evs, p), lo, hi)) for p in planes) / (
        len(planes) * 1e9)


def idle_share(evs: Sequence[Ev], lo: int, hi: int) -> Optional[float]:
    busy = busy_s(evs, lo, hi)
    if busy is None or hi <= lo:
        return None
    return 1.0 - busy / ((hi - lo) / 1e9)


def modules(evs: Sequence[Ev], pattern: str, lo: int, hi: int
            ) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name matches
    ``pattern`` and that ended inside the window, summed over chips."""
    rx = re.compile(pattern)
    hit = [e for e in evs if DEVICE_PLANE.match(e.plane)
           and e.line == MODULES_LINE and rx.search(e.name)
           and lo <= e.end <= hi]
    return sum(e.end - e.start for e in hit) / 1e9, len(hit)


def op_time(evs: Sequence[Ev], pattern: str, lo: int, hi: int
            ) -> Tuple[float, int]:
    """(seconds, events) of the device ops whose name matches
    ``pattern`` and that ended inside the window, summed over chips."""
    rx = re.compile(pattern)
    hit = [e for e in evs if DEVICE_PLANE.match(e.plane)
           and e.line == OPS_LINE and rx.search(short(e.name))
           and lo <= e.end <= hi]
    return sum(e.end - e.start for e in hit) / 1e9, len(hit)


def top_ops(evs: Sequence[Ev], lo: int, hi: int, k: int = 10
            ) -> List[list]:
    """The ``k`` device ops that took the most time in the window, as
    ``[name, seconds]``, averaged over chips; loop and branch ops, whose
    events span their bodies, are left out."""
    planes = device_planes(evs)
    tot: dict = {}
    for p in planes:
        for e in ops(evs, p):
            if e.end > lo and e.start < hi and not CONTAINER.match(e.name):
                key = short(e.name)
                tot[key] = tot.get(key, 0) + (min(e.end, hi)
                                              - max(e.start, lo))
    n = max(len(planes), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def idle_gaps(evs: Sequence[Ev], lo: int, hi: int, k: int = 10
              ) -> List[list]:
    """The ``k`` longest stretches in which the first chip ran no op, as
    ``[host activity, seconds]``.  The activity is the host event that
    overlapped the gap most, the benchmark's own window mark left out;
    ``"host idle"`` where none did."""
    planes = device_planes(evs)
    if not planes:
        return []
    gaps = gaps_ns(_clip(ops(evs, planes[0]), lo, hi), lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [e for e in evs if e.plane == HOST_PLANE and e.name != WINDOW]
    out = []
    for s, e in gaps:
        # most overlap first; on a tie the shorter, more specific event
        cands = [(min(h.end, e) - max(h.start, s), h.start - h.end, h.name)
                 for h in host]
        cands = [c for c in cands if c[0] > 0]
        out.append([max(cands)[2] if cands else "host idle", (e - s) / 1e9])
    return out
