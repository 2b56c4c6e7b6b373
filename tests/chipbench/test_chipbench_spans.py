"""The readers of the serving loop's stage spans and of the in-window
compile count: on synthetic events, and on a toy-size traced fleet run
on the CPU."""
import collections
import time
from types import SimpleNamespace

import pytest
from chipbench_toy import toy_cell

from benchmarks.chip import harness
from benchmarks.chip import trace as T

DEV, HOST = "/device:TPU:0", "/host:CPU"
STAGES = ("sample", "dispatch", "wait", "pull", "rollup")


def ctx_of(evs, out=None):
    lo, hi = T.window(evs)
    return SimpleNamespace(trace=SimpleNamespace(evs=evs, lo=lo, hi=hi),
                           out=out or {})


def span_events(stage, device=True):
    # window [1000, 2000) ns; spans of 100 and 300 ns end inside it, one
    # ends after it and one before it, one of another stage is inside
    evs = [
        T.Ev(HOST, "main", T.WINDOW, 1000, 2000),
        T.Ev(HOST, "python", f"fleet.{stage}", 1100, 1200),
        T.Ev(HOST, "python", f"fleet.{stage}#round=7#", 1500, 1800),
        T.Ev(HOST, "python", f"fleet.{stage}", 1900, 2100),
        T.Ev(HOST, "python", f"fleet.{stage}", 800, 900),
        T.Ev(HOST, "python", "fleet.checkpoint", 1200, 1900),
        T.Ev(HOST, "python", f"fleet.{stage}_x", 1200, 1900),
    ]
    if device:
        evs.append(T.Ev(DEV, T.OPS_LINE, "%fusion.1 = f32[8] fusion()",
                        1300, 1400))
    return evs


@pytest.mark.parametrize("stage", STAGES)
def test_span_reader_means_spans_ended_in_the_window(stage):
    reader = harness.metric_reader(f"{stage}_ms.serve")
    assert reader.read(ctx_of(span_events(stage))) == pytest.approx(2e-4)


@pytest.mark.parametrize("stage", STAGES)
def test_span_reader_reads_nothing_without_a_device(stage):
    reader = harness.metric_reader(f"{stage}_ms.serve")
    assert reader.read(ctx_of(span_events(stage, device=False))) is None


def test_span_reader_reads_nothing_without_the_span():
    evs = [e for e in span_events("sample") if "sample" not in e.name]
    assert harness.metric_reader("sample_ms.serve").read(ctx_of(evs)) is None


@pytest.mark.parametrize("name", ["compiles.serve", "compiles.train"])
def test_compile_reader_counts_the_window_only(name, monkeypatch):
    from repro.launch import compile_cache

    ev = compile_cache.CompileEvent
    stamps = [9.99, 10.0, 10.5, 12.0, 12.01, 30.0]
    monkeypatch.setattr(compile_cache, "compile_events",
                        lambda: [ev(t, 0.1, "jit(f)") for t in stamps])
    reader = harness.metric_reader(name)
    out = {"t0": 10.0, "window_s": 2.0}
    assert reader.read(ctx_of(span_events("pull"), out)) == 3
    monkeypatch.setattr(compile_cache, "compile_events", lambda: [])
    assert reader.read(ctx_of(span_events("pull"), out)) == 0
    no_dev = ctx_of(span_events("pull", device=False), out)
    assert reader.read(no_dev) is None


def test_toy_traced_fleet_run_has_one_span_per_stage_and_round(tmp_path):
    cell = toy_cell("fleet_m64_serve")
    res = harness.run_cell(cell.name, 2**31 + 29, 0.5, True,
                           t_start=time.perf_counter(), require_tpu=False,
                           cell=cell, trace_dir=str(tmp_path))
    assert res["correct"] is True
    assert res["metrics"] == {}  # no TPU plane: nothing to read
    path = T.find_xplane(tmp_path)
    evs = T.load(path)
    count = collections.Counter(e.name for e in evs
                                if e.plane == HOST
                                and e.name.startswith("fleet."))
    rounds = count["fleet.dispatch"]
    assert rounds >= res["attempted"] > 0
    # each round samples the next; the first round's sample comes first
    assert count == {"fleet.sample": rounds + 1, "fleet.dispatch": rounds,
                     "fleet.wait": rounds, "fleet.pull": rounds,
                     "fleet.rollup": rounds}
    # the spans of one round share its index
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ids = collections.defaultdict(list)
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fleet."):
                    ids[e.name].append(dict(e.stats)["round"])
    first = min(ids["fleet.dispatch"])
    want = list(range(first, first + rounds))
    for stage in STAGES[1:]:
        assert sorted(ids[f"fleet.{stage}"]) == want
    assert sorted(ids["fleet.sample"]) == want + [first + rounds]
