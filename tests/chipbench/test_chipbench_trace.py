"""The trace reduction, on synthetic events and on a trace recorded on a
TPU v5e chip (``traces/``, committed beside this file)."""
from pathlib import Path

import pytest
from chipbench_toy import ROOT  # noqa: F401  (puts the checkout on sys.path)

from benchmarks.chip import trace as T

DEV, HOST = "/device:TPU:0", "/host:CPU"
HERE = Path(__file__).resolve().parent


def ev(name, s, e, plane=DEV, line=T.OPS_LINE):
    return T.Ev(plane, line, name, s, e)


def synthetic():
    # window [100, 200): ops cover [100,120) ∪ [130,150) (nested and
    # overlapping events count once) and [190,210) clipped to [190,200)
    return [
        ev(T.WINDOW, 100, 200, HOST, "main"),
        ev("%while.3 = (s32[]) while(...)", 100, 120),
        ev("%fusion.1 = f32[8] fusion(%while.3)", 105, 115),
        ev("%fusion.2 = f32[8] fusion(...)", 130, 140),
        ev("%vmap_jit_gain_reduce_kernel__.2 = (f32[8,128]) custom-call(...)",
           135, 150),
        ev("%fusion.2 = f32[8] fusion(...)", 190, 210),
        ev("%fusion.9 = f32[8] fusion(...)", 10, 20),      # outside
        ev("jit_train_step(123)", 100, 150, line=T.MODULES_LINE),
        ev("jit_train_step(123)", 190, 210, line=T.MODULES_LINE),
        ev("jit__normal(9)", 160, 170, line=T.MODULES_LINE),
        ev("PjitFunction(agent_batches)", 150, 185, HOST, "python"),
        ev("device_get", 152, 160, HOST, "python"),
    ]


def test_union_and_gaps():
    assert T.union_ns([(0, 10), (5, 20), (30, 40), (35, 36)]) == 30
    assert T.union_ns([]) == 0
    assert T.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30),
                                                              (40, 50)]


def test_window_busy_and_idle():
    evs = synthetic()
    lo, hi = T.window(evs)
    assert (lo, hi) == (100, 200)
    assert T.busy_s(evs, lo, hi) == pytest.approx(50e-9)
    assert T.idle_share(evs, lo, hi) == pytest.approx(0.5)


def test_modules_and_ops_by_name():
    evs = synthetic()
    secs, n = T.modules(evs, r"^jit_train_step\(", 100, 200)
    assert n == 1 and secs == pytest.approx(50e-9)  # the second ends late
    secs, n = T.op_time(evs, "gain_reduce_kernel", 100, 200)
    assert n == 1 and secs == pytest.approx(15e-9)
    # an op that merely reads the kernel's output is not the kernel
    assert T.op_time(evs, "while", 100, 200)[1] == 1


def test_breakdown():
    evs = synthetic()
    top = T.top_ops(evs, 100, 200)
    names = [n for n, _ in top]
    assert "%while.3" not in names  # a loop's event spans its body
    assert names[0] == "%fusion.2" and top[0][1] == pytest.approx(20e-9)
    gaps = T.idle_gaps(evs, 100, 200)
    # idle [150,190) is the longest gap, [120,130) the other; the host
    # was sampling in the first, nothing overlapped the second
    assert gaps[0][0] == "PjitFunction(agent_batches)"
    assert gaps[0][1] == pytest.approx(40e-9)
    assert gaps[1] == ["host idle", pytest.approx(10e-9)]


@pytest.mark.parametrize("cell,module", [
    ("fleet_m64_serve", r"^jit_train_step\("),
    ("smollm135m_gradnorm", r"^jit_train_step\("),
])
def test_recorded_chip_trace(cell, module):
    evs = T.load(HERE / "traces" / f"{cell}.xplane.pb.gz")
    lo, hi = T.window(evs)
    assert hi > lo
    assert T.device_planes(evs) == [DEV]
    busy = T.busy_s(evs, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    secs, steps = T.modules(evs, module, lo, hi)
    assert steps >= 1 and secs > 0
    assert T.top_ops(evs, lo, hi)
    assert T.idle_gaps(evs, lo, hi)
    if cell == "smollm135m_gradnorm":
        ksecs, calls = T.op_time(evs, "gain_reduce_kernel", lo, hi)
        assert calls == steps and 0 < ksecs < secs
