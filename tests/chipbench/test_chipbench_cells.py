"""Each cell of ``BENCHMARK.json``, its run path end to end on the CPU,
called as a function: a language-model cell at its configuration's toy
size, the fleet at its own size."""
import pytest
from chipbench_toy import CELLS, run, toy_cell


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    cell = toy_cell(workload)
    res = run(cell, seconds=1.0)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.limits)
    names = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_no_device_metric_off_the_chip():
    # a CPU trace holds no TPU plane: every device reader finds nothing
    # to read and the line leaves those metrics out
    res = run(toy_cell("fleet_m64_serve"), seconds=0.5, trace=True)
    assert res["correct"] is True
    assert res["metrics"] == {}
    assert res["device"]["busy_s"] is None
    assert res["device"]["window_s"] > 0
