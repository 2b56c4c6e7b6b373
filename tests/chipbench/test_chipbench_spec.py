"""BENCHMARK.json resolves, by name, to files of the benchmark's own."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from chipbench_toy import ROOT

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_every_cell_resolves_by_name(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], bench)
        assert (harness.HERE / "kinds" / f"{cell.cfg['kind']}.py").is_file()
        assert hasattr(cell.kind, "run") and hasattr(cell.kind, "controls")
        assert hasattr(cell.ref, "__doc__") and cell.limits
        names = [m["name"] for m in cell.end_to_end + cell.per_layer]
        for name in names:
            assert hasattr(harness.metric_reader(name), "read"), name
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_names_units_and_bounds(bench):
    assert bench["command"][1].startswith(bench["paths"][0])
    metrics = bench["end_to_end"] + bench["per_layer"]
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_limits_name_only_numbers_the_runner_compares(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], bench)
        assert all(v > 0 for v in cell.limits.values())


def test_refuses_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fleet_m64_serve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_refuses_without_the_program(tmp_path, bench):
    # a directory holding only BENCHMARK.json and the benchmark's files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         "fleet_m64_serve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_traffic_mixes_are_data(bench):
    for w in bench["workloads"]:
        path = ROOT / "benchmarks/chip/traffic" / f"{w['traffic']}.json"
        mix = json.loads(path.read_text())
        assert mix["loop"] == "closed"
