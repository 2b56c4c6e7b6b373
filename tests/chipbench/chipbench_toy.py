"""Helpers the chip-benchmark tests share: the benchmark's cells at a
size the CPU test run can hold, the faults each cell can have, and a
timer for ``run_cell``."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

# the faults every cell of a runner kind can have, planted in the program
KIND_FAULTS = {
    "fleet_serve": {"half_batch", "state_unchanged", "answer_altered",
                    "mispriced_tier"},
    "lm_train": {"half_batch", "no_exchange", "loss_altered",
                 "state_unchanged"},
}
# an LM cell's trigger adds the fault of its own answer: the gain-reduce
# kernel's squared norm halved, or the lookahead probe's gain lost
TRIGGER_FAULTS = {"grad_norm": "gsq_halved",
                  "gain_lookahead": "probe_gain_zeroed",
                  "budget_dual": "probe_gain_zeroed",
                  "budget_window": "probe_gain_zeroed"}


def _files(workload: str) -> tuple:
    """The configuration and the mix ``workload`` names, as data."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    mix = harness.HERE / "traffic" / f"{cell['traffic']}.json"
    return (json.loads((ROOT / conf["file"]).read_text()),
            json.loads(mix.read_text()))


def kind_of(workload: str) -> str:
    return _files(workload)[0]["kind"]


def faults(workload: str) -> set:
    """The faults ``workload`` can have: its kind's, and for an LM cell
    its trigger's, read from its mix's ``comm``."""
    cfg, mix = _files(workload)
    out = set(KIND_FAULTS[cfg["kind"]])
    if cfg["kind"] == "lm_train":
        trig = mix["comm"].split("(")[0].split("|")[0].strip()
        out |= {TRIGGER_FAULTS[trig]} if trig in TRIGGER_FAULTS else set()
    return out


def cells_of(kind: str) -> list:
    return [w for w in CELLS if kind_of(w) == kind]


def toy_cut(cell):
    """``cell`` cut to its configuration's ``toy`` size, where it has one
    (the fleet runs at its own size, which is small)."""
    toy = cell.cfg.get("toy")
    if toy:
        cfg = copy.deepcopy(cell.cfg)
        cfg.update(toy["sizes"])
        cfg["program"] = dict(cfg["program"], **toy["program"])
        cell.cfg = cfg
        cell.mix = dict(cell.mix, tokens=dict(cell.mix["tokens"],
                                              pool=toy["pool"]))
    return cell


def toy_cell(workload: str):
    """``workload`` resolved by name and cut to its toy size."""
    return toy_cut(harness.resolve(workload))


def run(cell, seed: int = 2**31 + 17, seconds: float = 1.0,
        trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU, past the harness's look for a chip."""
    return harness.run_cell(cell.name, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            cell=cell)
