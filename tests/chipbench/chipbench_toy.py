"""Helpers the chip-benchmark tests share: the benchmark's cells at a
size the CPU test run can hold, and a timer for ``run_cell``."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402

# the smoke-test cut the repository applies to smollm-135m
# (repro.configs.reduced): 2 layers, d_model 256, 4 heads over 2 KV
# heads of 64, d_ff 512, vocab 512; sequences of 32 tokens
TOY_LM = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=64,
              vocab_size=512, seq_len=32)


def toy_cell(workload: str):
    """``workload`` resolved by name; a language-model cell is cut to the
    toy size (the fleet runs at its own size, which is small)."""
    cell = harness.resolve(workload)
    if cell.cfg["kind"] == "lm_train":
        cfg = copy.deepcopy(cell.cfg)
        cfg.update(TOY_LM)
        cfg["program"] = dict(cfg["program"], reduced=True)
        cell.cfg = cfg
        cell.mix = dict(cell.mix, tokens={"dist": "uniform", "pool": 8})
    return cell


def run(cell, seed: int = 2**31 + 17, seconds: float = 1.0,
        trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU, past the harness's look for a chip."""
    return harness.run_cell(cell.name, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            cell=cell)
