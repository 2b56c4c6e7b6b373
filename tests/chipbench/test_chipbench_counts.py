"""The benchmark's FLOP, byte and peak tables against hand counts."""
import json

import pytest
from chipbench_toy import ROOT

from benchmarks.chip import counts, harness, peaks

SMOLLM = ROOT / "benchmarks/chip/configs/smollm-135m.json"
# forward FLOPs per token of smollm-135m at 1024 tokens, by hand: two per
# matmul weight, plus QKᵀ and PV at 2 FLOPs per multiply-add each over 9
# heads of 64, 1024 keys and 30 layers
MATMUL = 30 * (576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536) + 49152 * 576
FWD = 2 * MATMUL + 2 * 2 * 1024 * 9 * 64 * 30


@pytest.fixture(scope="module")
def smollm():
    return json.loads(SMOLLM.read_text())


@pytest.fixture(scope="module")
def smollm_ref():
    # the counts the runner reads, through the configuration's reference
    return harness.reference(SMOLLM)


def test_smollm_param_count_by_hand(smollm, smollm_ref):
    # per layer: q 576·576, k and v 576·192 each, o 576·576, three
    # 576×1536 MLP matrices, two norms of 576; then the tied 49152×576
    # table and the final norm
    layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536 + 2 * 576
    assert counts.llama_param_count(smollm) == 30 * layer + 49152 * 576 + 576
    assert counts.llama_param_count(smollm) == 134_515_008
    assert smollm_ref.param_count(smollm) == 134_515_008


def test_smollm_train_flops_by_hand(smollm, smollm_ref):
    assert counts.llama_matmul_params(smollm) == MATMUL == 134_479_872
    assert counts.llama_forward_flops_per_token(smollm, 1024) == FWD
    assert counts.llama_train_flops_per_token(smollm, 1024) == 3 * FWD
    # a lookahead probe adds one forward pass
    assert counts.llama_train_flops_per_token(smollm, 1024, 1) == 4 * FWD
    assert smollm_ref.train_flops_per_token(smollm, 1024, 0) == 3 * FWD
    assert smollm_ref.train_flops_per_token(smollm, 1024, 1) == 4 * FWD
    assert 1.0e9 < 3 * FWD < 1.05e9


@pytest.mark.parametrize("workload,forwards", [("smollm135m_gradnorm", 3),
                                               ("smollm135m_budget", 4)])
def test_smollm_cells_read_the_hand_counts(workload, forwards):
    # what the runner reports per token in each cell: forward and
    # backward, and the budget controller's lookahead probe forward
    cell = harness.resolve(workload)
    assert cell.kind.flops_per_token(cell) == forwards * FWD
    assert cell.ref.param_count(cell.cfg) == 134_515_008


def test_gain_reduce_bytes():
    # two f32 inputs per agent, each element read once
    assert counts.gain_reduce_bytes(134_515_008, 2) == 2 * 4 * 134_515_008 * 2


def test_peak_table_refuses_unknown_device():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_peak_table_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12
    assert p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source
