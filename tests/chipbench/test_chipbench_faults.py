"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and the control fails a limit.

The harness runs as the benchmark runs it, past its look for a chip; the
program is broken by patching what the timed path calls.
"""
import jax.numpy as jnp
import pytest
from chipbench_toy import CELLS, cells_of, faults, run, toy_cell

import repro.comm.triggers as triggers
import repro.core.api as api
import repro.core.regression as regression
import repro.launch.steps as steps


def _wrap_step(monkeypatch, module, wrap):
    """Patch ``module.make_triggered_train_step`` to build ``wrap(step)``."""
    real = module.make_triggered_train_step

    def make(loss_fn, *a, **k):
        return wrap(real(loss_fn, *a, **k))

    monkeypatch.setattr(module, "make_triggered_train_step", make)


def _state_unchanged(step):
    return lambda state, batch, *a: (state, step(state, batch, *a)[1])


# -- the served fleet -----------------------------------------------------


def _fleet_half_batch(monkeypatch):
    real = regression.agent_batches

    def half(problem, key):
        xs, ys = real(problem, key)
        n = xs.shape[1] // 2
        return xs[:, :n], ys[:, :n]

    monkeypatch.setattr(regression, "agent_batches", half)


def _fleet_answer_altered(monkeypatch):
    # agent 8's transmit decision is reported flipped where it is made
    def wrap(step):
        def altered(state, batch, *a):
            state, m = step(state, batch, *a)
            m = dict(m, agent_tx=m["agent_tx"].at[8].set(1.0 - m["agent_tx"][8]))
            return state, m
        return altered

    _wrap_step(monkeypatch, api, wrap)


def _fleet_mispriced_tier(monkeypatch):
    # the sensor tier's (agents 48-63) wire bytes priced at a fifth
    real = api.per_agent_wire_bytes

    def priced(*a, **k):
        return real(*a, **k).at[48:].multiply(0.2)

    monkeypatch.setattr(api, "per_agent_wire_bytes", priced)


FLEET_FAULTS = {
    "state_unchanged": lambda mp: _wrap_step(mp, api, _state_unchanged),
    "half_batch": _fleet_half_batch,
    "answer_altered": _fleet_answer_altered,
    "mispriced_tier": _fleet_mispriced_tier,
}


@pytest.mark.parametrize("fault", sorted(FLEET_FAULTS))
def test_fleet_fault_is_not_correct(monkeypatch, fault):
    FLEET_FAULTS[fault](monkeypatch)
    workloads = cells_of("fleet_serve")
    assert workloads
    for workload in workloads:
        res = run(toy_cell(workload), seconds=1.0)
        assert res["correct"] is False, (workload, res["checks"])


# -- language-model training ----------------------------------------------


def _lm_half_batch(monkeypatch):
    real = steps.make_triggered_train_step

    def make(loss_fn, *a, **k):
        def half(params, batch):
            s = batch["tokens"].shape[-1] // 2
            return loss_fn(params, {k_: v[..., :s] for k_, v in batch.items()})
        return real(half, *a, **k)

    monkeypatch.setattr(steps, "make_triggered_train_step", make)


def _lm_no_exchange(monkeypatch):
    # the aggregate takes agent 0's update alone
    real = api.masked_mean

    def solo(grads, alphas):
        return real(grads, alphas * (jnp.arange(alphas.shape[0]) == 0))

    monkeypatch.setattr(api, "masked_mean", solo)


def _lm_loss_altered(monkeypatch):
    def wrap(step):
        def altered(state, batch, *a):
            state, m = step(state, batch, *a)
            return state, dict(m, loss=m["loss"] * 1.01)
        return altered

    _wrap_step(monkeypatch, steps, wrap)


def _lm_gsq_halved(monkeypatch):
    # the gain-reduce kernel returns half the squared norm
    real = triggers._norm_sq
    monkeypatch.setattr(triggers, "_norm_sq",
                        lambda grad, use_kernel: 0.5 * real(grad, use_kernel))


def _lm_probe_gain_zeroed(monkeypatch):
    # the lookahead probe's gain comes back 0
    def gain_fn(ctx, who):
        return lambda params, grad, batch, local_loss: 0.0 * local_loss

    monkeypatch.setattr(triggers, "_lookahead_gain_fn", gain_fn)


LM_FAULTS = {
    "state_unchanged": lambda mp: _wrap_step(mp, steps, _state_unchanged),
    "half_batch": _lm_half_batch,
    "no_exchange": _lm_no_exchange,
    "loss_altered": _lm_loss_altered,
    "gsq_halved": _lm_gsq_halved,
    "probe_gain_zeroed": _lm_probe_gain_zeroed,
}
# each LM cell with every fault it can have: the general ones, and its
# trigger's own, planted in the cell whose trigger it breaks
LM_CASES = [(w, f) for w in cells_of("lm_train") for f in sorted(faults(w))]


@pytest.mark.parametrize("workload,fault", LM_CASES)
def test_lm_fault_is_not_correct(monkeypatch, workload, fault):
    LM_FAULTS[fault](monkeypatch)
    res = run(toy_cell(workload), seconds=0.5)
    assert res["correct"] is False, res["checks"]


# -- the control: the reference one precision below the configuration ----


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    cell = toy_cell(workload)
    side, checks = next(cell.kind.controls(cell, 3, 2.0))
    assert side == "control"
    assert any(checks[k] > limit for k, limit in cell.limits.items()), checks


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_readers_cover_the_faults(workload):
    # each fault the chip calibration reads fails one of the cell's limits
    cell = toy_cell(workload)
    read = dict(cell.kind.controls(cell, 3, 1.0))
    assert list(read)[0] == "control"
    planted = {s.split(":")[1]: c for s, c in read.items() if ":" in s}
    assert set(planted) >= faults(workload)
    for name, checks in planted.items():
        assert not all(checks[k] <= lim for k, lim in cell.limits.items()), \
            (name, checks)
