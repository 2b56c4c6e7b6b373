"""Crash-safe checkpointing (ISSUE-10): atomic saves, corruption and
template validation, full-session TrainState round-trips over every
slot layout, FleetSession resume bit-equality, rollup persistence, the
watchdog, and the fault-injection schedules.

The load-bearing invariant: a killed session relaunched from its latest
complete checkpoint continues the EXACT trajectory the uninterrupted
run would have produced — bitwise params, bitwise net_state (rows and
(rows, line) payload buffers alike), strictly monotone rollup counters.
"""
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.checkpoint import CheckpointCorruptionError, CheckpointError
from repro.comm.rollup import CommRollup
from repro.configs.base import TrainConfig
from repro.core.api import (
    StepOptions,
    init_train_state,
    make_triggered_train_step,
)
from repro.launch import compile_cache
from repro.launch.faults import AgentFault, FaultInjector, fault_mask
from repro.launch.session import FleetSession, SessionOptions, Watchdog
from repro.optim import optimizers as opt_lib

M, N = 4, 6

# one spec per TrainState slot layout: EF only, controller rows, bare
# net rows, the delay (rows, line) tuple, and the retx (rows, line)
# tuple — the checkpoint must round-trip every shape the state can take
SLOT_SPECS = {
    "ef": "always|int8+ef",
    "ctrl": "budget_dual(rate=0.5)|int8+ef",
    "net_rows": "always|int8+ef @ bernoulli(p=0.3,seed=1)",
    "net_delay_tuple": "always|int8+ef @ delay(max_lag=3,seed=1)",
    "net_retx_tuple": "always|int8+ef @ retx(k=2,p=0.3,seed=1)",
}

# a mid-run join/leave schedule: agent 1 joins at step 2, agent 2
# leaves at step 4 — the churn masks key off TrainState.step, so a
# resumed session must replay them exactly
CHURN = ((0, 10_000), (2, 10_000), (0, 4), (0, 10_000))


def _loss_fn(params, batch):
    xs, ys = batch
    r = xs @ params["w"] - ys
    return 0.5 * jnp.mean(r * r)


def _batch(key):
    kx, ky = jax.random.split(key)
    xs = jax.random.normal(kx, (M, 8, N))
    ys = xs @ jnp.arange(1.0, N + 1.0) + 0.01 * jax.random.normal(ky, (M, 8))
    return xs, ys


def _make(spec, churn=None):
    cfg = TrainConfig(lr=0.1, optimizer="sgd", num_agents=M, comm=spec)
    opt = opt_lib.from_config(cfg)
    step = make_triggered_train_step(
        _loss_fn, opt, cfg,
        options=StepOptions(agent_metrics=True, churn=churn))
    return jax.jit(step), init_train_state({"w": jnp.zeros(N)}, opt, cfg)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _zeros_like_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(jnp.asarray(x)), tree)


@pytest.mark.parametrize("slot", sorted(SLOT_SPECS))
def test_trainstate_roundtrip_bitwise_continuation(tmp_path, slot):
    """Save mid-run, restore into a zeros template, continue BOTH —
    the restored trajectory must be bitwise the original's."""
    step, state = _make(SLOT_SPECS[slot])
    key = jax.random.key(0)
    for k in range(4):
        state, _ = step(state, _batch(jax.random.fold_in(key, k)))
    ckpt.save(str(tmp_path), 4, state)
    restored = ckpt.restore(str(tmp_path), _zeros_like_tree(state))
    assert _leaves_equal(state, restored)
    for k in range(4, 7):
        b = _batch(jax.random.fold_in(key, k))
        state, _ = step(state, b)
        restored, _ = step(restored, b)
    assert _leaves_equal(state, restored)


def test_churned_session_roundtrip_bitwise(tmp_path):
    """Churn masks key off TrainState.step — a restored state must
    replay joins/leaves in the same rounds as the original."""
    step, state = _make(SLOT_SPECS["net_retx_tuple"], churn=CHURN)
    key = jax.random.key(1)
    for k in range(3):
        state, _ = step(state, _batch(jax.random.fold_in(key, k)))
    ckpt.save(str(tmp_path), 3, state)
    restored = ckpt.restore(str(tmp_path), _zeros_like_tree(state))
    for k in range(3, 6):  # crosses agent 2's leave at step 4
        b = _batch(jax.random.fold_in(key, k))
        state, ma = step(state, b)
        restored, mb = step(restored, b)
        assert _leaves_equal(ma, mb)
    assert _leaves_equal(state, restored)


def test_atomic_save_ignores_tmp_orphans(tmp_path):
    ckpt.save(str(tmp_path), 5, {"w": jnp.ones(3)})
    # a crashed save leaves only a .tmp sibling — never a visible step
    orphan = tmp_path / "step_00000009.tmp"
    orphan.mkdir()
    (orphan / "arrays.npz").write_bytes(b"half-written")
    assert ckpt.latest_step(str(tmp_path)) == 5
    # and a re-save over a crashed .tmp of the SAME step succeeds
    (tmp_path / "step_00000005.tmp").mkdir()
    ckpt.save(str(tmp_path), 5, {"w": jnp.full(3, 2.0)})
    out = ckpt.restore(str(tmp_path), {"w": jnp.zeros(3)})
    assert np.array_equal(np.asarray(out["w"]), np.full(3, 2.0))


def test_corruption_detected(tmp_path):
    path = ckpt.save(str(tmp_path), 1, {"w": jnp.ones(8)})
    npz = os.path.join(path, "arrays.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[-1] ^= 0xFF
    open(npz, "wb").write(bytes(blob))
    with pytest.raises(CheckpointCorruptionError, match="checksum"):
        ckpt.restore(str(tmp_path), {"w": jnp.zeros(8)})


def test_leaf_count_mismatch_is_loud(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.ones(3), "b": jnp.ones(2)})
    with pytest.raises(CheckpointError, match="leaves"):
        ckpt.restore(str(tmp_path), {"w": jnp.zeros(3)})


def test_shape_mismatch_names_leaf(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.ones(3), "b": jnp.ones((2, 2))})
    with pytest.raises(CheckpointError) as e:
        ckpt.restore(str(tmp_path),
                     {"a": jnp.zeros(3), "b": jnp.zeros((2, 3))})
    assert "'b'" in str(e.value) and "shape" in str(e.value)


def test_dtype_mismatch_names_leaf_no_silent_cast(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.ones(3, jnp.float32)})
    with pytest.raises(CheckpointError) as e:
        ckpt.restore(str(tmp_path), {"a": jnp.zeros(3, jnp.int32)})
    assert "'a'" in str(e.value) and "dtype" in str(e.value)


def test_extra_metadata_roundtrip(tmp_path):
    extra = {"round": 17, "rollup": {"rounds": 17, "counters": {}}}
    ckpt.save(str(tmp_path), 17, {"w": jnp.ones(2)}, extra=extra)
    manifest = ckpt.read_manifest(str(tmp_path))
    assert manifest["step"] == 17
    assert manifest["extra"] == json.loads(json.dumps(extra))


# ----------------------------------------------------------------------
# FleetSession resume
# ----------------------------------------------------------------------


def _session(spec, options=None, on_round=None, batch_fn=_batch, key=7):
    cfg = TrainConfig(lr=0.1, optimizer="sgd", num_agents=M, comm=spec)
    opt = opt_lib.from_config(cfg)
    step = make_triggered_train_step(
        _loss_fn, opt, cfg, options=StepOptions(agent_metrics=True))
    state = init_train_state({"w": jnp.zeros(N)}, opt, cfg)
    return FleetSession(step, state, batch_fn, CommRollup(),
                        key=jax.random.key(key), options=options,
                        on_round=on_round)


def test_session_kill_resume_bit_equal(tmp_path):
    """N rounds + checkpoint + FRESH session auto-resume + N rounds ==
    2N uninterrupted rounds, to the bit, with monotone counters."""
    spec = SLOT_SPECS["net_retx_tuple"]
    opts = SessionOptions(ckpt_dir=str(tmp_path), ckpt_every=3)
    a = _session(spec, options=opts)
    assert a.run(rounds=6) == 6
    before = a.rollup.snapshot()

    b = _session(spec, options=opts)  # picks up step_00000006
    assert b.round_index == 6
    assert b.rollup.rounds == 6
    assert b.rollup.snapshot()["restarts"] == 1
    b.run(rounds=6)
    after = b.rollup.snapshot()

    ref = _session(spec)
    ref.run(rounds=12)
    assert _leaves_equal(b.state, ref.state)
    assert after["rounds"] == 12
    assert all(after["counters"][k] >= before["counters"][k]
               for k in before["counters"])
    # the untouched reference exports no restart/degradation fields
    assert "restarts" not in ref.rollup.snapshot()


def _record_batches(sess, batches):
    """Append ``(round, batch)`` for every batch the session's round
    sampler hands its step."""
    sample = sess._sample

    def recorded(key, k):
        batch = sample(key, k)
        batches.append((k, batch))
        return batch

    sess._sample = recorded


@pytest.mark.parametrize("faults", [(), (AgentFault(agent=2, start=3),)],
                         ids=["plain", "agent_fault"])
def test_session_compiles_sampler_once_across_resume(tmp_path, faults):
    """Each session compiles its round sampler once, with the round
    index traced and the base key an argument: over 5 rounds, a
    checkpoint, a resume and 3 more rounds, round k's batch is
    ``jax.jit(batch_fn)`` on ``fold_in(key, k)``, bit for bit."""
    batch_fn = FaultInjector(_batch, faults, M) if faults else _batch
    compile_cache.record_compiles()
    opts = SessionOptions(ckpt_dir=str(tmp_path))
    batches = []
    # the resumed session's own key (99) must give way to the restored 7
    for key, rounds in ((7, 5), (99, 3)):
        sess = _session(SLOT_SPECS["ef"], options=opts, batch_fn=batch_fn,
                        key=key)
        _record_batches(sess, batches)
        before = len(compile_cache.compile_events())
        sess.run(rounds)
        sess.checkpoint()
        names = [e.name for e in compile_cache.compile_events()[before:]]
        assert names.count("jit(sample_round)") == 1, names

    assert [k for k, _ in batches] == list(range(8))
    ref = jax.jit(batch_fn)
    for k, got in batches:
        round_key = jax.random.fold_in(jax.random.key(7), k)
        want = ref(round_key, k) if faults else ref(round_key)
        assert _leaves_equal(got, want), k
    if faults:  # agent 2 is down from round 3 on
        assert np.abs(np.asarray(batches[2][1][0][2])).max() > 0
        assert np.abs(np.asarray(batches[6][1][0][2])).max() == 0


def test_session_no_resume_starts_fresh(tmp_path):
    spec = SLOT_SPECS["ef"]
    opts = SessionOptions(ckpt_dir=str(tmp_path), ckpt_every=2)
    a = _session(spec, options=opts)
    a.run(rounds=4)
    fresh = _session(spec, options=SessionOptions(
        ckpt_dir=str(tmp_path), resume=False))
    assert fresh.round_index == 0
    assert fresh.rollup.rounds == 0


def test_session_resume_rejects_slot_mismatch(tmp_path):
    """A checkpoint from a different slot layout must fail loudly, not
    restore garbage."""
    opts = SessionOptions(ckpt_dir=str(tmp_path), ckpt_every=2)
    a = _session(SLOT_SPECS["net_delay_tuple"], options=opts)
    a.run(rounds=2)
    with pytest.raises(CheckpointError):
        _session(SLOT_SPECS["ef"], options=opts)


def _mixed_dtype_session(on_round=None):
    """A toy step whose metrics hold an int32 leaf and float32 leaves."""

    def train_step(state, batch):
        w = state["w"] + 0.1 * batch
        return {"w": w}, {"loss": jnp.sum(w * w), "agent_w": w,
                          "num_pos": jnp.sum(w > 0).astype(jnp.int32)}

    return FleetSession(train_step, {"w": jnp.zeros(M)},
                        lambda key: jax.random.normal(key, (M,)),
                        CommRollup(), key=jax.random.key(5),
                        on_round=on_round)


def _linreg_fleet_session(on_round=None):
    from repro.launch.session import build_linreg_fleet_session

    return build_linreg_fleet_session(seed=2, on_round=on_round)


PACKED_CASES = {
    "linreg_m64": (_linreg_fleet_session, 1),
    "net_retx_tuple": (lambda on_round=None: _session(
        SLOT_SPECS["net_retx_tuple"], on_round=on_round), 1),
    "mixed_dtype": (_mixed_dtype_session, 2),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_session_packed_metrics_equal_device_get(case):
    """Each round's metrics come home as one buffer per dtype, and the
    dict the session hands on equals ``jax.device_get`` of the plain
    jitted step's metrics on the same state and batches: same keys,
    shapes and dtypes, equal bit for bit."""
    build, n_buffers = PACKED_CASES[case]
    got = []
    sess = build(on_round=lambda k, m: got.append(m))
    plain = jax.jit(inspect.unwrap(sess._step))
    state = jax.tree_util.tree_map(jnp.copy, sess.state)
    _, buffers = sess._step(jax.tree_util.tree_map(jnp.copy, state),
                            sess._sample(sess._key, 0))
    assert len(buffers) == n_buffers
    assert sess.run(4) == 4 and len(got) == 4
    for k, m in enumerate(got):
        state, want = plain(state, sess._sample(sess._key, k))
        want = jax.device_get(want)
        assert jax.tree_util.tree_structure(m) == (
            jax.tree_util.tree_structure(want))
        for a, b in zip(jax.tree_util.tree_leaves(m),
                        jax.tree_util.tree_leaves(want), strict=True):
            assert isinstance(a, np.ndarray)
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
            assert a.tobytes() == b.tobytes(), k
    assert _leaves_equal(sess.state, state)


def test_rollup_state_roundtrip():
    budgets = (10.0, 10.0, float("inf"), float("inf"))
    src = CommRollup(tier_names=("a", "b"), tier_index=(0, 0, 1, 1),
                     budgets=budgets)
    for k in range(5):
        src.update({"loss": 1.0 / (k + 1), "num_tx": 2.0,
                    "wire_bytes": 64.0, "comm_rate": 0.5,
                    "agent_bytes": np.full(4, 16.0)})
    src.record_degradation("stall")
    dst = CommRollup(tier_names=("a", "b"), tier_index=(0, 0, 1, 1),
                     budgets=budgets)
    dst.load_state(src.state_dict())
    dst.record_restart()
    sa, sb = src.snapshot(), dst.snapshot()
    assert sb["rounds"] == sa["rounds"] == 5
    assert sb["counters"] == sa["counters"]
    assert sb["degradation_events"] == {"stall": 1}
    assert sb["restarts"] == 1
    assert "restarts" not in sa


def test_rollup_load_state_rejects_tier_mismatch():
    src = CommRollup(tier_names=("a",), tier_index=(0, 0),
                     budgets=(10.0, 10.0))
    src.update({"loss": 1.0, "agent_bytes": np.full(2, 1.0)})
    dst = CommRollup(tier_names=("a", "b"), tier_index=(0, 1),
                     budgets=(10.0, 20.0))
    with pytest.raises(ValueError, match="scenario mismatch"):
        dst.load_state(src.state_dict())


# ----------------------------------------------------------------------
# watchdog + fault schedules
# ----------------------------------------------------------------------


def test_watchdog_one_event_per_episode():
    roll = CommRollup()
    wd = Watchdog(roll, timeout=1.0, clock=lambda: 0.0)
    assert not wd.check(now=0.5)
    assert wd.check(now=1.5)        # stall flagged once...
    assert not wd.check(now=9.0)    # ...not re-flagged while ongoing
    wd.beat()
    assert wd.check(now=99.0)       # re-armed by the beat
    assert roll.snapshot()["degradation_events"] == {"stall": 2}


def test_watchdog_in_session_flags_stall():
    import time as _t

    slept = []

    def stall(k, metrics):
        if k == 1:
            _t.sleep(0.4)
            slept.append(k)

    s = _session(SLOT_SPECS["ef"], on_round=stall,
                 options=SessionOptions(watchdog_timeout=0.1))
    s.run(rounds=3)
    assert slept == [1]
    assert s.rollup.snapshot()["degradation_events"]["stall"] >= 1


def test_agent_fault_schedules():
    crash = AgentFault(agent=0, start=3)
    assert [crash.down(k) for k in (0, 2, 3, 99)] == [
        False, False, True, True]
    outage = AgentFault(agent=1, start=2, duration=2)
    assert [outage.down(k) for k in (1, 2, 3, 4)] == [
        False, True, True, False]
    flap = AgentFault(agent=2, start=4, duration=1, period=3)
    assert [flap.down(k) for k in (3, 4, 5, 6, 7, 8)] == [
        False, True, False, False, True, False]
    mask = fault_mask([crash, flap], 4, 4)
    assert mask.tolist() == [0.0, 1.0, 0.0, 1.0]


def test_fault_injector_zeroes_downed_rows():
    inj = FaultInjector(_batch, [AgentFault(agent=2, start=1)], M)
    xs0, _ = inj(jax.random.key(0), 0)        # round 0: everyone up
    assert np.abs(np.asarray(xs0[2])).max() > 0
    xs1, ys1 = inj(jax.random.key(1), 1)      # round 1: agent 2 down
    assert np.abs(np.asarray(xs1[2])).max() == 0
    assert np.abs(np.asarray(ys1[2])).max() == 0
    assert np.abs(np.asarray(xs1[1])).max() > 0
