"""The program's instrumentation: named scopes on the train step's
stages, the serving loop's per-stage seconds in the rollup, and the
compile record."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.rollup import CommRollup
from repro.launch import compile_cache

# the stage scopes each path runs (no lossy channel on either: the
# channel scope is checked on a lossy fleet below)
STAGES = ("prologue", "probe", "trigger", "compress", "aggregate", "update")


def scopes(lowered) -> set:
    """The scope names on the lowered program's op locations
    (``jit(train_step)/.../<scope>/.../<primitive>``; a transform wraps
    the scopes it maps, as in ``vmap(prologue)``)."""
    text = lowered.as_text(debug_info=True)
    paths = set(re.findall(r'loc\("jit\(train_step\)/([^"]*)"', text))
    return {word for path in paths for part in path.split("/")[:-1]
            for word in re.findall(r"\w+", part)}


def fleet_lowered(net=None):
    from repro.launch.session import build_linreg_fleet_session

    sess = build_linreg_fleet_session(net=net, seed=3)
    batch = sess._batch_fn(jax.random.key(0))
    return sess._step.lower(sess.state, batch)


def test_fleet_step_carries_stage_scopes():
    # the m=64 adaptive tiered fleet: hybrid dispatch, bank epilogues
    lowered = fleet_lowered()
    assert set(STAGES) <= scopes(lowered)
    assert "channel" not in scopes(lowered)
    assert "func.func public @main" in lowered.as_text()


def test_lossy_fleet_step_carries_channel_scope():
    from repro.configs.paper_linreg import TIERED_M64_ADAPTIVE_LOSSY

    lowered = fleet_lowered(TIERED_M64_ADAPTIVE_LOSSY)
    assert set(STAGES) | {"channel"} <= scopes(lowered)


def test_fleet_step_module_keeps_its_name():
    # the benchmark finds the step's device time by this module name
    assert fleet_lowered().as_text().startswith("module @jit_train_step ")


def test_fleet_pull_spans_carry_one_buffer(tmp_path):
    """A traced run of a few rounds: each round's ``fleet.pull`` span
    carries its round and ``buffers=1``, the fleet's one f32 buffer."""
    from jax.profiler import ProfileData

    from repro.launch.session import build_linreg_fleet_session

    sess = build_linreg_fleet_session(seed=3)
    sess.run(1)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run(3)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pulls = [dict(e.stats) for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name == "fleet.pull"]
    assert sorted(p["round"] for p in pulls) == [1, 2, 3]
    assert all(p["buffers"] == 1 for p in pulls)


def test_lm_step_carries_stage_scopes():
    # the toy smollm step: homogeneous vmap path, the grad-norm trigger
    # through the gain_reduce kernel, int8 with error feedback
    from repro.configs import get_config, reduced
    from repro.configs.base import InputShape
    from repro.core.api import init_train_state
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.optim import optimizers as opt_lib

    mesh = make_host_mesh()
    shape = InputShape("toy", seq_len=16, global_batch=2, kind="train")
    plan = S.plan_run(reduced(get_config("smollm-135m")), shape, mesh,
                      comm="grad_norm(mu=1.0,kernel=true)|int8+ef",
                      optimizer="sgd", lr=0.05, agents=2)
    jitted, _, batch_abs, *_ = S.build_train_step(
        mesh, plan, compute_dtype="bfloat16")
    model = build(plan.cfg.replace(compute_dtype="bfloat16"))
    params, _ = model.init(jax.random.key(0), dtype=jnp.bfloat16)
    state = init_train_state(params, opt_lib.from_config(plan.train_cfg),
                             plan.train_cfg)
    assert state.ef_memory is not None
    lowered = jitted.lower(state, batch_abs)
    assert set(STAGES) <= scopes(lowered)
    # the kernel runs inside the probe scope and keeps its own name
    text = lowered.as_text(debug_info=True)
    assert "jit(train_step)/vmap(probe)/jit(gain_reduce_kernel)" in text


def _rollup():
    return CommRollup(tier_names=("a",), tier_index=(0, 0),
                      clock=iter(np.arange(0.0, 100.0, 0.5)).__next__)


def test_stage_seconds_exported_only_when_nonzero():
    roll = _rollup()
    roll.update({"num_tx": 1.0})
    roll.record_stage_seconds({})
    assert "stage_seconds" not in roll.snapshot()
    assert "fleet_stage_seconds_total" not in roll.to_prometheus()
    roll.record_stage_seconds({"sample": 0.25, "pull": 0.5})
    roll.record_stage_seconds({"sample": 0.25})
    snap = roll.snapshot()
    assert snap["stage_seconds"] == {"pull": 0.5, "sample": 0.5}
    assert "stage_seconds" not in snap["counters"]
    text = roll.to_prometheus()
    assert ("# TYPE fleet_stage_seconds_total counter\n"
            'fleet_stage_seconds_total{stage="pull"} 0.5\n'
            'fleet_stage_seconds_total{stage="sample"} 0.5\n') in text


def test_stage_seconds_survive_state_roundtrip():
    src = _rollup()
    src.update({"num_tx": 1.0})
    src.record_stage_seconds({"dispatch": 0.125, "rollup": 0.0625})
    dst = _rollup()
    dst.load_state(src.state_dict())
    assert dst.snapshot()["stage_seconds"] == {"dispatch": 0.125,
                                               "rollup": 0.0625}
    dst.record_stage_seconds({"dispatch": 0.125})
    assert dst.state_dict()["stage_seconds"]["dispatch"] == 0.25


def test_checkpoint_without_stage_seconds_loads_as_zeros():
    src = _rollup()
    src.update({"num_tx": 1.0})
    old = src.state_dict()
    del old["stage_seconds"]  # a checkpoint written before the key
    dst = _rollup()
    dst.load_state(old)
    assert dst.state_dict()["stage_seconds"] == {}
    assert "stage_seconds" not in dst.snapshot()


def test_session_rounds_fill_stage_seconds():
    from repro.launch.session import build_linreg_fleet_session

    sess = build_linreg_fleet_session(seed=1)
    assert sess.run(3) == 3
    stages = sess.rollup.snapshot()["stage_seconds"]
    assert set(stages) == {"sample", "dispatch", "wait", "pull", "rollup"}
    assert all(v > 0 for v in stages.values())


def test_compile_events_count_new_shapes_only():
    compile_cache.record_compiles()
    compile_cache.record_compiles()  # idempotent: one listener
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(7)
    f(x).block_until_ready()
    n = len(compile_cache.compile_events())
    f(x).block_until_ready()
    assert len(compile_cache.compile_events()) == n
    y = jnp.ones(11)
    before = len(compile_cache.compile_events())
    f(y).block_until_ready()
    new = compile_cache.compile_events()[before:]
    assert [e.name for e in new] == ["jit(<lambda>)"]
    assert new[0].seconds >= 0
    assert new[0].end <= time.perf_counter()


def test_enable_compile_cache_records_compiles(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-cache")
    assert compile_cache.enable_compile_cache() == "/nonexistent-cache"
    assert compile_cache._LISTENING


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
