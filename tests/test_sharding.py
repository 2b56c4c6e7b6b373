"""Sharding-rule unit + property tests (1-device mesh semantics are
exercised here; the 512-device meshes only exist inside the dry-run)."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.rules import (
    agent_axis_names,
    agent_pspec,
    agent_shard_count,
    resolve_pspec,
    resolve_rules,
    tree_pspecs,
)


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1, 1), ("data", "model"))


def fake_mesh(shape, axes):
    """Mesh metadata stand-in with arbitrary logical sizes (no devices
    needed — resolve_pspec only reads .shape and .axis_names)."""

    class M:
        axis_names = axes

        def __init__(self):
            self.shape = dict(zip(axes, shape))

    return M()


def test_basic_rules(mesh1):
    mesh = fake_mesh((16, 16), ("data", "model"))
    rules = resolve_rules(mesh)
    assert rules["heads"] == "model"
    assert rules["batch"] == ("data",)
    assert rules["embed"] is None  # no fsdp
    rules_f = resolve_rules(mesh, fsdp=True)
    assert rules_f["embed"] == ("data",)


def test_resolve_pspec_divisibility_guard():
    mesh = fake_mesh((16, 16), ("data", "model"))
    rules = resolve_rules(mesh)
    # heads=9 not divisible by 16 -> replicated
    spec = resolve_pspec((64, 9, 64), ("embed", "heads", None), rules, mesh)
    assert spec == P()
    # heads=32 divisible -> sharded
    spec = resolve_pspec((64, 32, 64), ("embed", "heads", None), rules, mesh)
    assert spec == P(None, "model")


def test_resolve_pspec_axis_reuse_guard():
    """A mesh axis may appear at most once per PartitionSpec."""
    mesh = fake_mesh((16, 16), ("data", "model"))
    rules = resolve_rules(mesh)
    spec = resolve_pspec((160, 320), ("vocab", "ff"), rules, mesh)
    # both want "model"; second dim must fall back to replicated
    assert spec == P("model")


def test_multipod_batch_axes():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    rules = resolve_rules(mesh, agent_axes=("pod", "data"))
    spec = resolve_pspec((64, 128), ("batch", "embed"), rules, mesh)
    assert spec == P(("pod", "data"))


@given(
    dim=st.integers(1, 4096),
    axis_size=st.sampled_from([2, 4, 16]),
)
@settings(max_examples=50, deadline=None)
def test_pspec_never_breaks_divisibility(dim, axis_size):
    mesh = fake_mesh((axis_size,), ("model",))
    rules = {"ff": "model"}
    spec = resolve_pspec((dim,), ("ff",), rules, mesh)
    if dim % axis_size == 0 and axis_size > 1:
        assert spec == P("model")
    else:
        assert spec == P()


def test_tree_pspecs_structure(mesh1):
    mesh = fake_mesh((16, 16), ("data", "model"))
    rules = resolve_rules(mesh)
    axes = {"a": ("vocab", "embed"), "nested": {"b": ("layer", "embed", "ff")}}
    shapes = {
        "a": jax.ShapeDtypeStruct((32000, 512), jnp.float32),
        "nested": {"b": jax.ShapeDtypeStruct((4, 512, 2048), jnp.float32)},
    }
    specs = tree_pspecs(axes, shapes, rules, mesh)
    assert specs["a"] == P("model")
    assert specs["nested"]["b"] == P(None, None, "model")


def test_agent_axes_resolve_over_pod_and_data():
    """The fleet axis spans BOTH multipod data axes: an (m,) per-agent
    array shards ("pod", "data") when m divides the 2×16 product, and
    the helper reports the matching gateway count."""
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    rules = resolve_rules(mesh, agent_axes=("pod", "data"))
    assert agent_axis_names(mesh, rules) == ("pod", "data")
    assert agent_shard_count(mesh, rules) == 32
    assert agent_pspec(mesh, 64, rules) == P(("pod", "data"))
    spec = resolve_pspec((64, 7), ("agent", None), rules, mesh)
    assert spec == P(("pod", "data"))
    # axes the mesh does not have are filtered, not fatal
    mesh1d = fake_mesh((8,), ("data",))
    rules1d = resolve_rules(mesh1d, agent_axes=("pod", "data"))
    assert agent_axis_names(mesh1d, rules1d) == ("data",)
    assert agent_shard_count(mesh1d, rules1d) == 8


def test_agent_pspec_non_divisible_warns_and_replicates():
    """m not divisible by the agent mesh product must fall back to
    replication — LOUDLY: silently replicating the fleet axis is a
    whole-run perf cliff, not a per-parameter detail."""
    import warnings

    mesh = fake_mesh((8, 2), ("data", "model"))
    rules = resolve_rules(mesh)
    with pytest.warns(UserWarning, match="REPLICATION"):
        assert agent_pspec(mesh, 63, rules) == P()
    # divisible: sharded, and NO warning may fire
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert agent_pspec(mesh, 64, rules) == P("data")


def test_agent_axes_never_reused_within_one_pspec():
    """A mesh axis claimed by the agent dim cannot be claimed again by
    a later dim of the same tensor (the batch rule also wants "data")."""
    mesh = fake_mesh((8, 2), ("data", "model"))
    rules = resolve_rules(mesh)
    spec = resolve_pspec((64, 32), ("agent", "batch"), rules, mesh)
    assert spec == P("data")  # batch dim replicated, not double-claimed
    for s in (spec, resolve_pspec((64, 16, 32), ("agent", "batch", "ff"),
                                  rules, mesh)):
        seen = []
        for entry in s:
            for ax in ((entry,) if isinstance(entry, str) else entry or ()):
                assert ax not in seen, f"mesh axis {ax} appears twice in {s}"
                seen.append(ax)


def test_plan_run_agent_selection():
    """plan_run maps agents onto mesh axes per DESIGN §2."""
    from repro.configs import SHAPES, get_config
    from repro.launch import steps as S

    mesh1 = fake_mesh((16, 16), ("data", "model"))
    mesh2 = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    small = get_config("smollm-135m")
    big = get_config("kimi-k2-1t-a32b")

    p = S.plan_run(small, SHAPES["train_4k"], mesh1)
    assert not p.fsdp and p.agent_axes == ("data",) and p.num_agents == 16
    p = S.plan_run(small, SHAPES["train_4k"], mesh2)
    assert p.agent_axes == ("pod", "data") and p.num_agents == 32
    # FSDP is orthogonal to agent placement (agents stay on data axes —
    # see steps.plan_run comment / EXPERIMENTS.md §Perf qwen3 iter-2)
    p = S.plan_run(big, SHAPES["train_4k"], mesh2)
    assert p.fsdp and p.agent_axes == ("pod", "data") and p.num_agents == 32
    p = S.plan_run(big, SHAPES["train_4k"], mesh1)
    assert p.fsdp and p.num_agents == 16


def test_sharded_train_step_runs_on_host_mesh(rng):
    """End-to-end jit with in/out shardings on the (1,1) host mesh."""
    from repro.configs import SHAPES, get_config, reduced
    from repro.configs.base import InputShape
    from repro.core.api import init_train_state
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.optim import optimizers as opt_lib

    mesh = make_host_mesh()
    cfg = reduced(get_config("smollm-135m"))
    shape = InputShape("t", seq_len=16, global_batch=4, kind="train")
    plan = S.plan_run(cfg, shape, mesh, lr=0.1)
    jitted, state_abs, batch_abs, *_ = S.build_train_step(
        mesh, plan, compute_dtype="float32"
    )
    model = build(plan.cfg.replace(compute_dtype="float32"))
    params, _ = model.init(rng, dtype=jnp.float32)
    opt = opt_lib.from_config(plan.train_cfg)
    state = init_train_state(params, opt, plan.train_cfg)
    batch = {
        "tokens": jnp.ones((plan.num_agents, 4 // plan.num_agents, 16), jnp.int32),
        "labels": jnp.ones((plan.num_agents, 4 // plan.num_agents, 16), jnp.int32),
    }
    state2, metrics = jitted(state, batch)
    assert int(state2.step) == 1
    assert jnp.isfinite(metrics["loss"])
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a - b))) > 0
        for a, b in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(state2.params),
        )
    )
    assert moved
