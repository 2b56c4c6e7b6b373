"""Substrate layers: optimizers, schedules, data pipeline, checkpointing,
tree utils, HLO cost model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim import optimizers as O
from repro.optim import schedules as SCH
from repro.utils import tree as TU


# ----------------------------------------------------------------------
# optimizers
# ----------------------------------------------------------------------

def quad(params):
    return 0.5 * jnp.sum(params["w"] ** 2) + jnp.sum((params["b"] - 1.0) ** 2)


@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
def test_optimizers_converge_on_quadratic(name):
    opt = {"sgd": O.sgd(0.2), "momentum": O.momentum(0.1), "adamw": O.adamw(0.1)}[name]
    params = {"w": jnp.ones(4) * 3.0, "b": jnp.zeros(3)}
    state = opt.init(params)
    for step in range(200):
        g = jax.grad(quad)(params)
        upd, state = opt.update(g, state, params, jnp.int32(step))
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, upd)
    assert float(quad(params)) < 1e-3


def test_adamw_moments_fp32_under_bf16():
    opt = O.adamw(0.1)
    params = {"w": jnp.ones(4, jnp.bfloat16)}
    state = opt.init(params)
    assert state.mu["w"].dtype == jnp.float32
    g = {"w": jnp.ones(4, jnp.bfloat16)}
    upd, state = opt.update(g, state, params, jnp.int32(0))
    assert upd["w"].dtype == jnp.bfloat16  # cast back to param dtype


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 3.0}
    out = O.clip_by_global_norm(g, 1.0)
    assert float(jnp.linalg.norm(out["a"])) == pytest.approx(1.0, rel=1e-5)
    out2 = O.clip_by_global_norm(g, 1e9)
    np.testing.assert_allclose(out2["a"], g["a"])


def test_schedules():
    base = SCH.cosine(1.0, total_steps=100)
    cos = SCH.linear_warmup(base, warmup_steps=10)
    assert float(cos(jnp.int32(0))) == pytest.approx(0.1 * float(base(0)), rel=1e-4)
    assert float(cos(jnp.int32(9))) == pytest.approx(float(base(9)), rel=1e-4)
    assert float(cos(jnp.int32(100))) == pytest.approx(0.1, rel=1e-4)  # final_frac
    lin = SCH.linear_decay(2.0, total_steps=50)
    assert float(lin(jnp.int32(0))) == pytest.approx(2.0, rel=1e-5)
    assert float(lin(jnp.int32(50))) == pytest.approx(0.0, abs=1e-5)
    assert float(SCH.constant(0.3)(jnp.int32(7))) == pytest.approx(0.3)


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------

def test_lm_stream_deterministic_and_learnable(rng):
    from repro.data import synthetic as D

    t1 = D.sample_lm_tokens(rng, 4, 64, 97)
    t2 = D.sample_lm_tokens(rng, 4, 64, 97)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (4, 64) and t1.dtype == jnp.int32
    assert int(t1.min()) >= 0 and int(t1.max()) < 97
    # bigram structure: next-token conditional entropy < marginal entropy
    toks = np.asarray(D.sample_lm_tokens(rng, 64, 128, 17))
    pairs = np.stack([toks[:, :-1].ravel(), toks[:, 1:].ravel()])
    joint = np.zeros((17, 17))
    np.add.at(joint, (pairs[0], pairs[1]), 1)
    pj = joint / joint.sum()
    pm = pj.sum(0)
    h_marg = -np.sum(pm * np.log(pm + 1e-12))
    pc = pj / (pj.sum(1, keepdims=True) + 1e-12)
    h_cond = -np.sum(pj.sum(1) * np.sum(pc * np.log(pc + 1e-12), axis=1))
    assert h_cond < 0.8 * h_marg  # strongly structured


def test_lm_batch_agent_layout(rng):
    from repro.configs import get_config, reduced
    from repro.configs.base import InputShape
    from repro.data import synthetic as D

    cfg = reduced(get_config("smollm-135m"))
    shape = InputShape("t", seq_len=16, global_batch=8, kind="train")
    b = D.lm_batch(cfg, shape, rng, num_agents=4)
    assert b["tokens"].shape == (4, 2, 16)
    np.testing.assert_array_equal(
        np.asarray(b["tokens"][..., 1:]), np.asarray(b["labels"][..., :-1])
    )


# ----------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, rng):
    from repro.checkpoint import checkpointer as C

    tree = {
        "params": {"w": jax.random.normal(rng, (3, 4)), "b": jnp.zeros(2)},
        "step": jnp.int32(17),
    }
    C.save(str(tmp_path), 17, tree)
    C.save(str(tmp_path), 23, tree)
    assert C.latest_step(str(tmp_path)) == 23
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    back = C.restore(str(tmp_path), like, step=17)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_structure_mismatch(tmp_path, rng):
    from repro.checkpoint import checkpointer as C

    C.save(str(tmp_path), 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        C.restore(str(tmp_path), {"a": jnp.zeros(3), "b": jnp.zeros(1)})


# ----------------------------------------------------------------------
# tree utils (property)
# ----------------------------------------------------------------------

@given(
    scale=st.floats(-3, 3, allow_nan=False, width=32),
    n=st.integers(1, 16),
)
@settings(max_examples=30, deadline=None)
def test_tree_add_scaled_props(scale, n):
    a = {"x": jnp.arange(n, dtype=jnp.float32)}
    b = {"x": jnp.ones(n, jnp.float32)}
    out = TU.tree_add_scaled(a, b, scale)
    np.testing.assert_allclose(
        np.asarray(out["x"]), np.arange(n) + scale, rtol=1e-5, atol=1e-5
    )
    # dtype pinned to a's leaves
    a16 = {"x": jnp.ones(n, jnp.bfloat16)}
    assert TU.tree_add_scaled(a16, b, jnp.float32(scale))["x"].dtype == jnp.bfloat16


def test_tree_vdot_matches_flat(rng):
    a = {"x": jax.random.normal(rng, (5,)), "y": jax.random.normal(rng, (2, 3))}
    b = jax.tree_util.tree_map(lambda t: t * 0.5 + 1, a)
    flat_a = jnp.concatenate([t.ravel() for t in jax.tree_util.tree_leaves(a)])
    flat_b = jnp.concatenate([t.ravel() for t in jax.tree_util.tree_leaves(b)])
    assert float(TU.tree_vdot(a, b)) == pytest.approx(float(flat_a @ flat_b), rel=1e-5)


# ----------------------------------------------------------------------
# HLO cost model
# ----------------------------------------------------------------------

def test_hlo_cost_scan_trip_multiplication():
    from repro.analysis import hlo_cost

    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    def unrolled(x, w):
        for _ in range(8):
            x = x @ w
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    fs = hlo_cost.analyze(jax.jit(scanned).lower(x, w).compile().as_text())
    fu = hlo_cost.analyze(jax.jit(unrolled).lower(x, w).compile().as_text())
    want = 8 * 2 * 128**3
    assert abs(fs.flops - want) / want < 0.01
    assert abs(fu.flops - want) / want < 0.01
    # XLA's own counter misses the scan body multiplicity — that's why
    # hlo_cost exists; guard that the discrepancy is still there (if XLA
    # fixes it someday this test will flag the redundancy).
    xla = jax.jit(scanned).lower(x, w).compile().cost_analysis()["flops"]
    assert xla < want / 2


def test_hlo_cost_dot_flops_shape():
    from repro.analysis import hlo_cost

    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    cost = hlo_cost.analyze(jax.jit(f).lower(a, b).compile().as_text())
    want = 2 * 64 * 32 * 16
    assert abs(cost.flops - want) / want < 0.05


def test_hlo_collective_parse_canned():
    """Wire-byte factors on a canned post-SPMD HLO snippet."""
    from repro.analysis import hlo_cost

    txt = """
HloModule test

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    cost = hlo_cost.analyze(txt)
    b = 1024 * 4
    assert cost.collectives["all-reduce"]["count"] == 1
    assert cost.wire_bytes == pytest.approx(2 * b * 3 / 4)
