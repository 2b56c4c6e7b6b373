"""Server-side aggregation (eq. 10) + beyond-paper quantization/EF,
with hypothesis property tests on the invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm.compressors import dequantize_int8, fake_quantize, quantize_int8
from repro.core.aggregation import masked_mean, masked_mean_quantized


def tree(key, A):
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (A, 4, 3)),
        "b": jax.random.normal(k2, (A, 5)),
    }


def test_eq10_cases(rng):
    """The paper's four cases for m=2."""
    g = tree(rng, 2)
    w1 = jax.tree_util.tree_map(lambda t: t[0], g)
    w2 = jax.tree_util.tree_map(lambda t: t[1], g)

    both = masked_mean(g, jnp.array([1.0, 1.0]))
    only1 = masked_mean(g, jnp.array([1.0, 0.0]))
    none = masked_mean(g, jnp.array([0.0, 0.0]))

    for k in g:
        np.testing.assert_allclose(both[k], (w1[k] + w2[k]) / 2, rtol=1e-6)
        np.testing.assert_allclose(only1[k], w1[k], rtol=1e-6)
        np.testing.assert_allclose(none[k], jnp.zeros_like(w1[k]))  # hold


@given(alphas=st.lists(st.sampled_from([0.0, 1.0]), min_size=1, max_size=8))
@settings(max_examples=25, deadline=None)
def test_masked_mean_is_mean_of_transmitters(alphas):
    A = len(alphas)
    g = {"x": jnp.arange(A * 3, dtype=jnp.float32).reshape(A, 3)}
    out = masked_mean(g, jnp.asarray(alphas))["x"]
    tx = [i for i, a in enumerate(alphas) if a]
    want = (
        np.mean([np.arange(i * 3, i * 3 + 3) for i in tx], axis=0)
        if tx
        else np.zeros(3)
    )
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


@given(
    vals=st.lists(
        st.floats(-1e4, 1e4, allow_nan=False, width=32), min_size=1, max_size=64
    )
)
@settings(max_examples=50, deadline=None)
def test_quantize_int8_error_bound(vals):
    x = jnp.asarray(vals, jnp.float32)
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    back = dequantize_int8(q, scale)
    amax = float(jnp.max(jnp.abs(x)))
    # symmetric quantization: |err| <= scale/2 = amax/254
    assert float(jnp.max(jnp.abs(back - x))) <= amax / 254 + 1e-6


def test_quantize_zero_safe():
    q, s = quantize_int8(jnp.zeros(7))
    assert float(s) == 1.0 and not np.any(np.asarray(q))


def test_error_feedback_carries_residual(rng):
    """EF memory holds (g − Q(g)) for transmitting agents, 0 for silent."""
    g = tree(rng, 2)
    alphas = jnp.array([1.0, 0.0])
    ef0 = jax.tree_util.tree_map(jnp.zeros_like, g)
    agg, ef1 = masked_mean_quantized(g, alphas, ef0)
    for k in g:
        resid = g[k] - fake_quantize(g[k])
        np.testing.assert_allclose(ef1[k][0], resid[0], atol=1e-6)
        np.testing.assert_allclose(ef1[k][1], jnp.zeros_like(resid[1]))
        np.testing.assert_allclose(agg[k], fake_quantize(g[k])[0], atol=1e-6)


def test_error_feedback_reduces_bias(rng):
    """Over repeated rounds with a CONSTANT gradient, EF makes the mean
    applied update converge to the true gradient (unbiased in the limit),
    while plain quantization keeps a persistent bias."""
    g_const = {"x": jnp.full((1, 257), 0.77) * jnp.linspace(0.9, 1.1, 257)}
    alphas = jnp.ones((1,))

    applied_q, applied_ef = [], []
    ef = jax.tree_util.tree_map(jnp.zeros_like, g_const)
    for _ in range(32):
        aq, _ = masked_mean_quantized(g_const, alphas, None)
        applied_q.append(aq["x"])
        ae, ef = masked_mean_quantized(g_const, alphas, ef)
        applied_ef.append(ae["x"])
    true = g_const["x"][0]
    err_q = float(jnp.max(jnp.abs(jnp.mean(jnp.stack(applied_q), 0) - true)))
    err_ef = float(jnp.max(jnp.abs(jnp.mean(jnp.stack(applied_ef), 0) - true)))
    assert err_ef < err_q * 0.5, (err_ef, err_q)


# ----------------------------------------------------------------------
# Beyond-paper: top-k sparsified transmission (Aji & Heafield family)
# ----------------------------------------------------------------------

def test_topk_sparsify_keeps_largest(rng):
    from repro.comm.compressors import topk_sparsify

    x = jnp.asarray([0.1, -5.0, 0.3, 2.0, -0.2, 0.05])
    sparse, kept = topk_sparsify(x, 0.34)  # k = 2
    np.testing.assert_allclose(np.asarray(sparse),
                               [0.0, -5.0, 0.0, 2.0, 0.0, 0.0])
    assert int(kept) == 2


@given(frac=st.floats(0.05, 1.0))
@settings(max_examples=25, deadline=None)
def test_topk_fraction_property(frac):
    from repro.comm.compressors import topk_sparsify

    x = jnp.linspace(-1.0, 1.0, 64) + 1e-3  # distinct magnitudes
    sparse, kept = topk_sparsify(x, frac)
    k = max(1, int(frac * 64))
    assert int(kept) == k
    # kept entries are exactly the k largest |x|
    top_idx = np.argsort(-np.abs(np.asarray(x)))[:k]
    mask = np.zeros(64, bool)
    mask[top_idx] = True
    np.testing.assert_allclose(np.asarray(sparse), np.where(mask, x, 0.0),
                               atol=1e-7)


def test_masked_mean_topk_with_error_feedback(rng):
    from repro.comm.compressors import topk_sparsify
    from repro.core.aggregation import masked_mean_topk

    g = tree(rng, 2)
    alphas = jnp.array([1.0, 1.0])
    ef0 = jax.tree_util.tree_map(jnp.zeros_like, g)
    agg, ef1 = masked_mean_topk(g, alphas, 0.25, ef0)
    for k in g:
        sent = jnp.stack([topk_sparsify(g[k][a], 0.25)[0] for a in range(2)])
        np.testing.assert_allclose(np.asarray(agg[k]),
                                   np.asarray(jnp.mean(sent, 0)), atol=1e-6)
        np.testing.assert_allclose(np.asarray(ef1[k]),
                                   np.asarray(g[k] - sent), atol=1e-6)
