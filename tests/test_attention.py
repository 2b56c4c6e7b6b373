"""Attention-path equivalences: blockwise == direct, SWA masking,
decode-cache == full recompute, GQA expansion, RoPE invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import attention as A


def qkv_rand(rng, b=2, s=96, h=4, kv=2, hd=32):
    k1, k2, k3 = jax.random.split(rng, 3)
    return (
        jax.random.normal(k1, (b, s, h, hd)),
        jax.random.normal(k2, (b, s, kv, hd)),
        jax.random.normal(k3, (b, s, kv, hd)),
    )


def test_blockwise_equals_direct(rng):
    q, k, v = qkv_rand(rng)
    for window in (None, 24):
        ref = A.attend(q, k, v, causal=True, window=window)
        blk = A.attend_blockwise(q, k, v, causal=True, window=window, q_block=32)
        np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), atol=2e-5)


def test_blockwise_gradient_equals_direct(rng):
    q, k, v = qkv_rand(rng, s=64)

    def f(fn):
        return jax.grad(lambda q_: jnp.sum(fn(q_, k, v, causal=True, window=None) ** 2))(q)

    g_ref = f(A.attend)
    g_blk = f(lambda *a, **kw: A.attend_blockwise(*a, q_block=16, **kw))
    np.testing.assert_allclose(np.asarray(g_blk), np.asarray(g_ref), atol=3e-5)


def test_swa_window_masks_far_tokens(rng):
    """With window w, logits at position i must not depend on keys ≤ i−w."""
    q, k, v = qkv_rand(rng, b=1, s=48)
    out1 = A.attend(q, k, v, causal=True, window=16)
    v2 = v.at[:, :8].set(jax.random.normal(rng, v[:, :8].shape))  # perturb old
    k2 = k.at[:, :8].set(jax.random.normal(jax.random.fold_in(rng, 9), k[:, :8].shape))
    out2 = A.attend(q, k2, v2, causal=True, window=16)
    # positions >= 8+16 see identical windows
    np.testing.assert_allclose(
        np.asarray(out1[:, 24:]), np.asarray(out2[:, 24:]), atol=1e-6
    )
    assert not np.allclose(np.asarray(out1[:, :20]), np.asarray(out2[:, :20]))


def test_gqa_expand_repeats_heads(rng):
    k = jax.random.normal(rng, (1, 5, 2, 4))
    e = A._expand_kv(k, 6)
    assert e.shape == (1, 5, 6, 4)
    for rep in range(3):
        np.testing.assert_array_equal(e[:, :, rep], k[:, :, 0])
        np.testing.assert_array_equal(e[:, :, 3 + rep], k[:, :, 1])


@given(pos=st.integers(0, 40))
@settings(max_examples=10, deadline=None)
def test_decode_attend_matches_full(pos):
    """Sequential decode through the KV cache == direct attention over
    the same prefix, at every position."""
    rng = jax.random.key(42)

    class Cfg:
        num_heads, num_kv_heads, head_dim_ = 2, 1, 16
        swa_window, qk_norm, rope_theta, norm_eps = None, False, 10_000.0, 1e-5

    cfg = Cfg()
    d = 32
    k1, k2 = jax.random.split(rng)
    p = {
        "wq": 0.3 * jax.random.normal(k1, (d, 2, 16)),
        "wk": 0.3 * jax.random.normal(jax.random.fold_in(k1, 1), (d, 1, 16)),
        "wv": 0.3 * jax.random.normal(jax.random.fold_in(k1, 2), (d, 1, 16)),
    }
    S = pos + 1
    xs = jax.random.normal(k2, (1, S, d))

    # reference: full causal attention over the S-token prefix
    positions = jnp.arange(S)[None]
    q, k, v = A.qkv(p, cfg, xs, positions)
    ref = A.attend(q, k, v, causal=True)[:, -1]

    # decode: feed tokens one at a time through the cache
    cache = A.init_kv_cache(1, S + 4, 1, 16, jnp.float32)
    for t in range(S):
        out, cache = A.decode_attend(p, cfg, xs[:, t : t + 1], cache, jnp.int32(t))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), atol=2e-5)


def test_decode_swa_ring_buffer():
    """SWA decode cache is a ring buffer of window slots — old entries
    are overwritten and masked out."""

    class Cfg:
        num_heads, num_kv_heads, head_dim_ = 1, 1, 8
        swa_window, qk_norm, rope_theta, norm_eps = 4, False, 10_000.0, 1e-5

    cfg = Cfg()
    rng = jax.random.key(0)
    d = 8
    p = {
        "wq": jnp.eye(d).reshape(d, 1, 8),
        "wk": jnp.eye(d).reshape(d, 1, 8),
        "wv": jnp.eye(d).reshape(d, 1, 8),
    }
    cache = A.init_kv_cache(1, 4, 1, 8, jnp.float32)  # C = window
    xs = jax.random.normal(rng, (1, 10, d))
    for t in range(10):
        out, cache = A.decode_attend(p, cfg, xs[:, t : t + 1], cache, jnp.int32(t))
    # cache holds positions 6..9 only
    assert set(np.asarray(cache.pos_ids).tolist()) == {6, 7, 8, 9}


def test_rope_preserves_norm(rng):
    x = jax.random.normal(rng, (1, 12, 2, 16))
    pos = jnp.arange(12)[None]
    y = A.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )


def test_rope_relative_shift_invariance(rng):
    """q·k after RoPE depends only on relative distance."""
    q = jax.random.normal(rng, (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, 16))

    def score(qpos, kpos):
        qr = A.apply_rope(q, jnp.array([[qpos]]), 10_000.0)
        kr = A.apply_rope(k, jnp.array([[kpos]]), 10_000.0)
        return float(jnp.sum(qr * kr))

    assert score(5, 3) == pytest.approx(score(25, 23), rel=1e-4)
    assert score(7, 0) == pytest.approx(score(107, 100), rel=1e-4)
