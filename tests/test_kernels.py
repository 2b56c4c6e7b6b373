"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gain_reduce import ops as gr_ops
from repro.kernels.gain_reduce import ref as gr_ref
from repro.kernels.swa_attention import ops as swa_ops
from repro.kernels.swa_attention import ref as swa_ref


# ----------------------------------------------------------------------
# gain_reduce: fused (g·g, g·h) reduction
# ----------------------------------------------------------------------

@pytest.mark.parametrize(
    "shape",
    # (1000, 300) spans more than one grid step (kernel.MAX_TILES tiles)
    [(7,), (1024,), (1000, 37), (8, 128), (3, 5, 17), (4096, 64), (1000, 300)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gain_reduce_matches_ref(shape, dtype, rng):
    k1, k2 = jax.random.split(rng)
    g = jax.random.normal(k1, shape, dtype)
    h = jax.random.normal(k2, shape, dtype)
    gsq, ghg = gr_ops.gain_reduce(g, h)
    rsq, rhg = gr_ref.gain_reduce_ref(g, h)
    tol = 1e-5 * g.size if dtype == jnp.float32 else 2e-2 * g.size
    np.testing.assert_allclose(float(gsq), float(rsq), atol=tol, rtol=1e-4)
    np.testing.assert_allclose(float(ghg), float(rhg), atol=tol, rtol=1e-4)


def test_gain_reduce_zero_padding_exact(rng):
    """Padding to the tile multiple must contribute exactly nothing."""
    g = jax.random.normal(rng, (1025,))  # forces padding
    gsq, _ = gr_ops.gain_reduce(g, g)
    np.testing.assert_allclose(float(gsq), float(jnp.sum(g * g)), rtol=1e-6)


def test_gain_reduce_unknown_platform_raises(monkeypatch, rng):
    """The Pallas mode is chosen per call: a platform with no kernel
    path raises instead of silently interpreting."""
    g = jax.random.normal(rng, (1000, 37))
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        gr_ops.gain_reduce(g, g)


def test_gain_reduce_follows_default_device(monkeypatch, rng):
    """Under ``jax.default_device(cpu)`` the kernel interprets even when
    the default backend is a TPU (the CPU reference runs beside the chip)."""
    k1, k2 = jax.random.split(rng)
    g = jax.random.normal(k1, (1000, 37))
    h = jax.random.normal(k2, (1000, 37))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_device(jax.devices("cpu")[0]):
        gsq, ghg = gr_ops.gain_reduce(g, h)
    rsq, rhg = gr_ref.gain_reduce_ref(g, h)
    np.testing.assert_allclose(float(gsq), float(rsq), rtol=1e-5)
    np.testing.assert_allclose(float(ghg), float(rhg), rtol=1e-5, atol=1e-3)


def test_gain_estimate_formula(rng):
    g = jax.random.normal(rng, (2048,))
    h = 0.3 * g + 1.0
    eps = 0.05
    got = gr_ops.gain_estimate(g, h, eps)
    want = -eps * jnp.sum(g * g) + 0.5 * eps * eps * jnp.sum(g * h)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# ----------------------------------------------------------------------
# swa_attention: sliding-window flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("s", [64, 128, 200, 384])
@pytest.mark.parametrize("window", [32, 128, 1 << 30])
def test_swa_matches_ref_shapes(s, window, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    b, h, kv, hd = 2, 4, 2, 64
    q = jax.random.normal(k1, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(k2, (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(k3, (b, s, kv, hd), jnp.float32)
    out = swa_ops.swa_attention(q, k, v, window=window, bq=64, bk=64)
    ref = swa_ref.swa_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_swa_dtypes(dtype, atol, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    b, s, h, kv, hd = 1, 128, 2, 1, 64
    q = jax.random.normal(k1, (b, s, h, hd), dtype)
    k = jax.random.normal(k2, (b, s, kv, hd), dtype)
    v = jax.random.normal(k3, (b, s, kv, hd), dtype)
    out = swa_ops.swa_attention(q, k, v, window=64, bq=64, bk=64)
    ref = swa_ref.swa_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol, rtol=atol
    )


def test_swa_matches_model_attention(rng):
    """Kernel ≡ the model's jnp attention path for the SWA case."""
    from repro.models.attention import attend

    k1, k2, k3 = jax.random.split(rng, 3)
    b, s, h, kv, hd = 1, 256, 4, 2, 32
    q = jax.random.normal(k1, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(k2, (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(k3, (b, s, kv, hd), jnp.float32)
    win = 64
    out = swa_ops.swa_attention(q, k, v, window=win, bq=64, bk=64)
    ref = attend(q, k, v, causal=True, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
