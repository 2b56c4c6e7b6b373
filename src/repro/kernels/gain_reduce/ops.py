"""jit'd public wrapper for the gain-reduce Pallas kernel.

Handles arbitrary-length inputs: zero-pads to a whole number of grid
steps (zeros contribute nothing to either dot product) and reshapes to
the kernel's (nblk, 8, 128) layout.  The kernel compiles with Mosaic on
TPU and runs in interpret mode on CPU (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.gain_reduce.kernel import (
    BLOCK,
    LANE,
    SUBLANE,
    gain_reduce_kernel,
    tiles_per_step,
)


def _tile(x: jax.Array) -> jax.Array:
    flat = x.reshape(-1).astype(jnp.float32)
    step = BLOCK * tiles_per_step(max(-(-flat.size // BLOCK), 1))
    pad = (-flat.size) % step
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, SUBLANE, LANE)


def gain_reduce(g: jax.Array, h: jax.Array):
    """(gᵀg, gᵀh) over flattened inputs, single fused pass."""
    assert g.size == h.size, (g.shape, h.shape)
    return gain_reduce_kernel(_tile(g), _tile(h), interpret=interpret_mode())


def gain_estimate(g: jax.Array, h: jax.Array, eps: float):
    """Eq. (28): −ε gᵀg + (ε²/2) gᵀ(Hg), fused."""
    gsq, ghg = gain_reduce(g, h)
    return -eps * gsq + 0.5 * eps * eps * ghg
