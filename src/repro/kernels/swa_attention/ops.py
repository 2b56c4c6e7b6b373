"""jit'd public wrapper for the SWA flash-attention Pallas kernel.

Accepts the model layout q (B,S,H,hd), k/v (B,S,KV,hd); transposes to
the kernel's head-major layout, pads S to a block multiple and the
window to a kv-block multiple (padding keys are masked out by position,
padding queries are cropped after the call).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.swa_attention.kernel import swa_attention_kernel


def swa_attention(q, k, v, *, window: int, bq: int = 128, bk: int = 128):
    b, s, h, hd = q.shape
    # block sizes never exceed the (padded) sequence
    bq = min(bq, max(s, 1))
    bk = min(bk, max(s, 1))
    # a window ≥ S is plain causal attention: clamp so the kernel's
    # kv-block loop is O(S/bk), not O(window/bk)
    window = min(window, s + (-s) % bq)
    pad = (-s) % bq
    if pad:
        zq = jnp.zeros((b, pad, h, hd), q.dtype)
        zkv = jnp.zeros((b, pad, k.shape[2], hd), k.dtype)
        q = jnp.concatenate([q, zq], axis=1)
        k = jnp.concatenate([k, zkv], axis=1)
        v = jnp.concatenate([v, zkv], axis=1)
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    out = swa_attention_kernel(
        qT, kT, vT, window=window, bq=bq, bk=bk, interpret=interpret_mode()
    )
    out = out.transpose(0, 2, 1, 3)
    return out[:, :s] if pad else out
