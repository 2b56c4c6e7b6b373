"""jit'd public wrapper for the fused-CE Pallas kernel.

Accepts model-layout hidden states (B, S, D) + labels (B, S); flattens
to token-major, pads the token axis to a tile multiple (padded tokens
are masked out of the mean), and returns the mean NLL — a drop-in for
``layers.cross_entropy_fused`` on the forward path.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fused_ce.kernel import fused_ce_kernel


def fused_ce(x, table, labels, *, bt: int = 128, bv: int = 512):
    """Mean token NLL. x (B,S,D) or (T,D); labels matching leading dims."""
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
        labels = labels.reshape(-1)
    T = x.shape[0]
    bt = min(bt, max(T, 1))
    pad = (-T) % bt
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
        labels = jnp.concatenate([labels, jnp.zeros((pad,), labels.dtype)], axis=0)
    nll = fused_ce_kernel(
        x, table, labels.astype(jnp.int32)[:, None],
        bt=bt, bv=min(bv, table.shape[0] + (-table.shape[0]) % 8),
        interpret=interpret_mode(),
    )[:, 0]
    if pad:
        nll = nll[:T]
    return jnp.mean(nll)
