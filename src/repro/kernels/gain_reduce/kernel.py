"""Pallas TPU kernel: fused gain reduction (gᵀg, gᵀh) in one pass.

This is the per-step hot spot of the paper's trigger at scale: eq. (28)
needs ``gᵀg`` and ``gᵀ(Hg)`` over the *whole flattened gradient* (billions
of elements).  Two separate reductions read the gradient twice from HBM;
the fused kernel reads each tile once and accumulates both dot products
in fp32.

Memory layout: inputs reshaped to (nblk, 8, 128) tiles (8×128 = one VPU
vreg tile in fp32); each grid step reads ``tiles_per_step(nblk)`` tiles
and folds them into two (8, 128) fp32 output blocks that stay resident
in VMEM across the sequential grid (initialized at program 0).  The
final 1024-lane sums run outside the kernel: Mosaic cannot store
scalars to VMEM, so the kernel keeps whole vreg-shaped accumulators.
Arithmetic intensity is 2 FLOPs/4 bytes per input pair — firmly
memory-bound, hence the single-pass design.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANE = 8
LANE = 128
BLOCK = SUBLANE * LANE  # 1024 elements per tile
# tiles per grid step: 1 MiB of fp32 per input block, so two inputs
# double-buffered stay well inside the default scoped VMEM
MAX_TILES = 256


def tiles_per_step(nblk: int) -> int:
    """Grid-step width for ``nblk`` tiles (``nblk`` must be a multiple)."""
    return min(nblk, MAX_TILES)


def _kernel(g_ref, h_ref, gsq_ref, ghg_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        gsq_ref[...] = jnp.zeros_like(gsq_ref)
        ghg_ref[...] = jnp.zeros_like(ghg_ref)

    g = g_ref[...].astype(jnp.float32)  # (tb, 8, 128)
    h = h_ref[...].astype(jnp.float32)
    gsq_ref[...] += jnp.sum(g * g, axis=0)
    ghg_ref[...] += jnp.sum(g * h, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gain_reduce_kernel(g_tiles: jax.Array, h_tiles: jax.Array, *, interpret: bool):
    """g_tiles/h_tiles: (nblk, 8, 128). Returns (gsq, ghg) f32 scalars."""
    nblk = g_tiles.shape[0]
    tb = tiles_per_step(nblk)
    if nblk % tb:
        raise ValueError(f"{nblk} tiles is not a multiple of the {tb}-tile step")
    acc = jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.float32)
    in_spec = pl.BlockSpec((tb, SUBLANE, LANE), lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((SUBLANE, LANE), lambda i: (0, 0))
    gsq, ghg = pl.pallas_call(
        _kernel,
        grid=(nblk // tb,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[acc, acc],
        interpret=interpret,
    )(g_tiles, h_tiles)
    return jnp.sum(gsq), jnp.sum(ghg)
