"""The Pallas kernels compile for a TPU v5e at the widths the system runs.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
scalar stores to VMEM, unaligned blocks, too much fast memory.  These
tests compile each kernel with ``interpret=False`` for a *described*
v5e chip — the TPU compiler is installed, no chip is attached — and
check that the kernel really landed in the program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_ce.kernel import fused_ce_kernel
from repro.kernels.gain_reduce.kernel import LANE, SUBLANE, gain_reduce_kernel
from repro.kernels.swa_attention.kernel import swa_attention_kernel

# the flattened smollm-135m gradient, in (8, 128) tiles
GRAD_TILES = 131072


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_gain_reduce_compiles_at_smollm_gradient(one_chip):
    tiles = _sds((GRAD_TILES, SUBLANE, LANE), jnp.float32, one_chip)
    compiled = gain_reduce_kernel.lower(tiles, tiles, interpret=False).compile()
    _assert_kernel(compiled)


def test_gain_reduce_compiles_vmapped_over_agents(one_chip):
    """The hybrid prologue calls the kernel under ``vmap`` over agents."""
    tiles = _sds((4, GRAD_TILES, SUBLANE, LANE), jnp.float32, one_chip)
    fn = jax.vmap(functools.partial(gain_reduce_kernel, interpret=False))
    _assert_kernel(jax.jit(fn).lower(tiles, tiles).compile())


def test_fused_ce_compiles_at_smollm_widths(one_chip):
    cfg = get_config("smollm-135m")
    tokens = 2048
    x = _sds((tokens, cfg.d_model), jnp.bfloat16, one_chip)
    table = _sds((cfg.vocab_size, cfg.d_model), jnp.bfloat16, one_chip)
    labels = _sds((tokens, 1), jnp.int32, one_chip)
    compiled = fused_ce_kernel.lower(
        x, table, labels, bt=128, bv=512, interpret=False).compile()
    _assert_kernel(compiled)


def test_swa_attention_compiles_at_real_window(one_chip):
    """smollm-135m heads at the 4096-token window of the long-context
    variant (``models.long_context_variant``)."""
    cfg = get_config("smollm-135m")
    seq, hd = 8192, cfg.d_model // cfg.num_heads
    q = _sds((1, cfg.num_heads, seq, hd), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.num_kv_heads, seq, hd), jnp.bfloat16, one_chip)
    compiled = swa_attention_kernel.lower(
        q, kv, kv, window=4096, bq=128, bk=128, interpret=False).compile()
    _assert_kernel(compiled)


def test_gain_reduce_keeps_its_name_under_the_probe_scope(one_chip):
    """The chip benchmark finds the kernel's device events by
    ``gain_reduce_kernel`` in the op's name; the train step's ``probe``
    scope, under the per-agent ``vmap``, must leave it there."""
    import re

    from repro.comm.policy import CommPolicy

    trig = CommPolicy.parse_one(
        "grad_norm(mu=1.0,kernel=true)").build_trigger()
    grads = {"w": _sds((2, 4096, 64), jnp.float32, one_chip),
             "b": _sds((2, 64), jnp.float32, one_chip)}

    def train_step(grads):
        def per_agent(g):
            with jax.named_scope("probe"):
                return trig.prologue(None, g, None, None)

        return jax.vmap(per_agent)(grads)

    # the kernel picks Mosaic over interpret mode from the default device
    with jax.default_device(next(iter(one_chip.device_set))):
        compiled = jax.jit(train_step).lower(grads).compile()
    _assert_kernel(compiled)
    assert re.search(r"%\S*gain_reduce_kernel\S* = ", compiled.as_text())
