"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not module state) so importing
this module never touches jax device initialization — only the dry-run
process sets ``--xla_force_host_platform_device_count=512``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the steps place arrays through
    in/out shardings and sharding constraints, which is Auto-mode
    partitioning (jax's default axis type is Explicit)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _mesh((n // model, model), ("data", "model"))


def make_fleet_mesh(shards: int | None = None):
    """1-D agent/data mesh for fleet-sharded train steps.

    ``shards`` gateways over the first ``shards`` local devices (all of
    them by default) — the mesh the shard-scale benchmarks and tests
    run under ``--xla_force_host_platform_device_count=N``.  The single
    axis is named "data" so the default sharding rules put the agent
    logical axis on it.
    """
    n = len(jax.devices()) if shards is None else int(shards)
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(f"asked for {n} fleet shards but only {avail} "
                         f"devices are visible")
    return _mesh((n,), ("data",), jax.devices()[:n])
