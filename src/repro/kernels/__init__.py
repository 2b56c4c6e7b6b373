"""Pallas kernels for the paper's compute hot spots.

Each kernel package holds ``kernel.py`` (the Pallas program), ``ops.py``
(the public wrapper) and ``ref.py`` (the pure-jnp oracle tests compare
against).  The wrappers pick the Pallas mode when they are called, from
the platform the call will run on — never at import.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether a Pallas call made now runs in interpret mode.

    The platform is the default device's (``jax.default_device``) or,
    without one, the default backend's.  TPU compiles the kernel with
    Mosaic; CPU interprets it (the test suite); any other platform has
    no kernel, and asking raises.
    """
    dev = jax.config.jax_default_device
    platform = getattr(dev, "platform", dev) or jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"no Pallas kernel path for platform {platform!r}: kernels compile "
        f"on TPU and run in interpret mode on CPU"
    )
