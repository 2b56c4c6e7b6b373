"""Where JAX keeps its persistent compilation cache for this checkout,
and a record of every compile.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/chip/harness.py``) call :func:`enable_compile_cache` before
their first compile, so a second run of the same program skips
compilation, and every later compile (or load from the cache) is
recorded for :func:`compile_events`.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import List, NamedTuple

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]

# JAX's event for one backend compile, or one load from the persistent
# cache; its duration listeners get the program's name as ``fun_name``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileEvent(NamedTuple):
    end: float      # time.perf_counter() when the compile finished
    seconds: float  # how long it took
    name: str       # the compiled function's name


_EVENTS: List[CompileEvent] = []
_LISTENING = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _EVENTS.append(CompileEvent(time.perf_counter(), float(seconds),
                                    str(kw.get("fun_name", ""))))


def record_compiles() -> None:
    """Record every backend compile from now on (idempotent)."""
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True


def compile_events() -> List[CompileEvent]:
    """The compiles recorded so far, oldest first."""
    return list(_EVENTS)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and the compile record
    (:func:`record_compiles`); returns the cache's directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache/`` (gitignored): cached entries are found
    again only under the same directory, so it never holds a temp
    name, a pid or a time.
    """
    record_compiles()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
