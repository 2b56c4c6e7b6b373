"""Benchmark driver: one module per paper figure/table + framework tables.

  python -m benchmarks.run            # everything
  python -m benchmarks.run fig2_left  # one benchmark
  python -m benchmarks.run --list     # name + description per benchmark
  python -m benchmarks.run --smoke fig2_left hetero_frontier
                                      # toy sizes, claim asserts off (CI)
  python -m benchmarks.run --smoke --dispatch switch tiered_m64
                                      # pin the hetero dispatch path

Prints each benchmark's CSV and a final summary line per benchmark.
``--list`` descriptions come straight from each module's docstring, so
the catalogue cannot drift from the code (see benchmarks/README.md for
the full table).

Valued flags are driven by the ``KNOBS`` registry below — one
declaration per knob carries its flag, its parser (the loud-typo
contract: an invalid value fails on stderr with rc 2 before anything
runs), and its skip reason.  A benchmark opts into a knob simply by
taking the keyword in its ``run()`` signature; under a knob it does not
take, it is skipped loudly instead of silently running on defaults —
the same contract ``--smoke`` has always had.  Current knobs:

* ``--dispatch MODE`` — pin the heterogeneous train-step dispatch path
  (one of repro.core.api's ``DISPATCH_MODES``); artifacts gain a
  ``_MODE`` name suffix so CI can gate each lane separately.
* ``--seed N`` — re-key the benchmarks whose randomness takes a seed
  (the lossy-channel delivery stream).
* ``--devices N`` — force an N-device host platform (``--xla_force_
  host_platform_device_count``) for the fleet-sharding benchmarks; it
  MUST take effect before jax is imported, so the registry marks it
  ``pre_import`` and it is consumed at module top, before the
  benchmark imports.
* ``--ckpt-dir PATH`` — root directory for the fault-tolerance
  benchmark's crash-resume checkpoints (validated writable up front;
  default: a temp directory).
* ``--kill-round N`` — the round the fault-tolerance benchmark
  checkpoints and "kills" its session at (positive integer).

Dry-run-derived tables (roofline) read cached JSONs from
``experiments/dryrun`` — run ``python -m repro.launch.dryrun --all``
first if missing."""
from __future__ import annotations

import dataclasses
import inspect
import os
import sys
import time
import traceback
from typing import Callable, Optional


class KnobError(ValueError):
    """Invalid value for a registry knob (printed to stderr, rc 2)."""


# ----------------------------------------------------------------------
# the knob registry: one declaration per valued flag
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Knob:
    """One valued CLI flag the driver forwards to benchmark ``run()``s.

    ``parse`` validates the raw token (raising :class:`KnobError` with
    the user-facing message); ``apply`` runs once after a successful
    parse for environment side effects; ``pre_import`` knobs are
    consumed at module top, before anything imports jax.
    """

    flag: str                       # "--dispatch"
    param: str                      # run() keyword ("dispatch")
    parse: Callable[[Optional[str]], object]
    skip_reason: str                # "no dispatch knob"
    pre_import: bool = False
    apply: Optional[Callable[[object], None]] = None


def _parse_dispatch(value):
    # deferred import: DISPATCH_MODES lives behind jax, which must not
    # load before the pre_import knobs have been applied
    from repro.core.api import DISPATCH_MODES

    # same loud-typo contract as unknown benchmark names, mirroring
    # core.api's own validation
    if value is None or value not in DISPATCH_MODES:
        raise KnobError(
            f"unknown dispatch mode {value!r}: expected one of "
            f"{', '.join(DISPATCH_MODES)}"
        )
    return value


def _parse_seed(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        raise KnobError(f"--seed expects an integer, got {value!r}")


def _parse_devices(value):
    try:
        devices = int(value)
        if devices < 1:
            raise ValueError
    except (TypeError, ValueError):
        raise KnobError(
            f"--devices expects a positive integer, got {value!r}")
    return devices


def _apply_devices(devices):
    # the host platform device count is fixed at backend init — this
    # must run before the first jax import anywhere in the process
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()


def _parse_ckpt_dir(value):
    # validation lives in parse (apply only runs for pre_import knobs):
    # the directory must exist and be writable BEFORE any benchmark
    # runs, so a bad path fails with rc 2 instead of mid-benchmark
    if not value:
        raise KnobError("--ckpt-dir expects a directory path")
    try:
        os.makedirs(value, exist_ok=True)
    except OSError as e:
        raise KnobError(
            f"--ckpt-dir {value!r} is not a usable directory: {e}")
    if not os.access(value, os.W_OK):
        raise KnobError(f"--ckpt-dir {value!r} is not writable")
    return value


def _parse_kill_round(value):
    try:
        r = int(value)
        if r < 1:
            raise ValueError
    except (TypeError, ValueError):
        raise KnobError(
            f"--kill-round expects a positive integer, got {value!r}")
    return r


KNOBS = (
    Knob("--dispatch", "dispatch", _parse_dispatch, "no dispatch knob"),
    Knob("--seed", "seed", _parse_seed, "no seed knob"),
    Knob("--devices", "devices", _parse_devices, "no devices knob",
         pre_import=True, apply=_apply_devices),
    Knob("--ckpt-dir", "ckpt_dir", _parse_ckpt_dir, "no ckpt_dir knob"),
    Knob("--kill-round", "kill_round", _parse_kill_round,
         "no kill_round knob"),
)


def consume_knob(args: list, knob: Knob):
    """Pop ``knob.flag VALUE`` from ``args``; ``(value, rest)`` or
    ``(None, args)`` when the flag is absent.  Raises :class:`KnobError`
    on an invalid (or missing) value."""
    if knob.flag not in args:
        return None, args
    at = args.index(knob.flag)
    raw = args[at + 1] if at + 1 < len(args) else None
    return knob.parse(raw), args[:at] + args[at + 2:]


# pre_import knobs take effect NOW, before the benchmark imports below
# pull in jax
PRE_VALUES = {}
for _knob in (k for k in KNOBS if k.pre_import):
    try:
        _val, _rest = consume_knob(sys.argv[1:], _knob)
    except KnobError as e:
        print(e, file=sys.stderr)
        sys.exit(2)
    if _val is not None:
        sys.argv = sys.argv[:1] + _rest
        PRE_VALUES[_knob.param] = _val
        if _knob.apply is not None:
            _knob.apply(_val)

from benchmarks import (  # noqa: E402  (after the pre_import phase)
    adaptive_budget,
    async_rounds,
    dispatch_bench,
    fault_recovery,
    fig1_right,
    fig2_left,
    fig2_right,
    hetero_frontier,
    kernel_bench,
    lambda_decay,
    lossy_channels,
    roofline_table,
    serve_stream,
    shard_scale,
    theory_bounds,
    tiered_m64,
    triggered_lm,
)

ALL = {
    "fig2_left": fig2_left.run,        # paper Fig 2 (Left)
    "fig2_right": fig2_right.run,      # paper Fig 2 (Right)
    "fig1_right": fig1_right.run,      # paper Fig 1 (Right)
    "theory_bounds": theory_bounds.run,  # Thm 1 / Thm 2 table
    "lambda_decay": lambda_decay.run,  # beyond-paper: diminishing λ
    "hetero_frontier": hetero_frontier.run,  # beyond-paper: m=8 mixed policies
    "tiered_m64": tiered_m64.run,      # beyond-paper: m=64 tier-mix frontiers
    "adaptive_budget": adaptive_budget.run,  # beyond-paper: closed-loop λ
    "lossy_channels": lossy_channels.run,  # beyond-paper: lossy wires (repro.net)
    "async_rounds": async_rounds.run,  # beyond-paper: latency wires + churn
    "fault_recovery": fault_recovery.run,  # crash-resume + retx-vs-regate
    "dispatch_bench": dispatch_bench.run,  # unroll/switch/hybrid step+compile
    "shard_scale": shard_scale.run,    # fleet sharding vs single-device vmap
    "serve_stream": serve_stream.run,  # FleetSession serving throughput
    "triggered_lm": triggered_lm.run,  # beyond-paper: trigger on real arch
    "kernel_bench": kernel_bench.run,  # kernel traffic model
    "roofline_table": roofline_table.run,  # §Roofline from dry-run cache
}


def describe(fn) -> str:
    """First docstring sentence of the module defining ``fn``."""
    doc = inspect.getdoc(sys.modules[fn.__module__]) or ""
    head = doc.split("\n\n", 1)[0].replace("\n", " ").strip()
    return head


def list_benchmarks() -> int:
    smoke_ready = {
        n for n, fn in ALL.items()
        if "smoke" in inspect.signature(fn).parameters
    }
    undocumented = []
    for name, fn in ALL.items():
        tag = " [smoke]" if name in smoke_ready else ""
        desc = describe(fn)
        if not desc:
            undocumented.append(name)
        print(f"{name:17s}{tag:8s} {desc}")
    if undocumented:
        # the catalogue's no-drift promise: every benchmark module MUST
        # carry the docstring this listing is sourced from
        print(
            f"benchmark module(s) missing a docstring: "
            f"{', '.join(undocumented)}",
            file=sys.stderr,
        )
        return 1
    return 0


def main() -> int:
    args = sys.argv[1:]
    if "--list" in args:
        stray = [a for a in args if a != "--list"]
        for param, value in PRE_VALUES.items():
            # pre_import knobs were consumed at module top; keep the
            # --list contract honest anyway
            stray.append(f"--{param} {value}")
        if stray:
            # same loud-typo contract as the run path: --list takes no
            # other arguments, so reject them instead of silently
            # ignoring what may have been meant to run
            print(
                f"--list takes no other arguments, got: "
                f"{', '.join(map(repr, stray))}",
                file=sys.stderr,
            )
            return 2
        return list_benchmarks()
    smoke = "--smoke" in args
    values = dict(PRE_VALUES)
    for knob in KNOBS:
        if knob.pre_import:
            continue
        try:
            val, args = consume_knob(args, knob)
        except KnobError as e:
            print(e, file=sys.stderr)
            return 2
        if val is not None:
            values[knob.param] = val
    names = [a for a in args if a != "--smoke"] or list(ALL)
    # reject unknown names (and stray flags, which land here too) UP
    # FRONT, on stderr, before anything runs: a typo'd CI invocation
    # must fail loudly, not green-run the benchmarks it happened to
    # spell correctly
    unknown = [n for n in names if n not in ALL]
    if unknown:
        print(
            f"unknown benchmark(s): {', '.join(map(repr, unknown))}; "
            f"available: {', '.join(ALL)}",
            file=sys.stderr,
        )
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    failures = []
    ran = 0
    for name in names:
        fn = ALL[name]
        params = inspect.signature(fn).parameters
        if smoke and "smoke" not in params:
            # never silently fall back to a full-size, claim-asserting
            # run under --smoke
            print(f"\n===== {name} =====\n[{name}] SKIPPED: no smoke mode",
                  flush=True)
            continue
        # generated from the registry: a benchmark that does not take an
        # active knob must not silently run on its defaults (an
        # unsharded benchmark timed on a carved-up host platform, a
        # baked-in random stream under --seed, ... ) — skip it loudly
        missing = [k for k in KNOBS
                   if k.param in values and k.param not in params]
        if missing:
            for k in missing:
                print(f"\n===== {name} =====\n[{name}] SKIPPED: "
                      f"{k.skip_reason}", flush=True)
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        ran += 1
        try:
            kw = dict(smoke=True) if smoke else {}
            kw.update({p: v for p, v in values.items() if p in params})
            fn(verbose=True, **kw)
            print(f"[{name}] OK in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:
            failures.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    skipped = len(names) - ran
    reasons = "/".join(["smoke"] + [k.param for k in KNOBS])
    print(f"\n{ran - len(failures)}/{ran} benchmarks passed"
          + (f" ({skipped} skipped: no {reasons} knob)" if skipped else ""))
    # a run that executed nothing (every name skipped) must not go green
    return 1 if failures or ran == 0 else 0


if __name__ == "__main__":
    raise SystemExit(main())
