#!/usr/bin/env python3
"""Readings behind the limits that decide ``correct``, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11,12,... --seconds 2 --controls 3

For each seed it runs the cell as the benchmark does (a short window)
and prints the numbers compared.  Then, on the first ``--controls``
seeds, it prints what the control reads (the plain reference computed
one precision below the configuration's, put in the program's place)
and what each planted fault reads, all against the same reference.
The benchmark's own runs never run this; its readings set the limits
in ``limits/<cell>.json``, and ``PERF.md`` records them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# JAX's persistent compilation cache lives at a fixed path inside this
# checkout, whatever the environment names: only a cell's first run in a
# checkout compiles, and two checkouts share nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    cell = harness.resolve(args.workload)
    harness.device_info(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        t0 = time.perf_counter()
        out = cell.kind.run(cell, seed=seed, seconds=args.seconds,
                            t_start=t0)
        print(json.dumps({"seed": seed, "side": "program",
                          "checks": out["checks"],
                          "attempted": out["attempted"],
                          "memory_peak_bytes": out["memory_peak_bytes"],
                          "setup_s": out["setup_s"],
                          "s_after_window": time.perf_counter() - out["t0"]
                          - args.seconds}), flush=True)
    for seed in seeds[: args.controls]:
        for side, checks in cell.kind.controls(cell, seed, args.seconds):
            print(json.dumps({"seed": seed, "side": side, "checks": checks}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
