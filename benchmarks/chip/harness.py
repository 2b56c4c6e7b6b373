"""One cell, one run: resolve the cell by name, run it, reduce, report.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json`` — the configuration as it is run; its
  ``kind`` names the runner ``kinds/<kind>.py``, and its plain reference
  lies beside the file ``BENCHMARK.json`` names, ``<file>.json`` →
  ``<file>.ref.py``.
* ``traffic/<mix>.json`` — read by the one generator, ``traffic.py``.
* ``metrics/<metric>.py`` — a reader ``read(ctx) -> float | None``.
* ``limits/<cell>.json`` — the limit of each number that decides
  ``correct``, with the readings it was set from in ``PERF.md``.

A later cell adds such files and ``BENCHMARK.json`` entries; no file
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
import sys
import tempfile
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names hold ``.``/``-``)."""
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    name = path.relative_to(HERE) if path.is_relative_to(HERE) else path
    key = "chipbench_" + re.sub(r"\W", "_", str(name))
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    return _module(HERE / "metrics" / f"{name}.py")


def runner(kind: str) -> ModuleType:
    return _module(HERE / "kinds" / f"{kind}.py")


def reference(config_file: Path) -> ModuleType:
    """The plain reference beside a configuration's file."""
    path = Path(config_file)
    return _module(path.with_name(path.name.removesuffix(".json")
                                  + ".ref.py"))


def resolve(workload: str, bench: Optional[dict] = None,
            root: Path = ROOT) -> SimpleNamespace:
    """Everything one cell needs, found by name."""
    from benchmarks.chip import traffic

    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[cell["config"]]
    cfg_file = (Path(root) / conf["file"]).resolve()
    cfg = json.loads(cfg_file.read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    limits_path = HERE / "limits" / f"{workload}.json"
    return SimpleNamespace(
        name=workload,
        config_name=cell["config"],
        cfg=cfg,
        mix_name=cell["traffic"],
        mix=traffic.load_mix(HERE / "traffic" / f"{cell['traffic']}.json"),
        chips=int(cell["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        limits=json.loads(limits_path.read_text()),
        kind=runner(cfg["kind"]),
        ref=reference(cfg_file),
    )


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; refuses a host without the chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(f"needs a TPU, found platform {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), "
                         f"{len(devices)} visible")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _finite(x) -> bool:
    return x is not None and isinstance(x, (int, float)) and math.isfinite(x)


def judge(checks: dict, limits: dict) -> tuple:
    """``correct`` and the compared numbers, each beside its limit.  A
    number that is missing, not finite or above its limit fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = checks.get(name)
        out[name] = {"value": value, "limit": limit}
        if not _finite(value) or value > limit:
            ok = False
    return ok, out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             cell: Optional[SimpleNamespace] = None,
             trace_dir: Optional[str] = None) -> dict:
    """Run ``workload`` once and return the result line's object.

    ``t_start`` is the process's start on the ``time.perf_counter``
    clock: set-up is counted from it.  ``cell`` lets a caller hand in a
    resolved cell (a test at toy size); ``require_tpu=False`` is for
    such callers only.
    """
    from benchmarks.chip import trace as T
    from repro.launch.compile_cache import enable_compile_cache

    cell = cell or resolve(workload)
    device = device_info(cell.chips, require_tpu)
    enable_compile_cache()
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        tdir = trace_dir or tmp
        out = cell.kind.run(cell, seed=seed, seconds=seconds, t_start=t_start,
                            trace_dir=tdir if trace else None)
        ctx = SimpleNamespace(cell=cell, out=out, device=device, trace=None)
        if trace:
            evs = T.load(T.find_xplane(tdir))
            lo, hi = T.window(evs)
            ctx.trace = SimpleNamespace(evs=evs, lo=lo, hi=hi)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        busy = T.busy_s(ctx.trace.evs, ctx.trace.lo, ctx.trace.hi)
        device["busy_s"] = busy
        device["window_s"] = (ctx.trace.hi - ctx.trace.lo) / 1e9
        result["breakdown"] = {
            "device_ops": T.top_ops(ctx.trace.evs, ctx.trace.lo, ctx.trace.hi),
            "idle_gaps": T.idle_gaps(ctx.trace.evs, ctx.trace.lo,
                                     ctx.trace.hi),
        }
    ok, checks = judge(out["checks"], cell.limits)
    result["correct"] = bool(ok and out["failed"] == 0)
    result["checks"] = checks  # last: the numbers compared, with limits
    return result
