import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) pair.

The two lines above MUST stay first — jax locks the device count at
first init, and the production meshes need 512 placeholder host devices.
Only this entry point sets the flag; tests and benchmarks see 1 device.

Per pair we record to ``experiments/dryrun/<arch>_<shape>_<mesh>[_<tag>].json``:

  * ``memory_analysis``  — bytes per device (argument/temp/output): the
    "does it fit v5e HBM" proof
  * ``cost_analysis``    — XLA's own flops/bytes (kept for reference;
    it undercounts ``while`` bodies)
  * ``hlo_cost``         — our trip-count-aware flops / HBM bytes /
    collective wire bytes (the roofline inputs, §Roofline)
  * ``roofline``         — the three terms + bottleneck + MFU bound

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all            # everything, subprocesses
  python -m repro.launch.dryrun --all --opt      # optimized variant (§Perf)
"""
# NOTE: no `from __future__ import annotations` here — the XLA_FLAGS lines
# must be the first statements in the module, which rules out future imports.
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def mesh_name(multi_pod: bool) -> str:
    return "pod2" if multi_pod else "pod1"


def run_one(arch: str, shape_name: str, multi_pod: bool, opt: bool, out_dir: Path) -> dict:
    import jax

    from repro.analysis import hlo_cost
    from repro.analysis.roofline import Roofline, model_flops
    from repro.configs import SHAPES, get_config
    from repro.launch import steps as S
    from repro.launch.mesh import make_production_mesh
    from repro.models import runs_shape

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    tag = "opt" if opt else "base"
    name = f"{arch}_{shape_name}_{mesh_name(multi_pod)}_{tag}"

    ok, reason = runs_shape(cfg, shape)
    if not ok:
        rec = {"name": name, "status": "skipped", "reason": reason}
        (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
        return rec

    t0 = time.time()
    kw = {}
    if opt:
        kw = dict(remat=True, attn_q_block=512)
        if shape.kind == "decode":
            # flash-decoding cache sharding (EXPERIMENTS.md §Perf pair b)
            kw = dict(cache_seq_shard=True)
    plan = S.plan_run(cfg, shape, mesh, **kw)
    lowered = S.lower_for(mesh, plan)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    cost = hlo_cost.analyze(hlo_text)

    roof = Roofline(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name(multi_pod),
        chips=chips,
        flops_per_device=cost.flops,
        bytes_per_device=cost.hbm_bytes,
        wire_bytes_per_device=cost.wire_bytes,
        model_flops_global=model_flops(plan.cfg, shape),
        collectives=cost.collectives,
        peak_memory_per_device=float(
            ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
        ),
    )
    rec = {
        "name": name,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name(multi_pod),
        "tag": tag,
        "chips": chips,
        "compile_seconds": round(t_compile, 1),
        "plan": {
            "fsdp": plan.fsdp,
            "num_agents": plan.num_agents,
            "agent_axes": list(plan.agent_axes),
            "remat": plan.cfg.remat,
            "attn_q_block": plan.cfg.attn_q_block,
            "swa_window": plan.cfg.swa_window,
        },
        "memory_analysis": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "total_bytes": int(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
            ),
        },
        "xla_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "hlo_cost": hlo_cost.summarize(cost),
        "roofline": roof.to_dict(),
    }
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--opt", action="store_true", help="remat+flash optimized variant")
    ap.add_argument("--all", action="store_true", help="all (arch × shape), subprocess per arch")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--force", action="store_true", help="recompute cached results")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from repro.configs import SHAPES, list_archs

    if args.all:
        failures = 0
        for arch in list_archs():
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--both-meshes", "--out", str(out_dir),
            ]
            if args.opt:
                cmd.append("--opt")
            if args.force:
                cmd.append("--force")
            print(f"=== {arch} ===", flush=True)
            r = subprocess.run(cmd, env={**os.environ, "PYTHONPATH": "src"})
            failures += r.returncode != 0
        return 1 if failures else 0

    archs = [args.arch] if args.arch else list(list_archs())
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [bool(args.multi_pod)]

    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = "opt" if args.opt else "base"
                name = f"{arch}_{shape_name}_{mesh_name(mp)}_{tag}"
                path = out_dir / f"{name}.json"
                if path.exists() and not args.force:
                    rec = json.loads(path.read_text())
                    print(f"[cached] {name}: {rec.get('status')}", flush=True)
                    continue
                try:
                    rec = run_one(arch, shape_name, mp, args.opt, out_dir)
                    if rec["status"] == "ok":
                        r = rec["roofline"]
                        print(
                            f"[ok] {name}: mem/dev="
                            f"{rec['memory_analysis']['total_bytes']/1e9:.2f}GB "
                            f"t_comp={r['t_compute_s']:.4f}s t_mem={r['t_memory_s']:.4f}s "
                            f"t_coll={r['t_collective_s']:.4f}s -> {r['bottleneck']} "
                            f"({rec['compile_seconds']}s compile)",
                            flush=True,
                        )
                    else:
                        print(f"[skip] {name}: {rec['reason']}", flush=True)
                except Exception as e:
                    n_fail += 1
                    print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                    (out_dir / f"{name}.json").write_text(
                        json.dumps({"name": name, "status": "error", "error": str(e)})
                    )
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
