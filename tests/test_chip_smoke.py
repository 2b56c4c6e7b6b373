"""chip_smoke.py on the CPU: it refuses to run, and its phases work.

The script's phases only run for real on a TPU; here they run at toy
sizes (Pallas in interpret mode) so that a change that breaks one of
them fails tier-1 instead of the next chip run.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "cpu" in r.stderr
    assert '"ok": true' not in r.stdout


def test_kernel_phase_matches_reference(smoke):
    # 300 tiles span two grid steps of the kernel
    out = smoke.kernel_phase(0, "cpu", tiles=300, agents=2)
    assert out["err"] <= smoke.KERNEL_RTOL


def test_fleet_phase_resumes_bitwise_and_matches_cpu(smoke):
    out = smoke.fleet_phase(0, "cpu", rounds=60, ckpt_round=20)
    assert out["snapshot"]["rounds"] == 60
    assert out["loss_tail"] == out["cpu_loss_tail"]


def test_lm_phase_matches_f32_forward(smoke):
    from repro.configs import get_config, reduced

    out = smoke.lm_phase(0, "cpu", cfg=reduced(get_config("smollm-135m")),
                         agents=2, batch=1, seq=32, steps=2)
    assert len(out["losses"]) == 2
    assert out["rel_err"] <= smoke.LM_LOSS_RTOL


def test_shard_phase_on_four_host_devices():
    """The four-chip phase on four forced host devices (the test process
    itself is pinned to one, so it runs in a child)."""
    code = (
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(SCRIPT)!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "print(json.dumps(cs.shard_phase(0, 'cpu', chips=4)))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["max_rel"] < 5e-6
