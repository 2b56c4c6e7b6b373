#!/usr/bin/env python3
"""Bring-up check: the system's main paths, end to end, on a TPU.

    python chip_smoke.py              # one chip: kernel, fleet and LM phases
    python chip_smoke.py --chips 4    # the sharded fleet on four chips, alone

Every phase drives the system through the entry points a user calls,
with inputs made from ``--seed``, and compares what comes out with a
plain float32 reference; a mismatch raises.  Earlier lines report each
phase's compile seconds, steady time per step and results, labelled
with the device kind.  The last line of standard output is one JSON
object naming the device, printed only when every phase passed.  There
is no CPU fallback: without a TPU the script exits non-zero before any
phase runs.

Everything runs in this one process (a chip belongs to one process at a
time).  The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or,
unset, ``<checkout>/.jax_cache/`` (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# the fleet's reference runs on the host CPU beside the chip
_PLATFORMS = os.environ.get("JAX_PLATFORMS")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# -- phase sizes (one TPU v5e chip, 16 GB HBM) --------------------------
# The fleet is `serve.py --fleet`'s default scenario, tiered_m64_adaptive
# (m=64 agents, n=32); the checkpoint is cut at FLEET_CKPT_ROUND.
FLEET_ROUNDS = 300
FLEET_CKPT_ROUND = 120
# smollm-135m at published widths (30 layers, d_model 576, vocab 49152).
# The compile rehearsal for a v5e put seq 2048 over HBM (m=2: 17.52 of
# 15.75 GB; the f32 attention probabilities kept for the backward pass
# are 30·m·9·S² words), so the sequence is cut to 1024: 7.25 GB peak.
LM_AGENTS = 2
LM_BATCH = 1  # per agent
LM_SEQ = 1024
LM_STEPS = 4
LM_LR = 0.05
# grad_norm's gate reads ‖g‖² from the Pallas gain_reduce kernel
LM_SPEC = "grad_norm(mu=1.0,kernel=true)|int8+ef"
# the flattened smollm-135m gradient, in (8, 128) tiles
KERNEL_TILES = 131072
KERNEL_AGENTS = 2

# -- tolerances ---------------------------------------------------------
# f32 sums of ~1.3e8 products, blocked in the kernel and tree-reduced by
# XLA: each side's rounding error is a few ulp (2^-24) times log2(n)
# times Σ|terms|, well under 1e-6 of Σ|terms|; 1e-5 leaves margin.
KERNEL_RTOL = 1e-5
# bf16 compute against the f32 forward: bf16 keeps 8 significant bits
# (2^-9 ≈ 2e-3 relative per rounding); the loss is a mean over every
# token, so roundings average out well below that.
LM_LOSS_RTOL = 1e-2
# TPU f32 matmuls default to one bf16 pass, so single trigger decisions
# can flip against the CPU run and the trajectories part; compare the
# fleet's aggregates, not its rounds.  Across seeds 0-3 on the CPU the
# 50-round tail loss spans 0.499-0.526 (±3% of its mean) and the
# transmit rate 0.6886-0.6904: a same-seed run that parts by flipped
# decisions stays closer than independent seeds, so twice and five
# times those spreads bound it.
FLEET_TAIL = 50  # rounds in the final-loss mean
FLEET_LOSS_RTOL = 0.1
FLEET_TX_ATOL = 0.01
# the sharded step against the one-chip hybrid step: the bound
# tests/test_shard_fleet.py holds them to (the gateway reduce
# re-associates the center sum)
SHARD_RTOL = 5e-6
SHARD_ROUNDS = 6


def _say(kind: str, phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {kind}: {body}", flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# ----------------------------------------------------------------------
# kernel: gain_reduce against the jnp sums of its reference
# ----------------------------------------------------------------------


def kernel_phase(seed: int, kind: str, *, tiles: int = KERNEL_TILES,
                 agents: int = KERNEL_AGENTS) -> dict:
    from repro.kernels.gain_reduce import ops, ref

    n = tiles * 1024
    kg, kh = jax.random.split(jax.random.fold_in(jax.random.key(seed), 1))
    g = jax.random.normal(kg, (agents, n), jnp.float32)
    h = jax.random.normal(kh, (agents, n), jnp.float32)
    fused = jax.jit(jax.vmap(ops.gain_reduce))
    t0 = time.perf_counter()
    got = jax.block_until_ready(fused(g, h))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
        got = jax.block_until_ready(fused(g, h))
    step_ms = (time.perf_counter() - t0) / 3 * 1e3
    one = jax.block_until_ready(ops.gain_reduce(g[0], h[0]))

    want = jax.jit(jax.vmap(ref.gain_reduce_ref))(g, h)
    # Σ|terms| of each sum: the scale its rounding error is measured in
    scale = jax.jit(jax.vmap(
        lambda a, b: (jnp.sum(a * a), jnp.sum(jnp.abs(a * b)))))(g, h)
    got, one, want, scale = jax.device_get((got, one, want, scale))
    err = max(
        float(np.max(np.abs(got[i] - want[i]) / scale[i])) for i in range(2)
    )
    err_one = max(
        abs(float(one[i]) - float(want[i][0])) / float(scale[i][0])
        for i in range(2)
    )
    _check(max(err, err_one) <= KERNEL_RTOL,
           f"gain_reduce off its reference by {max(err, err_one):.3g} "
           f"of Σ|terms| (limit {KERNEL_RTOL})")
    _say(kind, "kernel", elements=n, agents=agents,
         compile_s=f"{compile_s:.2f}", call_ms=f"{step_ms:.3f}",
         gsq=float(got[0][0]), ghg=float(got[1][0]), err_of_scale=f"{err:.3g}")
    return {"err": err}


# ----------------------------------------------------------------------
# fleet: the served linreg fleet, checkpoint-resume, CPU reference
# ----------------------------------------------------------------------


def _fleet(seed: int, options=None):
    from repro.launch.session import build_linreg_fleet_session

    losses: list = []
    sess = build_linreg_fleet_session(
        seed=seed, options=options,
        on_round=lambda k, m: losses.append(float(m["loss"])))
    return sess, losses


def _fleet_aggregates(sess, losses, rounds: int) -> tuple:
    snap = sess.rollup.snapshot()
    agents = sum(t["agents"] for t in snap["tiers"].values())
    tx_rate = float(snap["counters"]["num_tx"]) / (rounds * agents)
    return float(np.mean(losses[-FLEET_TAIL:])), tx_rate, snap


def fleet_phase(seed: int, kind: str, *, rounds: int = FLEET_ROUNDS,
                ckpt_round: int = FLEET_CKPT_ROUND) -> dict:
    from repro.launch.session import SessionOptions

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sess, losses = _fleet(seed)
        t0 = time.perf_counter()
        sess.run(1)  # the first round compiles the step
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sess.run(rounds - 1)
        round_ms = (time.perf_counter() - t0) / (rounds - 1) * 1e3
    donation = [str(w.message) for w in caught if "donated" in str(w.message)]
    _check(not donation, f"TrainState donation failed: {donation[:1]}")
    loss_tail, tx_rate, snap = _fleet_aggregates(sess, losses, rounds)

    # a full CommRollup snapshot, and a loss that fell
    _check(snap["rounds"] == rounds, f"rollup saw {snap['rounds']} rounds")
    _check("budget_violation_rounds" in snap, "snapshot lacks violations")
    for name, tier in snap["tiers"].items():
        missing = {"tx_rate", "bytes_per_agent_round", "lam_ewma",
                   "violations"} - set(tier)
        _check(not missing, f"tier {name} snapshot lacks {sorted(missing)}")
    _check(all(np.isfinite(losses)), "non-finite fleet loss")
    _check(loss_tail < 0.5 * losses[0],
           f"fleet loss did not fall: {losses[0]} -> {loss_tail}")

    # checkpoint at ckpt_round, resume in this process, finish the run:
    # the continued lineage must equal the uninterrupted one bitwise
    with tempfile.TemporaryDirectory() as ckpt_dir:
        opts = SessionOptions(ckpt_dir=ckpt_dir)
        first, _ = _fleet(seed, opts)
        first.run(ckpt_round)
        first.checkpoint()
        resumed, tail_losses = _fleet(seed, opts)
        _check(resumed.round_index == ckpt_round,
               f"resumed at round {resumed.round_index}, not {ckpt_round}")
        resumed.run(rounds - ckpt_round)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(sess.state)),
                    jax.tree_util.tree_leaves(jax.device_get(resumed.state)),
                    strict=True):
        _check(np.array_equal(a, b), "resumed state differs from the "
               "uninterrupted run")
    _check(tail_losses == losses[ckpt_round:],
           "resumed losses differ from the uninterrupted run")

    # the same rounds on the host CPU, in this process
    with jax.default_device(jax.devices("cpu")[0]):
        ref, ref_losses = _fleet(seed)
        ref.run(rounds)
    ref_tail, ref_tx, _ = _fleet_aggregates(ref, ref_losses, rounds)
    _check(_rel(loss_tail, ref_tail) <= FLEET_LOSS_RTOL,
           f"final loss {loss_tail} vs CPU {ref_tail}")
    _check(abs(tx_rate - ref_tx) <= FLEET_TX_ATOL,
           f"transmit rate {tx_rate} vs CPU {ref_tx}")
    _say(kind, "fleet", rounds=rounds, compile_s=f"{compile_s:.2f}",
         round_ms=f"{round_ms:.3f}", loss_first=losses[0],
         loss_tail=loss_tail, cpu_loss_tail=ref_tail, tx_rate=tx_rate,
         cpu_tx_rate=ref_tx, resume_round=ckpt_round, resume="bitwise")
    return {"loss_tail": loss_tail, "cpu_loss_tail": ref_tail,
            "tx_rate": tx_rate, "cpu_tx_rate": ref_tx, "snapshot": snap}


# ----------------------------------------------------------------------
# LM: smollm-135m triggered training through launch/steps.py
# ----------------------------------------------------------------------


def lm_phase(seed: int, kind: str, *, cfg=None, agents: int = LM_AGENTS,
             batch: int = LM_BATCH, seq: int = LM_SEQ,
             steps: int = LM_STEPS) -> dict:
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.api import init_train_state
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.optim import optimizers as opt_lib

    cfg = cfg or get_config("smollm-135m")
    mesh = make_host_mesh()
    shape = InputShape("chip_smoke", seq_len=seq,
                       global_batch=agents * batch, kind="train")
    plan = S.plan_run(cfg, shape, mesh, comm=LM_SPEC, optimizer="sgd",
                      lr=LM_LR, agents=agents)
    jitted, *_ = S.build_train_step(mesh, plan, compute_dtype="bfloat16")
    model = build(plan.cfg.replace(compute_dtype="bfloat16"))
    kp, kd = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2))
    params, _ = model.init(kp, dtype=jnp.bfloat16)
    state = init_train_state(params, opt_lib.from_config(plan.train_cfg),
                             plan.train_cfg)
    toks = jax.random.randint(kd, (steps, agents, batch, seq + 1), 0,
                              cfg.vocab_size, jnp.int32)
    batches = [{"tokens": toks[i, ..., :-1], "labels": toks[i, ..., 1:]}
               for i in range(steps)]

    # the plain float32 forward of the step-0 parameters and batch
    ref_model = build(plan.cfg.replace(compute_dtype="float32"))
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(lambda p, b: jnp.mean(
            jax.vmap(ref_model.loss_fn, in_axes=(None, 0))(p, b)))(
                p32, batches[0]))
    del p32

    t0 = time.perf_counter()
    compiled = jitted.lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    if jax.devices()[0].platform == "tpu":
        _check("tpu_custom_call" in compiled.as_text(),
               "the gain_reduce kernel is missing from the train step")
    peak_gb = compiled.memory_analysis().peak_memory_in_bytes / 2**30
    metrics = []
    times = []
    for b in batches:
        t0 = time.perf_counter()
        state, m = compiled(state, b)
        m = jax.device_get(m)
        times.append(time.perf_counter() - t0)
        metrics.append(m)
    losses = [float(m["loss"]) for m in metrics]
    _check(all(np.isfinite(losses)), f"non-finite LM loss {losses}")
    _check(all(np.isfinite(float(m["grad_norm"])) for m in metrics),
           "non-finite LM gradient")
    err = _rel(losses[0], ref_loss)
    _check(err <= LM_LOSS_RTOL,
           f"step-0 loss {losses[0]} vs f32 forward {ref_loss}")
    step_ms = float(np.mean(times[1:])) * 1e3 if steps > 1 else float("nan")
    _say(kind, "lm", arch=cfg.name, layers=cfg.num_layers,
         d_model=cfg.d_model, vocab=cfg.vocab_size, agents=agents,
         batch_per_agent=batch, seq=seq, comm=f"'{LM_SPEC}'",
         compile_s=f"{compile_s:.2f}", step_ms=f"{step_ms:.1f}",
         peak_hbm_gb=f"{peak_gb:.2f}", losses=[round(x, 4) for x in losses],
         f32_loss=round(ref_loss, 4), rel_err=f"{err:.2e}",
         comm_rate=[float(m["comm_rate"]) for m in metrics])
    return {"losses": losses, "ref_loss": ref_loss, "rel_err": err}


# ----------------------------------------------------------------------
# four chips: the fleet-sharded step against the one-chip hybrid step
# ----------------------------------------------------------------------


def shard_phase(seed: int, kind: str, *, chips: int = 4,
                rounds: int = SHARD_ROUNDS) -> dict:
    from repro.configs.base import TrainConfig
    from repro.configs.paper_linreg import TIER_MIXES, TIERED_M64_CFG
    from repro.core import regression as R
    from repro.core.api import (
        StepOptions,
        init_train_state,
        make_triggered_train_step,
    )
    from repro.launch.mesh import make_fleet_mesh
    from repro.optim import optimizers as opt_lib

    mesh = make_fleet_mesh(chips)
    problem = R.make_problem(TIERED_M64_CFG, jax.random.key(seed))

    def loss_fn(params, batch):
        xs, ys = batch
        r = xs @ params["w"] - ys
        return 0.5 * jnp.mean(r * r)

    worst = 0.0
    for net in TIER_MIXES:
        cfg = TrainConfig(lr=TIERED_M64_CFG.stepsize, optimizer="sgd",
                          num_agents=net.num_agents,
                          comm=net.policies(lam_base=1.0))
        opt = opt_lib.from_config(cfg)
        with warnings.catch_warnings():
            # a fleet that cannot shard would silently replicate
            warnings.filterwarnings("error", message="agent axis of size")
            step_sh = jax.jit(make_triggered_train_step(
                loss_fn, opt, cfg,
                options=StepOptions(mesh=mesh, agent_metrics=True)))
        step_ref = jax.jit(make_triggered_train_step(
            loss_fn, opt, cfg, options=StepOptions(
                hetero_dispatch="hybrid", barriers=False,
                agent_metrics=True)))
        params = {"w": jnp.zeros((TIERED_M64_CFG.n,), jnp.float32)}
        s_ref = init_train_state(params, opt, cfg)
        s_sh = init_train_state(params, opt, cfg)
        batches = [R.agent_batches(problem, jax.random.fold_in(
            jax.random.key(seed + 1), i)) for i in range(rounds)]
        hlo = step_sh.lower(s_sh, batches[0]).compile().as_text()
        _check("all-reduce" in hlo,
               f"{net.name}: no gateway all-reduce in the sharded program")
        times = []
        for b in batches:
            s_ref, m_ref = step_ref(s_ref, b)
            t0 = time.perf_counter()
            s_sh, m_sh = jax.block_until_ready(step_sh(s_sh, b))
            times.append(time.perf_counter() - t0)
        per_agent = jax.tree_util.tree_leaves(
            (s_sh.ef_memory, s_sh.ctrl_state, s_sh.net_state,
             m_sh["agent_tx"]))
        for x in per_agent:
            _check(len(x.sharding.device_set) == chips
                   and not x.sharding.is_fully_replicated,
                   f"{net.name}: per-agent state not split over {chips} "
                   f"devices ({x.sharding})")
        ref_leaves = jax.tree_util.tree_leaves(jax.device_get((s_ref, m_ref)))
        sh_leaves = jax.tree_util.tree_leaves(jax.device_get((s_sh, m_sh)))
        _check(len(ref_leaves) == len(sh_leaves),
               f"{net.name}: state trees differ")
        rel = 0.0
        for x, y in zip(ref_leaves, sh_leaves):
            a, b_ = np.asarray(x, np.float64), np.asarray(y, np.float64)
            if a.size:
                d = float(np.max(np.abs(a - b_)))
                rel = max(rel, d / max(1.0, float(np.max(np.abs(a)))))
        _check(rel < SHARD_RTOL, f"{net.name}: sharded step off the hybrid "
               f"step by {rel:.3g} (limit {SHARD_RTOL})")
        worst = max(worst, rel)
        # the first two calls compile: once for the initial state on one
        # device, once for the state the step hands back split over chips
        compile_s = times[0] + times[1]
        step_ms = float(np.median(times[2:])) * 1e3
        _say(kind, "shard", fleet=net.name, agents=net.num_agents,
             gateways=chips, compile_s=f"{compile_s:.2f}",
             step_ms=f"{step_ms:.3f}", max_rel_diff=f"{rel:.3g}",
             all_reduce="yes", per_agent_devices=chips)
    return {"max_rel": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet phase, on four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) visible", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    if args.chips == 4:
        shard_phase(args.seed, kind, chips=4)
    else:
        kernel_phase(args.seed, kind)
        fleet_phase(args.seed, kind)
        lm_phase(args.seed, kind)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
