"""Runner for served fleets: ``FleetSession`` trains on arrival, closed loop.

Set-up builds the session through the program's entry point
(``build_linreg_fleet_session``) from the seed and runs the mix's warm-up
rounds, which compile the step and the sampler's programs.  The window
runs the session's own loop on its thread (``start()`` / ``stop()``):
each round is sampled while the previous one runs, back to back.  Round
completion times come from the ``on_round`` callback.

After the window the whole lineage (warm-up and window rounds) is
replayed by the plain reference, and the final weights and every
round's transmit decisions are compared.
"""
from __future__ import annotations

import time

import numpy as np

# the step module's name in the device trace
STEP_MODULE = r"^jit_train_step\("


def check_program(cfg: dict) -> None:
    """The program's deployment must be the one the file describes."""
    from repro.configs import paper_linreg as PL

    net = getattr(PL, cfg["program"]["network"])
    prob = getattr(PL, cfg["program"]["problem"])
    got = {"n": prob.n, "num_agents": prob.num_agents,
           "samples_per_agent": prob.samples_per_agent,
           "stepsize": prob.stepsize, "noise_std": prob.noise_std,
           "cov_range": list(prob.cov_range), "w0": prob.w0_scale,
           "tiers": [(t.name, t.count, t.spec(1.0)) for t in net.tiers]}
    want = {k: cfg[k] for k in got if k != "tiers"}
    want["tiers"] = [(t["name"], t["count"], t["policy"])
                     for t in cfg["tiers"]]
    if got != want or prob.cov_diag or prob.w_star:
        raise ValueError(f"the program's fleet differs from the "
                         f"configuration file: {got} != {want}")


def compare(cfg: dict, got: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``: the final weights' largest
    gap over the reference's largest weight; the share of the (round,
    agent) transmit decisions that differ from the reference's; the
    largest relative gap of a tier's transmit count; and the same of a
    tier's wire bytes, as the program's rollup prices them."""
    w, w_ref = got["w"], ref["w"]
    seen, seen_ref = np.asarray(got["decisions"]), ref["decisions"]
    tx, tx_ref = np.asarray(got["tier_tx"], np.float64), ref["tier_tx"]
    by, by_ref = (np.asarray(got["tier_bytes"], np.float64),
                  ref["tier_bytes"])
    return {
        "bytes_gap": float(np.max(np.abs(by - by_ref)
                                  / np.maximum(by_ref, 1.0))),
        "w_rel": float(np.max(np.abs(w - w_ref)) / max(np.max(np.abs(w_ref)),
                                                       1e-30)),
        "tx_mismatch": (float(np.mean(np.abs(seen - seen_ref)))
                        if seen.shape == seen_ref.shape else float("nan")),
        "tx_gap": float(np.max(np.abs(tx - tx_ref) / np.maximum(tx_ref, 1.0))),
    }


def run(cell, *, seed: int, seconds: float, t_start: float, trace_dir=None):
    import jax

    from benchmarks.chip import trace as T
    from benchmarks.chip.harness import memory_peak_bytes
    from benchmarks.chip.traffic import program_seed
    from repro.launch.session import build_linreg_fleet_session

    cfg = cell.cfg
    check_program(cfg)
    pseed = program_seed(seed)
    stamps: list = []
    decisions: list = []
    bad = [0]

    def on_round(k, metrics):
        stamps.append(time.perf_counter())
        decisions.append(metrics["agent_tx"])
        if not np.isfinite(metrics["loss"]):
            bad[0] += 1

    sess = build_linreg_fleet_session(seed=pseed, on_round=on_round)
    sess.run(int(cell.mix["warmup"]))
    warm = len(stamps)
    bad[0] = 0
    with T.recording(trace_dir), T.window_mark():
        t0 = time.perf_counter()
        sess.start()
        time.sleep(seconds)
        sess.stop()
    t_end = t0 + seconds
    done = [s for s in stamps[warm:] if s <= t_end]
    mem = memory_peak_bytes(cell.chips)

    rounds = sess.round_index
    state = jax.device_get(sess.state)
    rollup = sess.rollup.state_dict()
    got = {"w": np.asarray(state.params["w"], np.float64),
           "decisions": np.asarray(decisions, np.float64),
           "tier_tx": rollup["tier_tx"], "tier_bytes": rollup["tier_bytes"]}
    del sess, state
    ref = cell.ref.run(cfg, pseed, rounds)
    return {
        "setup_s": t0 - t_start,
        "window_s": seconds,
        "t0": t0,
        "completions": done,
        "attempted": len(done),
        "failed": bad[0],
        "memory_peak_bytes": mem,
        "checks": compare(cfg, got, ref),
        "rounds_total": rounds,
        "step_module": STEP_MODULE,
    }


# served rounds per second assumed when the control replays a window's
# worth of rounds (the cell's own size) without running the program
CONTROL_ROUNDS_PER_S = 75


def controls(cell, seed: int, seconds: float):
    """What the control and the planted faults read, against the
    reference, over a window's worth of rounds: ``(side, checks)``."""
    import jax.numpy as jnp

    from benchmarks.chip.traffic import program_seed

    cfg, ref_mod = cell.cfg, cell.ref
    pseed = program_seed(seed)
    rounds = int(cell.mix["warmup"]) + int(CONTROL_ROUNDS_PER_S * seconds)
    ref = ref_mod.run(cfg, pseed, rounds)
    yield "control", compare(cfg, ref_mod.run(cfg, pseed, rounds,
                                              dtype=jnp.bfloat16), ref)
    full = ref_mod.round_batch

    def half(*a, **k):
        xs, ys = full(*a, **k)
        n = xs.shape[1] // 2
        return xs[:, :n], ys[:, :n]

    ref_mod.round_batch = half
    try:
        yield "fault:half_batch", compare(cfg, ref_mod.run(cfg, pseed, rounds),
                                          ref)
    finally:
        ref_mod.round_batch = full
    frozen = dict(ref, w=np.full_like(ref["w"], cfg["w0"]))
    yield "fault:state_unchanged", compare(cfg, frozen, ref)
    # agent 8 (a metro agent) reports each decision flipped
    flipped = ref["decisions"].copy()
    flipped[:, 8] = 1.0 - flipped[:, 8]
    tier_tx = ref["tier_tx"].copy()
    tier_tx[1] += float(np.sum(flipped[:, 8] - ref["decisions"][:, 8]))
    tier_bytes = ref["tier_bytes"] * tier_tx / np.maximum(ref["tier_tx"], 1.0)
    yield "fault:answer_altered", compare(
        cfg, dict(ref, decisions=flipped, tier_tx=tier_tx,
                  tier_bytes=tier_bytes), ref)
    # the sensor tier's top-k payload priced without its 32 index bits
    mispriced = ref["tier_bytes"].copy()
    mispriced[-1] *= 8.0 / 40.0
    yield "fault:mispriced_tier", compare(
        cfg, dict(ref, tier_bytes=mispriced), ref)
