"""Runner for language-model training through the triggered step.

Set-up builds the program's step with ``launch.steps.plan_run`` and
``build_train_step`` and compiles it ahead of time; the weights and the
token batches are made on the device from the seed.  That one compiled
step and its state then run the first steps (those the reference
follows, on distinct batches) and go on into the window, which
dispatches the step back to back, pulling each step's metrics while the
next one runs.

After the window the reference follows the first ``CHECK_STEPS`` steps
from the same weights and batches; compared are each step's loss and
mean trigger gain (the gain-reduce kernel's squared norm, or the
lookahead probe's loss change), a controller's per-agent state after
each step, the norm of the first aggregate the optimizer gets, and per
leaf the norm of the parameters' change over those steps.

Everything particular to a model comes from its configuration's own
files, so a new architecture is added as files and no code here
changes.  ``configs/<name>.json`` names the program's architecture
(``program.arch``), the attributes the program's config must show
(``program.expect``), where each of the reference's leaves sits in the
program's parameter tree (``leaves``) and the CPU tests' cut (``toy``).
The reference module beside it, ``configs/<name>.ref.py``, gives
``init_params``, ``loss``, ``train`` (``lm_reference.train`` of that
loss), ``train_flops_per_token`` and ``param_count``.
"""
from __future__ import annotations

import time

import numpy as np

CHECK_STEPS = 3
STEP_MODULE = r"^jit_train_step\("
# triggers whose gain needs one more forward pass (the lookahead probe)
PROBE_TRIGGERS = ("gain_lookahead", "budget_dual", "budget_window")


def trigger_of(comm: str) -> str:
    """The trigger's name in a ``comm`` spec string."""
    return comm.split("(")[0].split("|")[0].strip()


def to_program(canon: dict, leaves: dict) -> dict:
    """The reference's flat leaves placed at their paths in the program's
    tree (the configuration's ``leaves``)."""
    out: dict = {}
    for name, path in leaves.items():
        node = out
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = canon[name]
    return out


def from_program(params: dict, leaves: dict) -> dict:
    out = {}
    for name, path in leaves.items():
        node = params
        for key in path:
            node = node[key]
        out[name] = node
    return out


def program_config(cfg: dict):
    """The program's model config, checked against the file: each
    attribute ``program.expect`` names must equal the file key it gives,
    or the fixed ``{"value": ...}``."""
    from repro.configs import get_config, reduced

    prog = cfg["program"]
    mc = get_config(prog["arch"])
    if prog.get("reduced"):
        # the repository's smoke-test cut of the same family (tests only)
        mc = reduced(mc)
    got = {attr: getattr(mc, attr) for attr in prog["expect"]}
    want = {attr: src["value"] if isinstance(src, dict) else cfg[src]
            for attr, src in prog["expect"].items()}
    if got != want:
        raise ValueError(f"the program's {mc.name} differs from the "
                         f"configuration file: {got} != {want}")
    return mc


def flops_per_token(cell) -> float:
    probe = trigger_of(cell.mix["comm"]) in PROBE_TRIGGERS
    return cell.ref.train_flops_per_token(cell.cfg, cell.cfg["seq_len"],
                                          int(probe))


def leaf_norms(a: dict, b: dict) -> dict:
    import jax.numpy as jnp

    return {k: float(jnp.linalg.norm(a[k].astype(jnp.float32)
                                     - b[k].astype(jnp.float32)))
            for k in a}


def compare(got: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    Leaves whose first aggregate in the reference is under a thousandth
    of the median leaf's are left out of the change (they move by
    rounding alone).  A leaf's gap is measured against the larger of its
    own reference change and the median leaf's.  A controller's rows
    ``(λ, σ, ĝ)`` are compared column by column, each against its
    largest reference value over the agents and steps."""
    agg = ref["agg_leaf_norm"]
    med_agg = float(np.median(list(agg.values())))
    keep = [k for k in agg if agg[k] >= 1e-3 * med_agg]
    dref = ref["dparam_leaf_norm"]
    med = float(np.median([dref[k] for k in keep]))
    dgap = max(abs(got["dparam_leaf_norm"][k] - dref[k])
               / max(dref[k], med, 1e-30) for k in keep)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"],
                                                       ref["loss"]))
    out = {
        "loss_gap": float(loss_gap),
        "gnorm_gap": abs(got["gnorm"][0] - ref["gnorm"][0]) / ref["gnorm"][0],
        "dparam_gap": float(dgap),
        "gain_gap": max(abs(a - b) / max(abs(b), 1e-30)
                        for a, b in zip(got["gain"], ref["gain"])),
    }
    if ref["ctrl"]:
        # each controller column against its largest reference value
        c, c_ref = np.asarray(got["ctrl"]), np.asarray(ref["ctrl"])
        scale = np.maximum(np.max(np.abs(c_ref), axis=(0, 1)), 1e-30)
        out["ctrl_gap"] = (float(np.max(np.abs(c - c_ref) / scale))
                           if c.shape == c_ref.shape else float("nan"))
    return out


def build(cell, pseed: int):
    """The program's compiled step, its initial state, and the batches."""
    import jax.numpy as jnp

    from benchmarks.chip.traffic import token_batches
    from repro.configs.base import InputShape
    from repro.core.api import init_train_state
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.optim import optimizers as opt_lib

    cfg, mix = cell.cfg, cell.mix
    tr = cfg["train"]
    mc = program_config(cfg)
    mesh = make_host_mesh()
    shape = InputShape("bench", seq_len=cfg["seq_len"],
                       global_batch=tr["agents"] * tr["batch_per_agent"],
                       kind="train")
    plan = S.plan_run(mc, shape, mesh, comm=mix["comm"],
                      optimizer=tr["optimizer"], lr=tr["lr"],
                      agents=tr["agents"])
    jitted, *_ = S.build_train_step(mesh, plan, compute_dtype=tr["dtype"])
    params = to_program(cell.ref.init_params(cfg, pseed, jnp.dtype(tr["dtype"])),
                        cfg["leaves"])
    state = init_train_state(params, opt_lib.from_config(plan.train_cfg),
                             plan.train_cfg)
    batches = token_batches(pseed, mix["tokens"], agents=tr["agents"],
                            batch=tr["batch_per_agent"],
                            seq_len=cfg["seq_len"], vocab=cfg["vocab_size"])
    compiled = jitted.lower(state, batches[0]).compile()
    return compiled, state, batches


def run(cell, *, seed: int, seconds: float, t_start: float, trace_dir=None):
    import jax

    from benchmarks.chip import trace as T
    from benchmarks.chip.harness import memory_peak_bytes
    from benchmarks.chip.traffic import program_seed

    cfg, mix = cell.cfg, cell.mix
    tr = cfg["train"]
    pseed = program_seed(seed)
    compiled, state, batches = build(cell, pseed)
    pool = len(batches)

    # the first steps, through the same compiled step, on distinct rows;
    # the mix's further warm-up steps follow them
    first, ctrl = [], []
    for i in range(max(CHECK_STEPS, int(mix["warmup"]))):
        state, m = compiled(state, batches[i % pool])
        first.append(jax.device_get(m))
        if i < CHECK_STEPS and state.ctrl_state is not None:
            ctrl.append(np.asarray(jax.device_get(state.ctrl_state)))
        if i + 1 == CHECK_STEPS:
            state_checked = state
    i += 1

    completions, pending, bad = [], None, 0
    with T.recording(trace_dir), T.window_mark():
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            state, m = compiled(state, batches[i % pool])
            i += 1
            if pending is not None:
                bad += not np.isfinite(float(jax.device_get(pending)["loss"]))
                completions.append(time.perf_counter())
            pending = m
        bad += not np.isfinite(float(jax.device_get(pending)["loss"]))
        completions.append(time.perf_counter())
    done = [c for c in completions if c <= t_end]
    mem = memory_peak_bytes(cell.chips)

    canon0 = cell.ref.init_params(cfg, pseed, jax.numpy.dtype(tr["dtype"]))
    got = {"loss": [float(m["loss"]) for m in first[:CHECK_STEPS]],
           "gnorm": [float(first[0]["grad_norm"])],
           "gain": [float(m["mean_gain"]) for m in first[:CHECK_STEPS]],
           "ctrl": ctrl,
           "dparam_leaf_norm": leaf_norms(
               from_program(state_checked.params, cfg["leaves"]), canon0)}
    del compiled, state, state_checked, m, pending
    # the reference takes its initial weights from the host
    canon0 = jax.device_get(canon0)
    ref = cell.ref.train(cfg, canon0, batches[:CHECK_STEPS], mix["comm"],
                         CHECK_STEPS)
    return {
        "setup_s": t0 - t_start,
        "window_s": seconds,
        "t0": t0,
        "completions": done,
        "attempted": len(done),
        "failed": int(bad),
        "memory_peak_bytes": mem,
        "checks": compare(got, ref),
        "tokens_per_step": tr["agents"] * tr["batch_per_agent"] * cfg["seq_len"],
        "flops_per_token": flops_per_token(cell),
        "step_module": STEP_MODULE,
        "gain_reduce": ({"elements": cell.ref.param_count(cfg),
                         "agents": tr["agents"]}
                        if "kernel=true" in mix["comm"].replace(" ", "")
                        else None),
    }


def controls(cell, seed: int, seconds: float):
    """What the control and the planted faults read, against the
    reference, on the cell's first steps: ``(side, checks)``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.traffic import program_seed, token_batches

    cfg, mix, ref_mod = cell.cfg, cell.mix, cell.ref
    tr = cfg["train"]
    pseed = program_seed(seed)
    canon0 = jax.device_get(ref_mod.init_params(cfg, pseed,
                                                jnp.dtype(tr["dtype"])))
    batches = token_batches(pseed, mix["tokens"], agents=tr["agents"],
                            batch=tr["batch_per_agent"],
                            seq_len=cfg["seq_len"],
                            vocab=cfg["vocab_size"])[:CHECK_STEPS]

    def train(bs, **kw):
        return ref_mod.train(cfg, canon0, bs, mix["comm"], CHECK_STEPS, **kw)

    ref = train(batches)
    yield "control", compare(train(batches, lowp=True), ref)
    half = [{k: v[..., : v.shape[-1] // 2] for k, v in b.items()}
            for b in batches]
    yield "fault:half_batch", compare(train(half), ref)
    solo = [{k: v[:1] for k, v in b.items()} for b in batches]
    yield "fault:no_exchange", compare(train(solo), ref)
    # the trigger's own answer altered where it is made: the kernel's
    # squared norm halved, or the lookahead probe's gain lost
    trig = trigger_of(mix["comm"])
    if trig == "grad_norm":
        yield "fault:gsq_halved", compare(train(batches, gain_scale=0.5), ref)
    elif trig in PROBE_TRIGGERS:
        yield "fault:probe_gain_zeroed", compare(
            train(batches, gain_scale=0.0), ref)
    yield "fault:loss_altered", compare(
        dict(ref, loss=[x * 1.01 for x in ref["loss"]]), ref)
    yield "fault:state_unchanged", compare(
        dict(ref, dparam_leaf_norm={k: 0.0 for k in ref["dparam_leaf_norm"]}),
        ref)
