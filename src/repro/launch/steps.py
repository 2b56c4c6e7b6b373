"""Step builders: sharded train/prefill/serve steps for any (arch × shape × mesh).

This is where the paper's technique meets the distribution substrate:

* ``plan_run`` decides FSDP, the agent axes (the paper's "agents" = the
  data-parallel slices: 16 on a pod, 32 across two — the paper's m,
  generalized), and the trigger config.
* ``build_train_step`` wires the event-triggered train step under ``jit``
  with explicit in/out shardings derived from logical axes.
* ``build_serve_step`` / ``build_prefill_step`` cover the decode shapes
  (one token + ``seq_len`` cache) and prefill.

The dry-run train step uses the paper-faithful SGD (eq. 3/6) — this also
keeps the 1T-param kimi-k2 inside v5e HBM (no fp32 Adam moments; see
EXPERIMENTS.md §Dry-run).  ``train.py`` defaults to AdamW for real runs.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig, TriggerConfig
from repro.core.api import (
    METRIC_KEYS,
    NET_METRIC_KEYS,
    StepOptions,
    TrainState,
    make_triggered_train_step,
)
from repro.models import build, input_axes, input_specs, long_context_variant
from repro.optim import optimizers as opt_lib
from repro.sharding.rules import agent_pspec, resolve_rules, tree_pspecs

FSDP_PARAM_THRESHOLD = 20e9


def _ns(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree (None leaves stay None)."""
    return jax.tree_util.tree_map(
        lambda s: None if s is None else NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


@dataclass(frozen=True)
class RunPlan:
    cfg: ModelConfig
    shape: InputShape
    fsdp: bool
    agent_axes: Tuple[str, ...]
    num_agents: int
    train_cfg: TrainConfig
    rules: dict
    seq_shard: bool = False


def plan_run(
    cfg: ModelConfig,
    shape: InputShape,
    mesh,
    *,
    comm: Optional[object] = None,
    trigger: Optional[TriggerConfig] = None,
    optimizer: str = "sgd",
    lr: float = 1e-2,
    fsdp: Optional[bool] = None,
    seq_shard: bool = False,
    remat: bool = False,
    attn_q_block: Optional[int] = None,
    inner_batch_shard: bool = False,
    cache_seq_shard: bool = False,
    microbatches: int = 1,
    agents: Optional[int] = None,
) -> RunPlan:
    """Plan one run; ``agents`` overrides the fleet size (default: the
    product of the mesh's agent axes), replicating the agents over the
    mesh."""
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    if remat or attn_q_block:
        cfg = cfg.replace(remat=remat, attn_q_block=attn_q_block)
    multipod = "pod" in mesh.axis_names
    if fsdp is None:
        fsdp = cfg.param_count() > FSDP_PARAM_THRESHOLD
    # Agents ALWAYS live on the data axes — each data slice computes only
    # its own agent's gradient (the paper's decentralized scheme under
    # SPMD).  FSDP is orthogonal: it additionally shards the params'
    # embed dim over the same axes (ZeRO-3 all-gather per layer).  An
    # earlier revision parked agents on "pod" under FSDP, which left the
    # data axis idle for activations — 16× replicated activation traffic
    # (EXPERIMENTS.md §Perf, qwen3 iter-2, hypothesis refuted).
    agent_axes: Tuple[str, ...] = ("pod", "data") if multipod else ("data",)
    num_agents = agents or int(math.prod(mesh.shape[a] for a in agent_axes))
    trigger = trigger or TriggerConfig(kind="gain_lookahead", lam=0.0)
    if comm is not None and not isinstance(comm, str):
        from repro.comm import CommPolicy

        # normalize CommPolicy values / per-agent lists to spec strings so
        # TrainConfig stays a hashable frozen dataclass
        comm = (str(comm) if isinstance(comm, CommPolicy)
                else tuple(str(p) for p in comm))
    train_cfg = TrainConfig(
        lr=lr,
        optimizer=optimizer,
        num_agents=num_agents,
        microbatches=microbatches,
        trigger=trigger,
        comm=comm,
    )
    rules = resolve_rules(
        mesh, fsdp=fsdp, agent_axes=agent_axes or ("data",),
        seq_shard=seq_shard, inner_batch_shard=inner_batch_shard,
        cache_seq_shard=cache_seq_shard,
    )
    if agents:
        rules["agent"] = None  # a custom agent count is replicated
    return RunPlan(
        cfg=cfg,
        shape=shape,
        fsdp=fsdp,
        agent_axes=agent_axes,
        num_agents=num_agents,
        train_cfg=train_cfg,
        rules=rules,
        seq_shard=seq_shard,
    )


# ----------------------------------------------------------------------


def _abstract_opt_state(optimizer: str, params_abs):
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    if optimizer == "sgd":
        return (), ()
    mom = jax.tree_util.tree_map(f32, params_abs)
    if optimizer == "momentum":
        return mom, "params-like"
    if optimizer == "adamw":
        return opt_lib.AdamState(mu=mom, nu=jax.tree_util.tree_map(f32, params_abs)), "adam"
    raise ValueError(optimizer)


def _opt_state_specs(optimizer: str, param_specs):
    if optimizer == "sgd":
        return ()
    if optimizer == "momentum":
        return param_specs
    if optimizer == "adamw":
        return opt_lib.AdamState(mu=param_specs, nu=param_specs)
    raise ValueError(optimizer)


def _install_gather_hook(mesh, plan: RunPlan, axes, *, train: bool = True):
    """ZeRO-3 gather-at-use: see repro.sharding.constraint.

    Train-only: gathering a layer's weights (params/L bytes) beats
    all-reducing a full train batch's activations.  At decode the
    activations are a handful of tokens — moving THEM is ~1000× cheaper
    than gathering 1T-scale weights per step (kimi decode_32k went
    7.4 s → collective-term when the hook leaked into serve; §Perf)."""
    from repro.sharding.constraint import make_gather_hook, set_gather_hook

    from repro.sharding.constraint import make_act_hook, set_act_hook

    set_gather_hook(
        make_gather_hook(mesh, axes, plan.rules) if (plan.fsdp and train) else None
    )
    set_act_hook(make_act_hook(mesh, plan.rules) if not train else None)


def build_train_step(mesh, plan: RunPlan, *, compute_dtype="bfloat16",
                     param_dtype=None, fleet_shard: bool = False):
    """Returns (jitted_step, state_abs, batch_abs, state_specs, batch_specs).

    ``fleet_shard=True`` swaps in the fleet-sharded step
    (:func:`repro.sharding.agent_shard.make_sharded_train_step`): the
    per-agent work runs under ``shard_map`` over the plan's agent axes
    with the two-level gateway reduce instead of the flat center sum.
    On a mesh that cannot shard the fleet it falls back to the plain
    hybrid step (``agent_pspec`` warns), so the knob is always safe.
    """
    cfg = plan.cfg.replace(compute_dtype=compute_dtype)
    model = build(cfg)
    pdt = jnp.dtype(param_dtype or compute_dtype)
    params_abs, axes = model.init(abstract=True, dtype=pdt)
    _install_gather_hook(mesh, plan, axes)
    param_specs = tree_pspecs(axes, params_abs, plan.rules, mesh)

    optimizer = opt_lib.from_config(plan.train_cfg)
    opt_abs, _ = _abstract_opt_state(plan.train_cfg.optimizer, params_abs)
    opt_specs = _opt_state_specs(plan.train_cfg.optimizer, param_specs)

    # adaptive budget policies carry a (m, CTRL_WIDTH) controller slot —
    # the abstract state must include it or the AOT-lowered step (dryrun)
    # would bake the open-loop no-controller path
    from repro.comm import CTRL_WIDTH, normalize_policy, resolve_policy

    resolved = normalize_policy(
        resolve_policy(plan.train_cfg, None), plan.train_cfg.num_agents
    )
    policies = resolved if isinstance(resolved, tuple) else (resolved,)
    # per-agent rows shard over the fleet (agent) axes — each data
    # slice owns its own agents' controller rows, same layout the
    # sharded train step's shard_map expects; a mesh that cannot shard
    # the fleet resolves to P() (replicated) exactly as before
    aspec = agent_pspec(mesh, plan.train_cfg.num_agents, plan.rules)
    if any(p.is_adaptive for p in policies):
        ctrl_abs = jax.ShapeDtypeStruct(
            (plan.train_cfg.num_agents, CTRL_WIDTH), jnp.float32
        )
        ctrl_specs = aspec
    else:
        ctrl_abs = ctrl_specs = None

    # lossy-channel policies (@ bernoulli etc.) carry a (m, NET_WIDTH)
    # per-agent channel slot; same discipline as the controller slot
    from repro.net import NET_WIDTH

    use_net = any(p.needs_net for p in policies)
    if use_net:
        net_abs = jax.ShapeDtypeStruct(
            (plan.train_cfg.num_agents, NET_WIDTH), jnp.float32
        )
        net_specs = aspec
    else:
        net_abs = net_specs = None

    state_abs = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=params_abs,
        opt_state=opt_abs,
        ef_memory=None,
        ctrl_state=ctrl_abs,
        net_state=net_abs,
    )
    state_specs = TrainState(
        step=P(), params=param_specs, opt_state=opt_specs, ef_memory=None,
        ctrl_state=ctrl_specs, net_state=net_specs,
    )

    batch_abs = input_specs(cfg, plan.shape, num_agents=plan.num_agents)
    batch_ax = input_axes(cfg, plan.shape, num_agents=plan.num_agents)
    batch_specs = tree_pspecs(batch_ax, batch_abs, plan.rules, mesh)

    # fleet_shard routes through StepOptions.mesh — the one
    # step-construction surface (DESIGN.md §9)
    step_fn = make_triggered_train_step(
        model.loss_fn, optimizer, plan.train_cfg,
        options=StepOptions(
            mesh=mesh if fleet_shard else None,
            rules=plan.rules if fleet_shard else None,
        ),
    )
    metric_specs = {k: P() for k in METRIC_KEYS}
    if use_net:
        # net_state-carrying steps emit the attempted/delivered split
        metric_specs.update({k: P() for k in NET_METRIC_KEYS})
    jitted = jax.jit(
        step_fn,
        in_shardings=_ns(mesh, (state_specs, batch_specs)),
        out_shardings=_ns(mesh, (state_specs, metric_specs)),
    )
    return jitted, state_abs, batch_abs, state_specs, batch_specs


def build_prefill_step(mesh, plan: RunPlan, *, compute_dtype="bfloat16"):
    """Full-sequence forward (inference prefill)."""
    cfg = plan.cfg.replace(compute_dtype=compute_dtype)
    model = build(cfg)
    params_abs, axes = model.init(abstract=True, dtype=jnp.dtype(compute_dtype))
    _install_gather_hook(mesh, plan, axes, train=False)
    param_specs = tree_pspecs(axes, params_abs, plan.rules, mesh)
    batch_abs = input_specs(cfg, plan.shape)
    batch_ax = input_axes(cfg, plan.shape)
    batch_specs = tree_pspecs(batch_ax, batch_abs, plan.rules, mesh)

    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch)
        return logits

    jitted = jax.jit(prefill_step, in_shardings=_ns(mesh, (param_specs, batch_specs)))
    return jitted, params_abs, batch_abs, param_specs, batch_specs


def build_serve_step(mesh, plan: RunPlan, *, compute_dtype="bfloat16"):
    """One-token decode against a seq_len cache (decode shapes)."""
    cfg = plan.cfg.replace(compute_dtype=compute_dtype)
    model = build(cfg)
    params_abs, axes = model.init(abstract=True, dtype=jnp.dtype(compute_dtype))
    _install_gather_hook(mesh, plan, axes, train=False)
    param_specs = tree_pspecs(axes, params_abs, plan.rules, mesh)
    inputs = input_specs(cfg, plan.shape)
    inputs_ax = input_axes(cfg, plan.shape)
    in_specs = tree_pspecs(inputs_ax, inputs, plan.rules, mesh)

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    jitted = jax.jit(
        serve_step,
        in_shardings=_ns(
            mesh,
            (param_specs, in_specs["cache"], in_specs["tokens"], in_specs["pos"]),
        ),
        out_shardings=(None, _ns(mesh, in_specs["cache"])),
        # donate the cache: in-place update instead of a full copy per
        # decoded token (halves cache memory, kills the copy traffic)
        donate_argnums=(1,),
    )
    return (
        jitted,
        params_abs,
        (inputs["cache"], inputs["tokens"], inputs["pos"]),
        param_specs,
        in_specs,
    )


def lower_for(mesh, plan: RunPlan, **kw):
    """Lower the right step for the plan's shape kind. Returns Lowered."""
    if plan.shape.kind == "train":
        jitted, state_abs, batch_abs, *_ = build_train_step(mesh, plan, **kw)
        return jitted.lower(state_abs, batch_abs)
    if plan.shape.kind == "prefill":
        jitted, params_abs, batch_abs, *_ = build_prefill_step(mesh, plan, **kw)
        return jitted.lower(params_abs, batch_abs)
    jitted, params_abs, (cache, tokens, pos), *_ = build_serve_step(mesh, plan, **kw)
    return jitted.lower(params_abs, cache, tokens, pos)