"""EventTriggeredDataParallel — the paper's technique as a train-step transform.

``make_triggered_train_step`` turns any per-batch loss into a distributed
train step implementing the paper's full loop:

  1. server broadcast of ``w_k``          → parameter replication /
                                            FSDP all-gather under pjit
  2. per-agent stochastic gradients g_k^i → ``vmap(value_and_grad)`` over
                                            the batch's leading agent axis
                                            (sharded over mesh data axes,
                                            so each device group computes
                                            only its own agent's gradient)
  3. local trigger decisions α_k^i        → the policy's Trigger stage
                                            (repro.comm.triggers, pure
                                            local computation, eq. 11/30/31)
  4. wire format of what IS sent          → the policy's Compressor chain
                                            (+ ErrorFeedback residuals)
  5. server aggregation, eq. (10)         → masked mean = one all-reduce
  6. parameter update                     → pluggable optimizer

The communication behaviour is a single :class:`repro.comm.CommPolicy`
value (or a per-agent tuple for heterogeneous networks)::

    step = make_triggered_train_step(
        loss_fn, opt, cfg,
        policy="gain_lookahead(lam=0.1)|topk(0.05)|int8+ef")

With ``optimizer="sgd"`` and a ``gain_lookahead`` trigger this is
*exactly* the paper's algorithm (the lookahead gain equals eq. (30) for
quadratic losses); every other combination is a labelled generalization.
Note eq. (10)'s "hold when silent" is exact under SGD (zero aggregated
gradient ⇒ zero update); adaptive optimizers still advance their moments.

Legacy entry: calling with only a :class:`TrainConfig` still works — the
scattered ``trigger``/``quantize_grads``/``topk_frac``/``error_feedback``
flags are converted through :func:`repro.comm.resolve_policy` (with a
``DeprecationWarning`` for the compression flags).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import (
    CommPolicy,
    batch_prologue,
    build_stage_bank,
    comm_stats,
    ctrl_init,
    dense_bits,
    dense_entries,
    ef_add,
    ef_init,
    ef_residual,
    fold_sum,
    normalize_policy,
    per_agent_wire_bytes,
    resolve_policy,
    structural_bytes,
)
from repro.configs.base import TrainConfig
from repro.core.aggregation import masked_mean
from repro.net.channels import (
    channel_round,
    delay_round,
    net_init,
    net_rows,
    retx_round,
    stale_scale,
    tx_cost,
)
from repro.sharding.constraint import constrain_params
from repro.utils.tree import tree_add_scaled

METRIC_KEYS = ("loss", "comm_rate", "any_tx", "num_tx", "mean_gain",
               "grad_norm", "wire_bytes")

# extra scalar metrics emitted ONLY by net_state-carrying (lossy-channel)
# steps — the attempted/delivered wire-byte split repro.net introduces.
# Channel-free programs keep exactly METRIC_KEYS (the launch-layer jit
# out_shardings are keyed on the metric dict, so the key set is part of
# the compiled program's signature).
NET_METRIC_KEYS = ("wire_bytes_attempted", "num_delivered",
                   "delivered_rate", "mean_staleness")

# extra scalar metric emitted ONLY by churn-carrying steps
# (``StepOptions.churn``): the number of currently-active agents — the
# denominator behind the active-only rates below.  Churn-free programs
# keep their exact pre-churn key set.
CHURN_METRIC_KEYS = ("num_active",)

# per-agent metric vectors emitted under ``StepOptions.agent_metrics``
# — the per-tier resolution the telemetry rollup (repro.comm.rollup)
# and the tiered-network frontiers consume.  agent_lam appears only for
# adaptive policies, agent_delivered/agent_staleness only on
# net_state-carrying (lossy-channel) traces, agent_active only on
# churn-carrying traces.
AGENT_METRIC_KEYS = ("agent_tx", "agent_bytes", "agent_lam",
                     "agent_delivered", "agent_staleness", "agent_active")

# the heterogeneous-network execution paths, fastest first (the default
# is DISPATCH_MODES[0]); benchmarks/run.py --dispatch validates against
# this same tuple so the CLI and the API cannot drift apart
DISPATCH_MODES = ("hybrid", "switch", "unroll")


@dataclasses.dataclass(frozen=True)
class StepOptions:
    """Execution options for :func:`make_triggered_train_step`.

    One struct instead of the grown kwarg sprawl — the documented
    step-construction surface::

        step = make_triggered_train_step(
            loss_fn, opt, cfg, policy=spec,
            options=StepOptions(agent_metrics=True))

    Fields:

    * ``hetero_dispatch`` — heterogeneous-network execution path, one
      of :data:`DISPATCH_MODES` (see the step docstring for the
      trade-offs).  Homogeneous policies ignore it.
    * ``barriers`` — keep the ``optimization_barrier`` ULP pins that
      make the dispatch paths bit-identical; must be ``False`` under
      ``vmap`` (no batching rule for the barrier primitive).
    * ``agent_metrics`` — add the per-agent :data:`AGENT_METRIC_KEYS`
      vectors to the metrics (tier-level wire accounting, λ
      trajectories — the telemetry hand-off).
    * ``scale`` / ``chan_scale`` — optional FIXED operating-point
      coordinates: the built step's call-time ``scale``/``chan_scale``
      arguments default to these when the caller passes ``None``
      (frontier engines keep passing traced per-lane values instead).
    * ``mesh`` / ``rules`` — the fleet-shard plumbing: a mesh swaps in
      the shard_map'd step (:func:`repro.sharding.agent_shard.
      make_sharded_train_step`) partitioned over the mesh's agent
      axes; ``rules`` optionally overrides its sharding rules and
      ``sketch_native`` turns on the gateway sketch-space merge.
      ``hetero_dispatch``/``barriers`` are ignored on that path (the
      sharded step is the hybrid dispatch, barrier-free, partitioned).
    * ``churn`` — the scenario-churn layer: a per-agent tuple of
      ``(join_step, leave_step)`` pairs (length ``cfg.num_agents``).
      Agent ``i`` is ACTIVE while ``join <= step < leave``; inactive
      agents contribute zero gradient weight and zero wire bytes, their
      EF/controller/channel state is frozen, and every rate-style
      metric divides by the number of ACTIVE agents.  ``None`` (the
      default) adds no ops — churn-free programs compile unchanged.

    The pre-struct keyword spellings (``hetero_dispatch=``,
    ``barriers=``, ``agent_metrics=`` directly on
    ``make_triggered_train_step``) still work with a
    ``DeprecationWarning`` and bit-equal behavior for one release.
    """

    hetero_dispatch: str = "hybrid"
    barriers: bool = True
    agent_metrics: bool = False
    scale: Optional[float] = None
    chan_scale: Optional[float] = None
    mesh: Any = None
    rules: Optional[dict] = None
    sketch_native: bool = False
    churn: Optional[Tuple[Tuple[int, int], ...]] = None

    def __post_init__(self):
        if self.hetero_dispatch not in DISPATCH_MODES:
            raise ValueError(
                f"unknown hetero_dispatch {self.hetero_dispatch!r}: "
                f"expected one of "
                f"{', '.join(repr(m) for m in DISPATCH_MODES)}"
            )
        if self.churn is not None:
            # normalize to a hashable tuple-of-pairs and validate the
            # schedule shape up front (the length-vs-num_agents check
            # happens at step build, where the config is known)
            pairs = tuple(tuple(int(v) for v in p) for p in self.churn)
            for p in pairs:
                if len(p) != 2:
                    raise ValueError(
                        f"churn entries must be (join, leave) pairs, "
                        f"got {p!r}"
                    )
                if p[0] >= p[1]:
                    raise ValueError(
                        f"churn (join, leave) must satisfy join < "
                        f"leave, got {p!r}"
                    )
            object.__setattr__(self, "churn", pairs)


_UNSET = object()  # sentinel: legacy keyword not passed


def _merge_legacy_options(options: Optional[StepOptions],
                          legacy: dict) -> StepOptions:
    """Fold the deprecated keyword spellings into a StepOptions (one
    release of bit-equal behavior; tests pin the equivalence)."""
    given = {k: v for k, v in legacy.items() if v is not _UNSET}
    if given:
        import warnings

        warnings.warn(
            f"keyword(s) {', '.join(sorted(given))} on "
            "make_triggered_train_step are deprecated; pass "
            "options=StepOptions(...) instead",
            DeprecationWarning,
            stacklevel=3,
        )
    return dataclasses.replace(options or StepOptions(), **given)


def _microbatched(fn, m: int):
    """Scan ``fn(params, batch) -> scalar`` over ``m`` equal microbatches.

    Gradients of the scanned mean equal the full-batch gradient (the loss
    is a token mean over equal-sized slices), but the live activation set
    is 1/m of the batch — the standard fit-in-HBM knob
    (EXPERIMENTS.md §Perf, qwen3 iter-9)."""

    def scanned(params, batch):
        mb = jax.tree_util.tree_map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch
        )

        def body(acc, b):
            return acc + fn(params, b), None

        tot, _ = jax.lax.scan(body, jnp.float32(0.0), mb)
        return tot / m

    return scanned


def _warn_ef_memory_missing():
    """Trace-time notice: the policy asks for error feedback but the
    TrainState carries no residual memory (it was initialized with a
    different policy), so EF is off for this run."""
    import warnings

    warnings.warn(
        "policy requests error feedback (+ef) but state.ef_memory is None "
        "— pass the same policy to init_train_state to allocate it; "
        "running WITHOUT error feedback",
        UserWarning,
        stacklevel=2,
    )


def _warn_ctrl_state_missing():
    """Trace-time notice: the policy carries an adaptive (budget)
    trigger but the TrainState has no controller slot, so the threshold
    stays open-loop at its lam0 — no adaptation this run."""
    import warnings

    warnings.warn(
        "policy has an adaptive budget trigger but state.ctrl_state is "
        "None — pass the same policy to init_train_state to allocate "
        "it; running OPEN-LOOP at the trigger's lam0 (no adaptation)",
        UserWarning,
        stacklevel=2,
    )


def _warn_net_state_missing():
    """Trace-time notice: the policy names a lossy channel but the
    TrainState carries no per-agent channel-state slot (it was
    initialized with a different policy), so the channel is OFF —
    the step runs the exact lossless program."""
    import warnings

    warnings.warn(
        "policy attaches a lossy channel (@ ...) but state.net_state is "
        "None — pass the same policy to init_train_state to allocate "
        "it; running over an IDEAL wire (no losses simulated)",
        UserWarning,
        stacklevel=2,
    )


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any
    ef_memory: Optional[Any] = None  # error-feedback residuals (A, *param)
    # per-agent controller rows (A, CTRL_WIDTH) for adaptive budget
    # triggers; None (plain policies) threads through with zero extra ops
    ctrl_state: Optional[Any] = None
    # per-agent channel rows (A, NET_WIDTH) = [staleness, aux, uid] for
    # lossy-channel policies (repro.net); None (channel-free and
    # @ ideal) threads through with zero extra ops
    net_state: Optional[Any] = None


def init_train_state(params, optimizer, cfg: TrainConfig,
                     policy=None) -> TrainState:
    """Build the initial state; EF memory is allocated iff the resolved
    policy (or any per-agent policy) carries error feedback, the
    controller slot iff any trigger is adaptive (budget_dual/_window),
    and the channel slot iff any policy attaches a non-trivial lossy
    channel (``@ bernoulli(...)`` etc. — ``@ ideal`` allocates none)."""
    resolved = normalize_policy(resolve_policy(cfg, policy), cfg.num_agents)
    policies = resolved if isinstance(resolved, tuple) else (resolved,)
    ef = ef_init(params, cfg.num_agents) if any(p.needs_ef for p in policies) else None
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
        ef_memory=ef,
        ctrl_state=ctrl_init(resolved, cfg.num_agents),
        # params size the delay-line payload buffer of @ delay policies;
        # loss-only channels keep the bare (A, NET_WIDTH) rows
        net_state=net_init(resolved, cfg.num_agents, params),
    )


def make_triggered_train_step(
    loss_fn: Callable,
    optimizer,
    cfg: TrainConfig,
    *,
    policy=None,
    aux_loss_fn: Optional[Callable] = None,
    use_kernel: bool = False,
    oracle: Optional[tuple] = None,
    options: Optional[StepOptions] = None,
    hetero_dispatch=_UNSET,
    barriers=_UNSET,
    agent_metrics=_UNSET,
):
    """Build ``train_step(state, batch, scale=None, chan_scale=None)
    -> (state, metrics)``.

    Execution options live in one :class:`StepOptions` struct
    (``options=``); the bare ``hetero_dispatch``/``barriers``/
    ``agent_metrics`` keywords are the deprecated spellings — they
    shim through with a ``DeprecationWarning`` and bit-equal behavior.
    ``options.mesh`` routes to the fleet-sharded step
    (:func:`repro.sharding.agent_shard.make_sharded_train_step`).

    ``loss_fn(params, batch) -> scalar`` is the local empirical loss; the
    batch pytree's leaves must carry a leading agent axis of size
    ``cfg.num_agents``.  ``aux_loss_fn`` (e.g. MoE load-balance) is added
    to the differentiated objective but not to the trigger's gain.

    ``policy`` is a :class:`~repro.comm.CommPolicy`, a spec string, or a
    per-agent sequence of either (heterogeneous networks); when omitted
    it resolves from ``cfg.comm``, falling back to the legacy flag set.
    ``use_kernel`` is the deprecated spelling of the trigger-level
    ``kernel=true`` spec argument.  ``oracle`` is the ``(Σ, w*)`` pair
    the ``gain_exact`` trigger requires.

    ``hetero_dispatch`` picks the heterogeneous-network execution path
    (one of :data:`DISPATCH_MODES`): ``"hybrid"`` (default) batches the
    shared gradient prologue — per-agent ``value_and_grad`` plus the
    :class:`~repro.comm.StageBank`'s deduped trigger gain precursors —
    over the agent axis in ONE ``jax.vmap``, then runs only the comm
    epilogue (trigger gate / compressor / EF update / controller step)
    through a ``lax.scan`` + ``lax.switch`` over the DISTINCT policies,
    each branch vmapped over its own agents — agent-parallel gradient
    AND comm work, with only the policy axis sequential, at O(#distinct
    policies) compile cost; ``"switch"`` scans the agent axis with the
    prologue carried inside the scan (the pre-hybrid path: same compile
    cost, all per-agent work serialized); ``"unroll"`` is the PR-1
    Python loop (compile cost O(m), kept as the bit-identical
    reference).  Homogeneous policies ignore it (the homogeneous path
    has always vmapped the whole agent axis).  benchmarks/
    BENCH_dispatch.json records the measured step/compile times.

    The built step takes an optional traced ``scale`` — an f32 scalar
    multiplying every trigger's transmit threshold (λ/μ).  The default
    ``None`` adds no ops; a traced scale turns the step into a family
    of operating points, which is how ``repro.core.frontier`` vmaps a
    whole loss-vs-wire-bytes frontier out of ONE train step.  For
    adaptive budget triggers (``budget_dual``/``budget_window``) the
    scale multiplies the *target* instead — λ is closed-loop state in
    ``state.ctrl_state``, a per-agent ``(A, CTRL_WIDTH)`` slot
    ``init_train_state`` allocates iff the policy is adaptive.  A
    ``None`` ctrl_state emits zero extra ops (plain policies compile
    unchanged); an adaptive policy stepped without the slot gates
    open-loop at its ``lam0`` (with a ``UserWarning``), bit-identical
    to ``gain_lookahead(lam=lam0)``.

    Policies may attach a lossy-channel model with an ``@ channel``
    spec suffix (repro.net): the step then draws per-agent delivery
    inside the compiled program (traced counter-based randomness — no
    Python event loop), aggregates eq. (10) over DELIVERED messages,
    folds dropped payloads back into EF memory whole, carries per-agent
    staleness in ``state.net_state`` (escalating starved agents'
    effective thresholds), and splits the wire metrics into attempted
    vs delivered bytes (adaptive controllers price delivered).  The
    optional traced ``chan_scale`` scales the channel's severity (loss
    probability up, rate capacity down) — the second frontier-grid
    coordinate, vmapped by ``repro.core.frontier`` into loss-rate ×
    budget-scale surfaces.  Channel-free policies and ``@ ideal``
    compile to the exact pre-channel program (``net_state`` is None —
    the same static slot discipline as EF memory and the controllers);
    a lossy policy stepped without the slot warns and runs ideal.

    ``barriers=False`` drops the ``optimization_barrier`` ULP pins that
    keep the two hetero dispatch paths bit-identical — required when
    the step runs under ``vmap`` (the barrier primitive has no batching
    rule in this jax); the paths then agree to float tolerance, not
    bitwise.  ``agent_metrics=True`` adds per-agent vectors
    (``agent_tx``, ``agent_bytes``, both ``(m,)``) to the metrics —
    the per-tier wire accounting the tiered-network frontiers need.
    """
    opts = _merge_legacy_options(
        options,
        dict(hetero_dispatch=hetero_dispatch, barriers=barriers,
             agent_metrics=agent_metrics),
    )
    if opts.mesh is not None:
        # fleet-shard plumbing: the shard_map'd hybrid step partitioned
        # over the mesh's agent axes (microbatching, policy resolution
        # and the per-agent machinery all happen inside)
        from repro.sharding.agent_shard import make_sharded_train_step

        step = make_sharded_train_step(
            loss_fn, optimizer, cfg, opts.mesh, policy=policy,
            aux_loss_fn=aux_loss_fn, use_kernel=use_kernel,
            oracle=oracle, rules=opts.rules,
            sketch_native=opts.sketch_native,
            agent_metrics=opts.agent_metrics,
            churn=opts.churn,
        )
        if opts.scale is None and opts.chan_scale is None:
            return step

        def pinned(state, batch, scale=None, chan_scale=None):
            return step(
                state, batch,
                opts.scale if scale is None else scale,
                opts.chan_scale if chan_scale is None else chan_scale,
            )

        return pinned
    hetero_dispatch = opts.hetero_dispatch
    barriers = opts.barriers
    agent_metrics = opts.agent_metrics

    if cfg.microbatches > 1:
        loss_fn = _microbatched(loss_fn, cfg.microbatches)
        if aux_loss_fn is not None:
            aux_loss_fn = _microbatched(aux_loss_fn, cfg.microbatches)

    resolved = normalize_policy(
        resolve_policy(cfg, policy, use_kernel=use_kernel), cfg.num_agents
    )
    hetero: Optional[Tuple[CommPolicy, ...]] = (
        resolved if isinstance(resolved, tuple) else None
    )
    if opts.churn is not None and len(opts.churn) != cfg.num_agents:
        raise ValueError(
            f"churn schedule has {len(opts.churn)} entries but "
            f"num_agents={cfg.num_agents}"
        )
    if (
        hetero is None
        and resolved.needs_net
        and resolved.channel_model().depth > 0
    ):
        # a homogeneous payload-buffering policy (@ delay / @ retx,
        # both depth > 0) runs through the stage-bank dispatch (a P=1
        # bank): the buffer's enqueue/dequeue epilogue lives in ONE
        # place (repro.comm.bank) instead of being re-derived on the
        # homogeneous vmap path
        hetero = (resolved,) * cfg.num_agents

    def build_stages(pol: CommPolicy):
        trig = pol.build_trigger(loss_fn=loss_fn, probe_eps=cfg.lr, oracle=oracle)
        # trivial (@ ideal) channels collapse to None at build time, so
        # the traced program is exactly the channel-free one
        chan = pol.channel_model() if pol.needs_net else None
        return trig, pol.chain(), pol.needs_ef, pol.is_adaptive, chan

    if hetero is None:
        trigger, chain, needs_ef, adaptive, channel = build_stages(resolved)
        chains = (chain,)
        needs_ctrl = adaptive
        needs_net = channel is not None
    elif hetero_dispatch in ("hybrid", "switch"):
        bank = build_stage_bank(
            hetero, loss_fn=loss_fn, probe_eps=cfg.lr, oracle=oracle
        )
        needs_ef = bank.needs_ef
        needs_ctrl = bank.needs_ctrl
        needs_net = bank.needs_net
        chains = bank.agent_chains()
        # the bank's deduped phase-1 gain precursors (probe forward
        # pass / HVP / ‖g‖²) — the hybrid path evaluates them inside
        # its prologue vmap so the epilogue scan is left with only the
        # cheap gate/controller/compressor work.  When every trigger's
        # batch consumption lives in the prologue, the scan also drops
        # the per-agent batch slice entirely (a leafless None operand).
        prologue_fns, _ = bank.prologues()
        scan_batch_free = bank.epilogue_batch_free
    else:
        stages = [build_stages(p) for p in hetero]
        needs_ef = any(ef for _, _, ef, _, _ in stages)
        needs_ctrl = any(ad for _, _, _, ad, _ in stages)
        needs_net = any(ch is not None for _, _, _, _, ch in stages)
        chains = tuple(c for _, c, _, _, _ in stages)

    def objective(params, batch):
        main = loss_fn(params, batch)
        if aux_loss_fn is not None:
            return main + aux_loss_fn(params, batch), main
        return main, main

    def grad_prologue(params, agent_batch, barrier: bool):
        """One agent's (loss, grad) — the policy-independent prologue
        shared by every dispatch path (keeping switch/unroll provably on
        the same ops)."""
        (obj, main), g = jax.value_and_grad(objective, has_aux=True)(
            params, agent_batch
        )
        # Per-agent gradient (and probe) trees CANNOT inherit the
        # FSDP embed@data layout — the agent axis IS the data axis.
        # Pin them to model-axis (TP-style) sharding so each device
        # holds params/TP per agent, not a replicated full tree
        # (EXPERIMENTS.md §Perf, qwen3 iter-6 → iter-7).  No-op when
        # no gather hook is installed (non-FSDP plans, CPU tests).
        g = constrain_params(g, "")
        if barrier and barriers:
            # pin (loss, grad) before the trigger: XLA otherwise
            # CSE-fuses the loss with the trigger's probe
            # re-evaluation, which would put the unrolled hetero path
            # one ULP off the switch path (whose cond boundary blocks
            # that fusion).  Off under vmap (barriers=False) —
            # optimization_barrier has no batching rule in this jax.
            main, g = jax.lax.optimization_barrier((main, g))
        return main, g

    def trigger_call(trig, is_adaptive, use_ctrl, params, g, agent_batch,
                     main, step, ctrl_row, scale, delivered=None, pre=None):
        """One trigger evaluation under either protocol.

        Returns ``(alpha, gain, new_ctrl_row)`` where the row is
        ``None`` whenever the state carries no controller slot — the
        zero-extra-ops contract: plain policies (and adaptive policies
        stepped open-loop) emit exactly the pre-controller program.

        ``delivered`` is the channel's {0,1} draw for this round (drawn
        BEFORE the trigger, so it is independent of alpha); adaptive
        triggers price ``alpha × delivered`` — delivered bytes — so the
        controllers re-gate under loss.  Fixed triggers never see it
        (their threshold is staleness-scaled upstream instead), and the
        channel-free default (``None``) adds no kwarg — the trigger
        traces its pre-channel ops.  ``pre`` is the trigger's gain
        precursor when the caller computed it (``trig.prologue``, the
        same ops the trigger would run itself)."""
        kw = {} if pre is None else {"pre": pre}
        if is_adaptive:
            row = ctrl_row if use_ctrl else trig.ctrl0
            if delivered is not None:
                kw["delivered"] = delivered
            (alpha, gain), new_row = trig(
                params, g, agent_batch, main, step, row, scale, **kw
            )
            return alpha, gain, (new_row if use_ctrl else None)
        alpha, gain = trig(params, g, agent_batch, main, step, scale, **kw)
        return alpha, gain, (ctrl_row if use_ctrl else None)

    def train_step(state: TrainState, batch, scale=None, chan_scale=None):
        # StepOptions may pin a FIXED operating point; a traced
        # call-time coordinate (the frontier engines') always wins
        if scale is None:
            scale = opts.scale
        if chan_scale is None:
            chan_scale = opts.chan_scale
        # the channel engages only when the state actually carries the
        # per-agent channel rows — same static slot discipline as EF and
        # the controllers: a None slot traces the exact lossless program
        use_net = needs_net and state.net_state is not None
        if needs_net and not use_net:
            _warn_net_state_missing()
        if hetero is None:
            use_ctrl = needs_ctrl and state.ctrl_state is not None
            if needs_ctrl and not use_ctrl:
                _warn_ctrl_state_missing()

            # the trigger's gain precursor (probe forward, ‖g‖², ...),
            # evaluated under its own scope and handed to the trigger
            probe = getattr(trigger, "prologue", None)

            def per_agent(agent_batch, ctrl_row, net_row):
                with jax.named_scope("prologue"):
                    main, g = grad_prologue(state.params, agent_batch, False)
                if use_net:
                    # channel draw FIRST (delivery independent of this
                    # round's alpha); the staleness factor escalates a
                    # starved agent's effective threshold/target
                    with jax.named_scope("channel"):
                        cost = tx_cost(g, chain)
                        d, stale, finalize = channel_round(
                            channel, net_row, state.step, chan_scale, cost
                        )
                        eff_scale = stale_scale(
                            scale, channel.boost, stale, adaptive
                        )
                else:
                    d, eff_scale = None, scale
                pre = None
                if probe is not None:
                    with jax.named_scope("probe"):
                        pre = probe(state.params, g, agent_batch, main)
                with jax.named_scope("trigger"):
                    alpha, gain, new_row = trigger_call(
                        trigger, adaptive, use_ctrl, state.params, g,
                        agent_batch, main, state.step, ctrl_row, eff_scale,
                        delivered=d if adaptive else None, pre=pre,
                    )
                if use_net:
                    delivered = alpha * d
                    with jax.named_scope("channel"):
                        new_net_row = finalize(delivered)
                    return (main, g, alpha, gain, new_row, d, delivered,
                            new_net_row)
                return main, g, alpha, gain, new_row

            in_axes = (0, 0 if use_ctrl else None, 0 if use_net else None)
            outs = jax.vmap(per_agent, in_axes=in_axes)(
                batch,
                state.ctrl_state if use_ctrl else None,
                state.net_state if use_net else None,
            )
            if use_net:
                (losses, grads, alphas, gains, new_ctrl, ds, delivereds,
                 new_net) = outs
            else:
                losses, grads, alphas, gains, new_ctrl = outs
                ds, delivereds, new_net = None, alphas, state.net_state
            new_ctrl = new_ctrl if use_ctrl else state.ctrl_state
            if chain:
                # EF engages only when the state actually carries memory
                # (init_train_state with the same policy) — keeping the
                # TrainState pytree structure stable across steps
                use_ef = needs_ef and state.ef_memory is not None
                if needs_ef and not use_ef:
                    _warn_ef_memory_missing()
                with jax.named_scope("compress"):
                    g_eff = ef_add(grads, state.ef_memory if use_ef else None)
                    sent = jax.tree_util.tree_map(
                        lambda g: jax.vmap(chain.compress)(g), g_eff
                    )
                    new_ef = (
                        ef_residual(g_eff, sent, alphas,
                                    delivered=ds if use_net else None)
                        if use_ef else state.ef_memory
                    )
            else:
                sent, new_ef = grads, state.ef_memory
        elif hetero_dispatch in ("hybrid", "switch"):
            # Heterogeneous two-phase dispatch into the deduped stage
            # bank.  "hybrid" runs phase 1 — the policy-independent
            # gradient prologue plus the bank's deduped trigger gain
            # precursors — batched over the agent axis in ONE vmap
            # (agent-parallel gradient work), then dispatches the comm
            # epilogue blocked over the DISTINCT-POLICY axis: P
            # branches, each vmapping its policy's epilogue over that
            # policy's own contiguous agent block.  "switch" carries the
            # prologue along a scan over the AGENT axis (the pre-hybrid
            # path: same O(#distinct policies) compile cost, but both
            # gradient and comm work serialized per agent).  Either way
            # every agent runs exactly the ops the unrolled loop ran
            # (bit-identical on CPU), traced once per DISTINCT policy.
            hybrid = hetero_dispatch == "hybrid"
            has_mem = needs_ef and state.ef_memory is not None
            if needs_ef and not has_mem:
                _warn_ef_memory_missing()
            use_ctrl = needs_ctrl and state.ctrl_state is not None
            if needs_ctrl and not use_ctrl:
                _warn_ctrl_state_missing()
            branches = bank.epilogues(has_mem, use_ctrl, use_net)
            mem = state.ef_memory if has_mem else None
            ctrl = state.ctrl_state if use_ctrl else None
            net = state.net_state if use_net else None

            if hybrid:
                use_pre = bool(prologue_fns)

                # phase 1: stacked (losses, grads) — plus the deduped
                # trigger gain precursors, stacked to a per-agent (P,)
                # vector — for all agents from ONE vmap.  Precursors
                # are union-computed (every distinct precursor for
                # every agent: the prologue is un-switched), which is
                # agent-parallel and bounded by the handful of distinct
                # computations a bank dedupes to.  The prologue's
                # optimization_barrier must stay OFF inside the vmap
                # (no batching rule); pinning the stacked outputs
                # instead serves the same anti-CSE purpose — the
                # epilogue consumes materialized stacks, so the
                # trigger's probe re-evaluation cannot fuse back into
                # the loss computation anyway.
                def agent_prologue(ab):
                    with jax.named_scope("prologue"):
                        main, g = grad_prologue(state.params, ab, False)
                    if not prologue_fns:
                        return main, g, None
                    with jax.named_scope("probe"):
                        pre = jnp.stack([
                            jnp.asarray(fn(state.params, g, ab, main),
                                        jnp.float32)
                            for fn in prologue_fns
                        ])
                    return main, g, pre

                losses, grads, pres = batch_prologue(agent_prologue)(batch)
                if barriers:
                    if pres is None:
                        losses, grads = jax.lax.optimization_barrier(
                            (losses, grads)
                        )
                    else:
                        losses, grads, pres = jax.lax.optimization_barrier(
                            (losses, grads, pres)
                        )

                # phase 2: sort-by-policy blocked dispatch over the
                # DISTINCT POLICIES.  Branch p gathers exactly its own
                # agents' rows (a static, correctly-sized contiguous
                # block — no padding) and vmaps the epilogue over them:
                # comm work is agent-parallel within each policy and
                # only the policy axis (P entries, not m agents) is
                # sequential.  The earlier scan+switch layout padded
                # every group to the largest — pathological for
                # one-big-tier fleets, where each small branch would
                # materialize ~0.9·m duplicate rows.  Results merge
                # back to agent order by one inverse static gather
                # (arithmetic-free, so per-agent values stay exact).
                # With every trigger's batch use hoisted into the
                # prologue, the branches skip gathering the data arrays
                # entirely.
                block_rows, inv_order = bank.policy_blocks()

                def run_block(rows, epilogue):
                    rows = jnp.asarray(rows, jnp.int32)
                    take = lambda tree: jax.tree_util.tree_map(
                        lambda x: x[rows], tree
                    )
                    # statically 5- vs 7-output (use_net) so the
                    # channel-free trace is the exact old program;
                    # chan_scale is an unbatched scalar the block
                    # closes over (the frontier vmap batches it one
                    # level up)
                    if use_net:
                        def per_agent(main, g, pre_i, ab, mem_i,
                                      ctrl_i, net_i):
                            return epilogue(
                                state.params, g, ab, main, state.step,
                                mem_i, ctrl_i, scale, pre_i, net_i,
                                chan_scale,
                            )

                        return jax.vmap(per_agent)(
                            losses[rows], take(grads),
                            take(pres) if use_pre else None,
                            None if scan_batch_free else take(batch),
                            take(mem), take(ctrl), take(net),
                        )

                    def per_agent(main, g, pre_i, ab, mem_i, ctrl_i):
                        return epilogue(
                            state.params, g, ab, main, state.step,
                            mem_i, ctrl_i, scale, pre_i,
                        )

                    return jax.vmap(per_agent)(
                        losses[rows], take(grads),
                        take(pres) if use_pre else None,
                        None if scan_batch_free else take(batch),
                        take(mem), take(ctrl),
                    )

                outs = [
                    run_block(rows, epi)
                    for rows, epi in zip(block_rows, branches)
                ]
                # agent i's result sits at position inv_order[i] of the
                # block concatenation — a static gather, so the merge
                # is exact
                inv_ix = jnp.asarray(inv_order, jnp.int32)
                merge = lambda parts: jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs)[inv_ix], *parts
                )
                n_out = 7 if use_net else 5
                merged = tuple(
                    merge([o[k] for o in outs]) for k in range(n_out)
                )
                if use_net:
                    (alphas, gains, sent, new_mem, new_ctrl, delivereds,
                     new_net) = merged
                else:
                    alphas, gains, sent, new_mem, new_ctrl = merged
            else:
                agent_idx = jnp.asarray(bank.agent_index, jnp.int32)

                def agent_body(carry, inp):
                    if use_net:
                        idx, agent_batch, mem_i, ctrl_i, net_i = inp
                    else:
                        idx, agent_batch, mem_i, ctrl_i = inp
                    main, g = grad_prologue(state.params, agent_batch, True)
                    operands = (
                        state.params, g, agent_batch, main, state.step,
                        mem_i,
                    )
                    if use_ctrl or scale is not None or use_net:
                        # the epilogue's optional ctrl operand precedes
                        # scale, so it must be passed (possibly as the
                        # leafless None pytree) whenever scale is
                        operands = operands + (ctrl_i,)
                    if scale is not None or use_net:
                        # trailing operand feeds the epilogues' optional
                        # threshold scale (the frontier grid
                        # coordinate); arity stays uniform across the
                        # branch list either way because the epilogue
                        # declares it with a default
                        operands = operands + (scale,)
                    if use_net:
                        # fill the remaining defaults positionally up to
                        # the channel tail: pre (unused on this path),
                        # this agent's net row, and the channel-grid
                        # coordinate (a scan-invariant scalar)
                        operands = operands + (None, net_i, chan_scale)
                        (alpha, gain, sent_i, new_mem_i, new_ctrl_i,
                         delivered_i, new_net_i) = jax.lax.switch(
                            idx, branches, *operands
                        )
                        return carry, (main, alpha, gain, sent_i,
                                       new_mem_i, new_ctrl_i,
                                       delivered_i, new_net_i)
                    alpha, gain, sent_i, new_mem_i, new_ctrl_i = \
                        jax.lax.switch(idx, branches, *operands)
                    return carry, (main, alpha, gain, sent_i, new_mem_i,
                                   new_ctrl_i)

                if use_net:
                    _, (losses, alphas, gains, sent, new_mem, new_ctrl,
                        delivereds, new_net) = jax.lax.scan(
                            agent_body, 0.0,
                            (agent_idx, batch, mem, ctrl, net),
                        )
                else:
                    _, (losses, alphas, gains, sent, new_mem, new_ctrl) = \
                        jax.lax.scan(
                            agent_body, 0.0, (agent_idx, batch, mem, ctrl)
                        )
            if barriers:
                # same barrier as the unroll path below: pin the
                # per-agent scalar stacks so both programs reduce a
                # materialized (m,) buffer (XLA otherwise folds this
                # mean into the scan as a sequential accumulator — off
                # by one ULP)
                losses, gains = jax.lax.optimization_barrier(
                    (losses, gains)
                )
            new_ef = new_mem if has_mem else state.ef_memory
            new_ctrl = new_ctrl if use_ctrl else state.ctrl_state
            if not use_net:
                # lossless: the delivery vector IS the decision vector
                # (the same traced value — aggregation compiles unchanged)
                delivereds, new_net = alphas, state.net_state
        else:
            # Heterogeneous "unroll": the PR-1 Python loop over agents —
            # compile cost O(m), kept as the bit-identical reference.
            use_ctrl = needs_ctrl and state.ctrl_state is not None
            if needs_ctrl and not use_ctrl:
                _warn_ctrl_state_missing()
            per = []
            ctrl_rows = []
            net_rows_out = []
            for i, (trig_i, chain_i, ef_i, ad_i, chan_i) in enumerate(stages):
                agent_batch = jax.tree_util.tree_map(lambda x: x[i], batch)
                main, g = grad_prologue(state.params, agent_batch, True)
                use_chan = use_net and chan_i is not None
                use_retx = use_chan and chan_i.retx_k > 0
                use_delay = use_chan and chan_i.depth > 0 and not use_retx
                net_i = jax.tree_util.tree_map(
                    lambda x: x[i], state.net_state
                ) if use_net else None
                if use_retx:
                    cost = tx_cost(g, chain_i)
                    d, stale, pending, commit = retx_round(
                        chan_i, net_i, state.step, chan_scale, cost
                    )
                    eff_scale = stale_scale(scale, chan_i.boost, stale, ad_i)
                elif use_delay:
                    d, stale, commit = delay_round(
                        chan_i, net_i, state.step, chan_scale
                    )
                    eff_scale = stale_scale(scale, chan_i.boost, stale, ad_i)
                elif use_chan:
                    cost = tx_cost(g, chain_i)
                    d, stale, finalize = channel_round(
                        chan_i, net_rows(net_i), state.step,
                        chan_scale, cost,
                    )
                    eff_scale = stale_scale(scale, chan_i.boost, stale, ad_i)
                else:
                    d, eff_scale = None, scale
                alpha, gain, new_row = trigger_call(
                    trig_i, ad_i, use_ctrl, state.params, g, agent_batch,
                    main, state.step,
                    state.ctrl_state[i] if use_ctrl else None, eff_scale,
                    delivered=d if (use_chan and ad_i) else None,
                )
                ctrl_rows.append(new_row)
                use_ef = ef_i and state.ef_memory is not None
                if ef_i and not use_ef:
                    _warn_ef_memory_missing()
                mem_i = jax.tree_util.tree_map(
                    lambda m: m[i], state.ef_memory
                ) if use_ef else None
                g_eff = ef_add(g, mem_i)
                s = chain_i.compress_tree(g_eff) if chain_i else g_eff
                if use_retx:
                    # same semantics as the bank's retx branch: alpha
                    # becomes the realized attempt, the server sees the
                    # buffered payload on re-offer rounds, and the EF
                    # fold is deferred to final failure
                    attempt, out_s, delivered, fold, new_net_i = commit(
                        alpha, s
                    )
                    resid = jax.tree_util.tree_map(
                        lambda ge, se, f:
                        (ge - se) * (alpha * (1.0 - pending)) + f,
                        g_eff, s, fold,
                    ) if use_ef else None
                    s = out_s
                    alpha = attempt
                    net_rows_out.append(new_net_i)
                    per.append((main, alpha, gain, s, resid, delivered))
                    continue
                resid = ef_residual(
                    g_eff, s, alpha, delivered=d if use_chan else None
                ) if use_ef else None
                if use_delay:
                    # the wire payload enqueues; what the server sees
                    # is the matured head with its staleness weight
                    s, delivered, new_net_i = commit(alpha * d, s)
                    net_rows_out.append(new_net_i)
                elif use_chan:
                    delivered = alpha * d
                    new_row = finalize(delivered)
                    net_rows_out.append(
                        (new_row, net_i[1]) if isinstance(net_i, tuple)
                        else new_row
                    )
                else:
                    # channel-free agent (inside a lossy network or not):
                    # delivery IS the decision and the row is untouched
                    delivered = alpha
                    if use_net:
                        net_rows_out.append(net_i)
                per.append((main, alpha, gain, s, resid, delivered))

            # materialize the stacked per-agent scalars: without the
            # barrier XLA re-associates mean(stack(scalars)) into a
            # scalar-add chain, drifting one ULP from the switch path's
            # reduce over the scan's output buffer
            if barriers:
                stack = lambda xs: jax.lax.optimization_barrier(
                    jnp.stack(xs)
                )
            else:
                stack = jnp.stack
            losses = stack([p[0] for p in per])
            alphas = stack([p[1] for p in per])
            gains = stack([p[2] for p in per])
            delivereds = stack([p[5] for p in per]) if use_net else alphas
            new_net = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *net_rows_out
            ) if use_net else state.net_state
            sent = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *[p[3] for p in per]
            )
            if needs_ef and state.ef_memory is not None:
                zeros_like_slice = lambda m: jnp.zeros_like(m[0])
                new_ef = jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(leaves),
                    *[
                        p[4] if p[4] is not None else jax.tree_util.tree_map(
                            zeros_like_slice, state.ef_memory
                        )
                        for p in per
                    ],
                )
            else:
                new_ef = state.ef_memory
            new_ctrl = (
                jnp.stack(ctrl_rows) if use_ctrl else state.ctrl_state
            )

        # scenario churn: inactive agents (outside their [join, leave)
        # window) are masked OUT of this round — zero aggregation
        # weight, zero wire bytes, frozen per-agent state — all with
        # jnp.where/multiplies over the agent axis AFTER dispatch, so
        # one mask covers every execution path.  churn=None (the
        # default) is a static skip: churn-free programs compile
        # unchanged.
        if opts.churn is not None:
            act = (
                (state.step >= jnp.asarray(
                    [j for j, _ in opts.churn], jnp.int32))
                & (state.step < jnp.asarray(
                    [l for _, l in opts.churn], jnp.int32))
            ).astype(jnp.float32)
            n_act = jnp.maximum(fold_sum(act), 1.0)
            alphas = alphas * act
            gains = gains * act
            delivereds = delivereds * act

            def freeze(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        act.reshape((-1,) + (1,) * (n.ndim - 1)) > 0.5,
                        n, o,
                    ),
                    new, old,
                )

            if new_ef is not None and new_ef is not state.ef_memory:
                new_ef = freeze(new_ef, state.ef_memory)
            if new_ctrl is not None and new_ctrl is not state.ctrl_state:
                new_ctrl = freeze(new_ctrl, state.ctrl_state)
            if use_net:
                new_net = freeze(new_net, state.net_state)
        else:
            act = n_act = None

        # eq. (10) over DELIVERED messages: under a lossy channel the
        # server can only average what arrived.  Channel-free paths bind
        # ``delivereds`` to the same traced value as ``alphas``, so this
        # line compiles exactly as the pre-channel ``masked_mean``.
        with jax.named_scope("aggregate"):
            agg = masked_mean(sent, delivereds)
        with jax.named_scope("update"):
            updates, opt_state = optimizer.update(
                agg, state.opt_state, state.params, state.step
            )
            params = tree_add_scaled(state.params, updates, 1.0)
        # wire ratios against the gradients' NATIVE dtype width (int8 on
        # bf16 grads is 0.5, not fp32's 0.25) — all static at trace
        # time; the entry count prices fixed-payload sketch chains
        db = dense_bits(sent)
        sb = structural_bytes(sent, per_agent=True)
        de = dense_entries(sent, per_agent=True)
        ratios = tuple(
            c.ratio_for(db, entries=de) if c else 1.0 for c in chains
        )
        stats = comm_stats(alphas, gains, structural=sb, ratios=ratios)
        metrics = {
            # fold_sum: association-fixed, so switch/unroll agree bitwise
            "loss": fold_sum(losses) / losses.shape[0],
            "comm_rate": stats.comm_rate,
            "any_tx": stats.any_tx,
            "num_tx": stats.num_tx,
            "mean_gain": stats.mean_gain,
            "grad_norm": jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(x.astype(jnp.float32)))
                    for x in jax.tree_util.tree_leaves(agg)
                )
            ),
            "wire_bytes": stats.wire_bytes,
        }
        if act is not None:
            # active-only accounting: inactive agents are excluded from
            # every mean/rate (their alphas/gains/delivereds are already
            # masked to zero above, so only the denominators change)
            metrics["loss"] = fold_sum(losses * act) / n_act
            metrics["comm_rate"] = stats.num_tx / n_act
            metrics["mean_gain"] = fold_sum(gains) / n_act
            metrics["num_active"] = fold_sum(act)
        if use_net:
            # the attempted/delivered split: comm_rate/any_tx/num_tx and
            # wire_bytes_attempted price the DECISIONS (what agents put
            # on the wire); wire_bytes is redefined to what ARRIVED —
            # the bytes the budget controllers are accountable for.
            # Under a delay channel ``delivereds`` are the
            # staleness-discounted APPLICATION weights of the matured
            # payloads, so the delivered metrics price what entered the
            # aggregate this round.  Emitted only on net_state-carrying
            # traces so channel-free programs keep the exact
            # METRIC_KEYS signature.
            dstats = comm_stats(delivereds, gains, structural=sb,
                                ratios=ratios)
            metrics["wire_bytes"] = dstats.wire_bytes
            metrics["wire_bytes_attempted"] = stats.wire_bytes
            metrics["num_delivered"] = dstats.num_tx
            metrics["delivered_rate"] = dstats.comm_rate
            stale_col = net_rows(new_net)[:, 0]
            if act is not None:
                metrics["delivered_rate"] = dstats.num_tx / n_act
                metrics["mean_staleness"] = fold_sum(
                    stale_col * act
                ) / n_act
            else:
                metrics["mean_staleness"] = (
                    fold_sum(stale_col) / stale_col.shape[0]
                )
        if agent_metrics:
            # per-agent vectors for tier-level accounting (a (1,)-long
            # ratio tuple is the homogeneous case and broadcasts);
            # agent_bytes prices DELIVERED bytes under a channel —
            # identical tracer to the decision vector without one
            metrics["agent_tx"] = alphas
            metrics["agent_bytes"] = per_agent_wire_bytes(
                delivereds, structural=sb, ratios=ratios
            )
            if act is not None:
                metrics["agent_active"] = act
            if use_net:
                metrics["agent_delivered"] = delivereds
                metrics["agent_staleness"] = net_rows(new_net)[..., 0]
            if needs_ctrl and new_ctrl is not None:
                # the controllers' per-agent thresholds — the λ
                # trajectories the adaptive benchmarks plot
                metrics["agent_lam"] = new_ctrl[..., 0]
        return (
            TrainState(state.step + 1, params, opt_state, new_ef,
                       new_ctrl, new_net),
            metrics,
        )

    return train_step


class HybridMachinery(NamedTuple):
    """The resolved policy machinery behind the hybrid dispatch path.

    ``make_triggered_train_step`` assembles this inline; the fleet-
    sharded step (:mod:`repro.sharding.agent_shard`) builds the same
    pieces through :func:`build_hybrid_machinery` so the shard_map'd
    program runs exactly the per-agent ops the single-device hybrid
    step runs — just partitioned over the mesh's agent axes.
    """

    bank: Any                        # deduped StageBank over the agents
    grad_prologue: Callable          # (params, agent_batch) -> (loss, grad)
    prologue_fns: Tuple[Callable, ...]
    scan_batch_free: bool            # epilogues never touch the batch
    chains: Tuple[Any, ...]          # per-agent chain (wire pricing)
    needs_ef: bool
    needs_ctrl: bool
    needs_net: bool


def build_hybrid_machinery(
    loss_fn: Callable,
    cfg: TrainConfig,
    *,
    policy=None,
    aux_loss_fn: Optional[Callable] = None,
    use_kernel: bool = False,
    oracle: Optional[tuple] = None,
) -> HybridMachinery:
    """Resolve a policy into the hybrid dispatch's stage-bank machinery.

    Homogeneous policies are widened to a per-agent tuple so the result
    is ALWAYS a (deduped, so P=1 in that case) :class:`StageBank` — the
    uniform substrate the sharded train step dispatches into.  The
    returned ``grad_prologue`` is the barrier-free per-agent
    ``value_and_grad`` (the only variant that composes under
    vmap/shard_map).
    """
    if cfg.microbatches > 1:
        loss_fn = _microbatched(loss_fn, cfg.microbatches)
        if aux_loss_fn is not None:
            aux_loss_fn = _microbatched(aux_loss_fn, cfg.microbatches)
    resolved = normalize_policy(
        resolve_policy(cfg, policy, use_kernel=use_kernel), cfg.num_agents
    )
    hetero = (
        resolved
        if isinstance(resolved, tuple)
        else (resolved,) * cfg.num_agents
    )
    bank = build_stage_bank(
        hetero, loss_fn=loss_fn, probe_eps=cfg.lr, oracle=oracle
    )

    def objective(params, batch):
        main = loss_fn(params, batch)
        if aux_loss_fn is not None:
            return main + aux_loss_fn(params, batch), main
        return main, main

    def grad_prologue(params, agent_batch):
        (obj, main), g = jax.value_and_grad(objective, has_aux=True)(
            params, agent_batch
        )
        g = constrain_params(g, "")
        return main, g

    prologue_fns, _ = bank.prologues()
    return HybridMachinery(
        bank=bank,
        grad_prologue=grad_prologue,
        prologue_fns=tuple(prologue_fns),
        scan_batch_free=bank.epilogue_batch_free,
        chains=bank.agent_chains(),
        needs_ef=bank.needs_ef,
        needs_ctrl=bank.needs_ctrl,
        needs_net=bank.needs_net,
    )


def make_plain_train_step(loss_fn, optimizer, cfg: TrainConfig, **kw):
    """Dense baseline: every agent always transmits (synchronous SGD)."""
    import dataclasses

    from repro.comm.registry import StageSpec

    resolved = normalize_policy(
        resolve_policy(cfg, kw.pop("policy", None)), cfg.num_agents
    )
    dense = StageSpec("always")
    if isinstance(resolved, tuple):
        policy = tuple(dataclasses.replace(p, trigger=dense) for p in resolved)
    else:
        policy = dataclasses.replace(resolved, trigger=dense)
    return make_triggered_train_step(loss_fn, optimizer, cfg, policy=policy, **kw)
