"""Batched operating-point frontiers over the REAL triggered train step.

The paper's headline artifact — loss vs. communication under
event-triggered scheduling — is a *frontier*: the same training run at
many trigger tightnesses.  ``repro.core.regression.sweep`` already
compiles closed-form-simulator frontiers as one program; this module
does the same for the full :func:`repro.core.api.make_triggered_train_step`
path (compressor chains, error feedback, heterogeneous stage banks —
everything the simulator deliberately leaves out), replacing the last
O(grid) Python rerun loop with one ``jit``.

Grid axis layout
----------------
An operating point is the base policy with every trigger's *knob*
multiplied by a ``scale`` — one traced f32 per grid point.  For fixed
triggers the knob is the transmit threshold (λ/μ): the λ-scale axis the
tiered benchmarks sweep.  For the adaptive budget triggers
(``budget_dual``/``budget_window``) λ is closed-loop controller state,
so the scale multiplies the *target* (rate or bytes) instead — the same
grid axis sweeps **communication budgets**; :func:`budget_scales` maps
absolute per-round targets onto it.  The engine stacks the TrainState
``G`` times (every pytree leaf — EF memory and the ``ctrl_state``
controller rows included, so each lane's controllers chase their own
scaled budget) and vmaps the train step as

    vmap(step, in_axes=(0, None, 0))(states, batch, scales)

so parameters, optimizer state and EF residuals evolve per lane while
each round's *batch is shared across lanes* — the same
comparable-operating-points convention as ``sweep``'s shared trial
keys.  The step is built with ``barriers=False`` (the ULP-pinning
``optimization_barrier`` has no vmap batching rule) and
``agent_metrics=True`` (CommStats accounting stays per lane AND per
agent: ``agent_bytes`` lets tiered scenarios check per-tier wire
budgets after the fact).

A second, optional grid coordinate — ``chan_scales`` — sweeps channel
severity for lossy-channel policies (repro.net): it multiplies each
lane's loss probability (divides its rate capacity), so flattening a
loss-rate × budget-scale meshgrid into two aligned ``(G,)`` vectors
compiles the whole 2-D surface as the SAME single ``scan(vmap(step))``
program (``in_axes=(0, None, 0, 0)``).  Channel state (the
``net_state`` staleness/aux rows) stacks per lane like every other
slot; the counter-based per-round randomness is keyed on (seed, step,
agent), so lanes share one delivery stream — common random numbers
across the grid.  ``chan_scales=None`` (the default) is the exact
pre-channel three-argument engine.

One compile per frontier: ``run_frontier`` traces a single
``scan(vmap(step))`` program regardless of ``len(scales)``; the
heterogeneous ``lax.switch`` dispatch keeps its O(#distinct policies)
compile cost because the switch *index* is not batched — only the
operands carry the grid axis.  The default ``hetero_dispatch="hybrid"``
step composes cleanly under the grid vmap: its internal agent-axis vmap
(the shared gradient prologue) simply gains the leading ``(G,)`` batch
dimension — vmap-of-vmap — while the comm-epilogue scan+switch stays
index-unbatched exactly as before (tests/test_frontier.py pins
hybrid/switch/unroll lane-for-lane equality under the grid).
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.api import (
    StepOptions,
    TrainState,
    init_train_state,
    make_triggered_train_step,
)


class FrontierResult(NamedTuple):
    """One batched frontier run.

    ``state`` is the stacked final TrainState (leading ``(G,)`` axis on
    every leaf); ``metrics`` maps each train-step metric to its
    ``(G, K)`` trajectory (``(G, K, m)`` for the per-agent vectors);
    ``scales`` is the ``(G,)`` operating-point grid.  ``chan_scales``
    is the per-lane channel-severity grid, or ``None`` for frontiers
    without a channel axis (the default — identical program to the
    pre-channel engine).
    """

    state: TrainState
    metrics: Dict[str, jnp.ndarray]
    scales: jnp.ndarray
    chan_scales: Optional[jnp.ndarray] = None


def stack_states(state: TrainState, grid_size: int) -> TrainState:
    """Broadcast one TrainState into ``grid_size`` identical lanes."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (grid_size,) + x.shape), state
    )


def budget_scales(targets, base: float) -> jnp.ndarray:
    """Absolute per-round budget targets → a ``(G,)`` scale grid.

    The frontier's grid coordinate multiplies an adaptive trigger's
    target, so a policy built with base target ``base`` (bytes for
    ``budget_window``, rate for ``budget_dual``) swept at
    ``budget_scales(targets, base)`` runs one lane per absolute target
    in ``targets`` — a budget axis instead of a λ axis, same engine,
    same single compile.
    """
    if base <= 0:
        raise ValueError(f"base target must be positive, got {base!r}")
    return jnp.asarray(targets, jnp.float32) / jnp.float32(base)


def batch_fn_arity(batch_fn: Callable) -> int:
    """1 for the classic ``batch_fn(round_key)``, 2 for the
    round-indexed ``batch_fn(round_key, step)`` form (drifting-target
    data modes and agent fault schedules need the round number inside
    the compiled program).  Uninspectable callables default to the
    1-arg contract."""
    try:
        params = inspect.signature(batch_fn).parameters
    except (TypeError, ValueError):
        return 1
    n = 0
    for p in params.values():
        if p.kind == p.VAR_POSITIONAL:
            return 2
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n += 1
    return 2 if n >= 2 else 1


def make_frontier_step(
    loss_fn: Callable,
    optimizer,
    cfg,
    *,
    policy=None,
    aux_loss_fn: Optional[Callable] = None,
    oracle: Optional[tuple] = None,
    hetero_dispatch: str = "hybrid",
    channel_axis: bool = False,
    mesh=None,
    rules=None,
    churn=None,
):
    """Build ``batched_step(states, batch, scales) -> (states, metrics)``.

    The vmapped, barrier-free train step: lane ``i`` advances its own
    TrainState under threshold scale ``scales[i]`` on the shared
    ``batch``.  With ``channel_axis=True`` the returned function takes a
    fourth ``chan_scales`` argument — the per-lane channel-severity
    coordinate (loss-probability multiplier / capacity divisor) vmapped
    alongside ``scales``, so loss-rate × budget-scale surfaces compile
    as the same single program.  Use :func:`run_frontier` for the
    whole-run loop.

    ``mesh`` swaps in the fleet-sharded step
    (:func:`repro.sharding.agent_shard.make_sharded_train_step`): the
    agent axis partitions over the mesh's agent axes and the grid vmap
    batches the shard_map'd program — same single trace, no per-lane
    retrace (``hetero_dispatch`` is ignored; the sharded step is the
    hybrid dispatch partitioned).  ``rules`` optionally overrides the
    mesh's default sharding rules.
    """
    step = make_triggered_train_step(
        loss_fn,
        optimizer,
        cfg,
        policy=policy,
        aux_loss_fn=aux_loss_fn,
        oracle=oracle,
        options=StepOptions(
            hetero_dispatch=hetero_dispatch,
            barriers=False,
            agent_metrics=True,
            mesh=mesh,
            rules=rules,
            churn=churn,
        ),
    )
    if channel_axis:
        return jax.vmap(step, in_axes=(0, None, 0, 0))
    return jax.vmap(step, in_axes=(0, None, 0))


def run_frontier(
    loss_fn: Callable,
    optimizer,
    cfg,
    params: Any,
    *,
    scales,
    steps: int,
    batch_fn: Callable,
    key,
    policy=None,
    aux_loss_fn: Optional[Callable] = None,
    oracle: Optional[tuple] = None,
    hetero_dispatch: str = "hybrid",
    chan_scales=None,
    mesh=None,
    rules=None,
    churn=None,
) -> FrontierResult:
    """Run a whole loss-vs-communication frontier as ONE jitted program.

    ``scales`` is the ``(G,)`` grid of trigger-threshold multipliers —
    ``1.0`` reproduces the base policy exactly (λ·1.0 is the identity
    in IEEE floats): a single lane of :func:`make_frontier_step` driven
    round by round is bit-equal to the plain train-step loop, while
    this function's scanned whole run agrees to ~1 ULP (the scan body
    compiles in a different fusion context; the integer-valued wire
    accounting stays exact).  ``batch_fn(round_key) -> batch`` samples one
    round's per-agent batch inside the scan; every lane consumes the
    same batch.  A two-argument ``batch_fn(round_key, step)``
    additionally receives the traced round index (an i32 scalar) —
    drifting-target data modes evaluate their drift schedule inside the
    scan; the one-argument form keeps the exact pre-feature scan carry.
    ``steps`` rounds are scanned with keys split from ``key``.
    ``churn`` threads a per-agent ``((join, leave), ...)`` activity
    schedule to every lane (see :class:`StepOptions`).

    ``chan_scales`` adds the channel-parameter grid axis: a ``(G,)``
    per-lane channel-severity coordinate (must match ``scales`` in
    length — flatten a loss-rate × budget-scale meshgrid into the two
    aligned vectors), multiplying each lane's channel loss probability
    (dividing its rate capacity).  Lanes share the per-round PRNG
    stream (common random numbers: a delivery lost at severity s is
    lost at every severity ≥ s), so surfaces are comparable point to
    point.  ``None`` (the default) runs the exact pre-channel engine.

    ``mesh``/``rules`` select the fleet-sharded step (see
    :func:`make_frontier_step`) — the same ``scan(vmap(step))`` program
    with the agent axis partitioned over the mesh.
    """
    scales = jnp.asarray(scales, jnp.float32)
    if scales.ndim != 1:
        raise ValueError(f"scales must be a 1-D grid, got shape {scales.shape}")
    grid = int(scales.shape[0])
    if chan_scales is not None:
        chan_scales = jnp.asarray(chan_scales, jnp.float32)
        if chan_scales.shape != scales.shape:
            raise ValueError(
                f"chan_scales must align with scales lane-for-lane: got "
                f"{chan_scales.shape} vs {scales.shape}"
            )
    batched_step = make_frontier_step(
        loss_fn,
        optimizer,
        cfg,
        policy=policy,
        aux_loss_fn=aux_loss_fn,
        oracle=oracle,
        hetero_dispatch=hetero_dispatch,
        channel_axis=chan_scales is not None,
        mesh=mesh,
        rules=rules,
        churn=churn,
    )
    arity = batch_fn_arity(batch_fn)

    def _xs(key):
        keys = jax.random.split(key, steps)
        if arity == 1:
            return keys
        return keys, jnp.arange(steps, dtype=jnp.int32)

    def _batch(x):
        return batch_fn(*x) if arity >= 2 else batch_fn(x)

    if chan_scales is None:
        def _run(params, scales, key):
            state0 = init_train_state(params, optimizer, cfg, policy=policy)
            states = stack_states(state0, grid)

            def body(states, x):
                states, metrics = batched_step(states, _batch(x), scales)
                return states, metrics

            return jax.lax.scan(body, states, _xs(key))

        states, metrics = jax.jit(_run)(params, scales, key)
    else:
        def _run(params, scales, chan_scales, key):
            state0 = init_train_state(params, optimizer, cfg, policy=policy)
            states = stack_states(state0, grid)

            def body(states, x):
                states, metrics = batched_step(
                    states, _batch(x), scales, chan_scales
                )
                return states, metrics

            return jax.lax.scan(body, states, _xs(key))

        states, metrics = jax.jit(_run)(params, scales, chan_scales, key)
    # scan stacks metrics (K, G, ...) — present them grid-major (G, K, ...)
    metrics = {k: jnp.moveaxis(v, 0, 1) for k, v in metrics.items()}
    return FrontierResult(state=states, metrics=metrics, scales=scales,
                          chan_scales=chan_scales)


def frontier_curve(result: FrontierResult) -> Dict[str, jnp.ndarray]:
    """Reduce a frontier run to its per-point curve coordinates.

    Returns ``(G,)`` arrays: ``final_loss`` (last-round train loss),
    ``wire_bytes`` / ``transmissions`` (run totals), ``comm_rate``
    (run mean), plus ``agent_bytes`` ``(G, m)`` run totals when the
    per-agent metrics are present.
    """
    m = result.metrics
    curve = {
        "scale": result.scales,
        "final_loss": m["loss"][:, -1],
        "wire_bytes": jnp.sum(m["wire_bytes"], axis=1),
        "transmissions": jnp.sum(m["num_tx"], axis=1),
        "comm_rate": jnp.mean(m["comm_rate"], axis=1),
    }
    if "agent_bytes" in m:
        curve["agent_bytes"] = jnp.sum(m["agent_bytes"], axis=1)
    if "agent_lam" in m:
        # final per-agent controller thresholds (adaptive policies)
        curve["agent_lam"] = m["agent_lam"][:, -1]
    if "num_active" in m:
        # churn frontiers: run-mean active-agent count per lane
        curve["num_active"] = jnp.mean(m["num_active"], axis=1)
    if result.chan_scales is not None:
        curve["chan_scale"] = result.chan_scales
    if "wire_bytes_attempted" in m:
        # lossy-channel frontiers: wire_bytes above is DELIVERED bytes;
        # expose the attempted total and mean delivery alongside
        curve["wire_bytes_attempted"] = jnp.sum(
            m["wire_bytes_attempted"], axis=1
        )
        curve["delivered_rate"] = jnp.mean(m["delivered_rate"], axis=1)
        curve["mean_staleness"] = m["mean_staleness"][:, -1]
    return curve
