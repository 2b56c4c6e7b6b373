"""Fleet-scale agent sharding: the hybrid train step under ``shard_map``.

``make_sharded_train_step`` partitions the triggered train step's agent
axis over the mesh's ``agent`` logical axes (``sharding/rules.py``):
each shard — a *tier gateway* — runs the hybrid dispatch's vmapped
gradient prologue and comm epilogue for only its ``m / #gateways``
agents, then the flat center sum is replaced by a TWO-LEVEL reduce:

    agents --(local masked partial sum)--> gateway
    gateways --(one lax.psum over the agent mesh axes)--> center

so the collective's per-device operand is ONE payload (the model-sized
partial), independent of the fleet size m — the center-side cost is
O(#gateways), verified against ``analysis/hlo_cost`` collective stats
by ``benchmarks/shard_scale.py``.

SPMD uniformity and the epilogue
--------------------------------
The single-device hybrid step dispatches the comm epilogue over the
DISTINCT-POLICY axis with static per-policy gathers (sort-by-policy
blocks).  Under shard_map every gateway must trace the SAME program
while owning a different policy mix, so the sharded epilogue instead
runs a vmapped ``lax.switch`` over the shard's slice of the per-agent
policy-index vector: all P distinct epilogues are union-computed per
agent and selected arithmetic-free, so per-agent values match the
blocked dispatch exactly (compute is P× the minimum — the price of a
uniform program; P is the handful of distinct tiers, not m).

Sketch-native gateway aggregation
---------------------------------
Count-sketch is linear (``encode(Σ αᵢ xᵢ) = Σ αᵢ encode(xᵢ)``), so for
fleets whose every chain is one terminal ``sketch(rows,cols,seed)``
stage, ``sketch_native=True`` merges updates at the gateways WITHOUT
densifying: each agent's payload is encoded once, gateways sum the
(rows, cols) counter grids locally, ONE psum carries grid-sized
operands to the center, and the non-linear median decode runs once on
the merged grid (the FetchSGD "merge then decode" estimator).  Error
feedback stays agent-local and unchanged — each sender knows its own
decode.  By linearity the merged grid equals the encode of the masked
dense sum to a few ULP; the decode-once estimate differs from the
hybrid step's mean-of-decodes (that is the point — one decode at the
center instead of m), so sketch-native is opt-in.

Fallback: a mesh with no shardable agent axis (or a fleet size not
divisible by it — ``agent_pspec`` warns LOUDLY) returns the plain
hybrid step; the sharded path is a strict perf transform, never a
semantic fork.  Params/optimizer state are treated as replicated
(the paper's models are small); FSDP composition is out of scope.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import ef_add, sketch_decode, sketch_encode, sketch_params
from repro.comm.stats import (
    dense_bits,
    dense_entries,
    fold_sum,
    structural_bytes,
)
from repro.configs.base import TrainConfig
from repro.core.api import (
    METRIC_KEYS,
    NET_METRIC_KEYS,
    TrainState,
    _warn_ctrl_state_missing,
    _warn_ef_memory_missing,
    _warn_net_state_missing,
    StepOptions,
    build_hybrid_machinery,
    make_triggered_train_step,
)
from repro.net.channels import net_rows
from repro.sharding.rules import (
    agent_axis_names,
    agent_pspec,
    agent_shard_count,
    resolve_rules,
)
from repro.utils.tree import tree_add_scaled


def sketch_native_params(chains) -> Optional[tuple]:
    """``(rows, cols, seed)`` iff EVERY agent's chain is a single
    terminal sketch stage with identical parameters — the condition
    under which the gateway merge is exactly a sum in sketch space
    (prefix stages would make the per-agent payload differ from the
    tree the encode closes over; differing tables cannot be summed)."""
    if not chains or any(c is None or len(c.stages) != 1 for c in chains):
        return None
    params = {sketch_params(c) for c in chains}
    if len(params) != 1 or None in params:
        return None
    return params.pop()


def make_sharded_train_step(
    loss_fn: Callable,
    optimizer,
    cfg: TrainConfig,
    mesh,
    *,
    policy=None,
    aux_loss_fn: Optional[Callable] = None,
    use_kernel: bool = False,
    oracle: Optional[tuple] = None,
    rules: Optional[dict] = None,
    sketch_native: bool = False,
    agent_metrics: bool = False,
    churn=None,
):
    """Build the fleet-sharded ``train_step(state, batch, scale=None,
    chan_scale=None) -> (state, metrics)``.

    Same contract as ``make_triggered_train_step(...,
    hetero_dispatch="hybrid", barriers=False)`` — per-agent state slots
    (EF memory, controller rows, channel rows), the frontier ``scale``
    / ``chan_scale`` grid coordinates, and the metric key set are all
    identical, and the per-agent/param values agree to a few ULP (the
    two-level reduce re-associates the center sum; integer-valued wire
    accounting stays exact).  The step composes under ``vmap`` /
    ``scan`` unchanged, so ``repro.core.frontier`` can drive it as one
    ``scan(vmap(step))`` program without retracing per lane.

    ``rules`` defaults to ``resolve_rules(mesh)``; the agent axis
    shards over ``rules["agent"]`` (mesh-filtered).  ``sketch_native``
    requires a shardable mesh and a uniformly sketch-terminal fleet
    (see module docstring) and raises ``ValueError`` otherwise.
    """
    rules = rules if rules is not None else resolve_rules(mesh)
    m = cfg.num_agents
    aspec = agent_pspec(mesh, m, rules)  # warns LOUDLY on replication
    axes = agent_axis_names(mesh, rules)
    shards = agent_shard_count(mesh, rules)
    if churn is not None and len(churn) != m:
        raise ValueError(
            f"churn schedule has {len(churn)} entries but num_agents={m}"
        )

    mach = build_hybrid_machinery(
        loss_fn, cfg, policy=policy, aux_loss_fn=aux_loss_fn,
        use_kernel=use_kernel, oracle=oracle,
    )
    skp = sketch_native_params(mach.chains) if sketch_native else None
    if sketch_native and skp is None:
        raise ValueError(
            "sketch_native=True requires every agent's chain to be a "
            "single terminal sketch(rows,cols,seed) stage with identical "
            "parameters — gateway merge is only a sum in sketch space "
            "when all agents share one sketch table"
        )

    if shards <= 1 or aspec == P():
        if sketch_native:
            raise ValueError(
                "sketch_native=True needs a shardable agent axis "
                f"(got {shards} shard(s) over axes {axes!r} for m={m}): "
                "the decode-once estimator only exists on the gateway "
                "path — drop sketch_native or fix the mesh/fleet sizes"
            )
        # 1-gateway fleet (or the replication fallback agent_pspec just
        # warned about): the sharded program IS the hybrid step
        return make_triggered_train_step(
            loss_fn, optimizer, cfg, policy=policy,
            aux_loss_fn=aux_loss_fn, use_kernel=use_kernel, oracle=oracle,
            options=StepOptions(
                hetero_dispatch="hybrid", barriers=False,
                agent_metrics=agent_metrics, churn=churn,
            ),
        )

    bank = mach.bank
    grad_prologue = mach.grad_prologue
    prologue_fns = mach.prologue_fns
    scan_batch_free = mach.scan_batch_free
    chains = mach.chains
    needs_ef, needs_ctrl, needs_net = (
        mach.needs_ef, mach.needs_ctrl, mach.needs_net,
    )
    agent_index = tuple(bank.agent_index)
    use_pre = bool(prologue_fns)
    # static churn schedule → an (m, 2) [join, leave) array sharded
    # like every other per-agent operand; None adds no operand at all
    churn_arr = (
        jnp.asarray([[j, l] for j, l in churn], jnp.int32)
        if churn is not None else None
    )

    def train_step(state: TrainState, batch, scale=None, chan_scale=None):
        use_net = needs_net and state.net_state is not None
        if needs_net and not use_net:
            _warn_net_state_missing()
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

        # static wire pricing — shape-only, the same numbers the hybrid
        # step derives from the stacked sent tree (fake compression
        # keeps the wire tree in the gradients' native dtype, and grads
        # are params-shaped)
        db = dense_bits(state.params)
        sb = structural_bytes(state.params, per_agent=False)
        de = dense_entries(state.params, per_agent=False)
        ratios = tuple(
            c.ratio_for(db, entries=de) if c else 1.0 for c in chains
        )
        ratio_arr = jnp.asarray(ratios, jnp.float32)
        ix_arr = jnp.asarray(agent_index, jnp.int32)

        def body(params, opt_state, step_ctr, scale_a, chan_a, batch_l,
                 mem_l, ctrl_l, net_l, ix_l, ratio_l, churn_l=None):
            # phase 1: this gateway's slice of the vmapped gradient
            # prologue (plus the bank's deduped trigger gain precursors)
            def agent_prologue(ab):
                main, g = grad_prologue(params, ab)
                if not use_pre:
                    return main, g, None
                pre = jnp.stack([
                    jnp.asarray(fn(params, g, ab, main), jnp.float32)
                    for fn in prologue_fns
                ])
                return main, g, pre

            losses, grads, pres = jax.vmap(agent_prologue)(batch_l)

            # phase 2: SPMD-uniform comm epilogue — vmapped switch over
            # the local policy-index slice (every gateway traces the
            # same program; per-agent values are selected exactly)
            if use_net:
                def per_agent(ix, main, g, pre_i, ab, mem_i, ctrl_i,
                              net_i):
                    return jax.lax.switch(
                        ix, branches, params, g, ab, main, step_ctr,
                        mem_i, ctrl_i, scale_a, pre_i, net_i, chan_a,
                    )

                outs = jax.vmap(per_agent)(
                    ix_l, losses, grads, pres,
                    None if scan_batch_free else batch_l,
                    mem_l, ctrl_l, net_l,
                )
                (alphas, gains, sent, new_mem, new_ctrl, delivereds,
                 new_net) = outs
            else:
                def per_agent(ix, main, g, pre_i, ab, mem_i, ctrl_i):
                    return jax.lax.switch(
                        ix, branches, params, g, ab, main, step_ctr,
                        mem_i, ctrl_i, scale_a, pre_i,
                    )

                outs = jax.vmap(per_agent)(
                    ix_l, losses, grads, pres,
                    None if scan_batch_free else batch_l, mem_l, ctrl_l,
                )
                alphas, gains, sent, new_mem, new_ctrl = outs
                delivereds, new_net = alphas, net_l

            # scenario churn: mask this gateway's slice BEFORE the
            # two-level reduce — inactive agents carry zero aggregation
            # weight, zero wire bytes, frozen per-agent state (the same
            # post-dispatch masking the single-device step applies)
            if churn_l is not None:
                act = (
                    (step_ctr >= churn_l[:, 0])
                    & (step_ctr < churn_l[:, 1])
                ).astype(jnp.float32)
                n_act = jnp.maximum(
                    jax.lax.psum(fold_sum(act), axes), 1.0
                )
                alphas = alphas * act
                gains = gains * act
                delivereds = delivereds * act

                def freeze(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(
                            act.reshape(
                                (-1,) + (1,) * (n.ndim - 1)
                            ) > 0.5,
                            n, o,
                        ),
                        new, old,
                    )

                if has_mem:
                    new_mem = freeze(new_mem, mem_l)
                if use_ctrl:
                    new_ctrl = freeze(new_ctrl, ctrl_l)
                if use_net:
                    new_net = freeze(new_net, net_l)
            else:
                act = n_act = None

            # two-level reduce: agents -> gateway (local masked partial
            # sum) -> center (ONE psum whose operand is payload-sized,
            # independent of m)
            den = jnp.maximum(
                jax.lax.psum(fold_sum(delivereds), axes), 1.0
            )
            if skp is not None:
                rows, cols, seed = skp
                # merge in sketch space: encode once per agent, sum the
                # counter grids (linearity), decode ONCE at the center
                g_eff = ef_add(grads, mem_l)

                def enc_leaf(x):
                    return jax.vmap(
                        lambda v: sketch_encode(v, rows, cols, seed)
                    )(x)

                enc = jax.tree_util.tree_map(enc_leaf, g_eff)

                def gw_grid(e):
                    a = delivereds.reshape((-1,) + (1,) * (e.ndim - 1))
                    return jax.lax.psum(jnp.sum(e * a, axis=0), axes)

                merged = jax.tree_util.tree_map(gw_grid, enc)
                agg = jax.tree_util.tree_map(
                    lambda t, p: sketch_decode(
                        t / den, p.shape, p.dtype, rows, cols, seed
                    ),
                    merged, params,
                )
            else:
                def gw_dense(s):
                    a = delivereds.reshape(
                        (-1,) + (1,) * (s.ndim - 1)
                    ).astype(s.dtype)
                    total = jax.lax.psum(jnp.sum(s * a, axis=0), axes)
                    return total / den.astype(s.dtype)

                agg = jax.tree_util.tree_map(gw_dense, sent)

            updates, new_opt = optimizer.update(
                agg, opt_state, params, step_ctr
            )
            new_params = tree_add_scaled(params, updates, 1.0)

            psum = lambda x: jax.lax.psum(x, axes)
            tot_alpha = psum(fold_sum(alphas))
            att_bytes = (sb * psum(fold_sum(alphas * ratio_l))).astype(
                jnp.float32
            )
            # rate denominators: active agents only under churn (same
            # rate semantics as the single-device step's active-masked
            # means; the two-level reduce re-associates as usual)
            loss_num = (
                psum(fold_sum(losses * act)) if act is not None
                else psum(fold_sum(losses))
            )
            rate_den = n_act if act is not None else jnp.float32(m)
            metrics = {
                "loss": loss_num / rate_den,
                "comm_rate": tot_alpha / rate_den,
                "any_tx": jax.lax.pmax(jnp.max(alphas), axes),
                "num_tx": tot_alpha,
                "mean_gain": psum(fold_sum(gains)) / rate_den,
                "grad_norm": jnp.sqrt(
                    sum(
                        jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(agg)
                    )
                ),
                "wire_bytes": att_bytes,
            }
            if act is not None:
                metrics["num_active"] = psum(fold_sum(act))
            if use_net:
                dtot = psum(fold_sum(delivereds))
                metrics["wire_bytes"] = (
                    sb * psum(fold_sum(delivereds * ratio_l))
                ).astype(jnp.float32)
                metrics["wire_bytes_attempted"] = att_bytes
                metrics["num_delivered"] = dtot
                metrics["delivered_rate"] = dtot / rate_den
                stale_col = net_rows(new_net)[:, 0]
                if act is not None:
                    stale_col = stale_col * act
                metrics["mean_staleness"] = psum(
                    fold_sum(stale_col)
                ) / rate_den
            if agent_metrics:
                metrics["agent_tx"] = alphas
                metrics["agent_bytes"] = (
                    sb * ratio_l * delivereds
                ).astype(jnp.float32)
                if use_net:
                    metrics["agent_delivered"] = delivereds
                    metrics["agent_staleness"] = net_rows(new_net)[..., 0]
                if use_ctrl:
                    metrics["agent_lam"] = new_ctrl[..., 0]
                if act is not None:
                    metrics["agent_active"] = act
            return {
                "params": new_params,
                "opt_state": new_opt,
                "mem": new_mem if has_mem else None,
                "ctrl": new_ctrl if use_ctrl else None,
                "net": new_net if use_net else None,
                "metrics": metrics,
            }

        mkeys = list(METRIC_KEYS) + (
            list(NET_METRIC_KEYS) if use_net else []
        )
        if churn_arr is not None:
            mkeys.append("num_active")
        metric_specs = {k: P() for k in mkeys}
        if agent_metrics:
            metric_specs["agent_tx"] = aspec
            metric_specs["agent_bytes"] = aspec
            if use_net:
                metric_specs["agent_delivered"] = aspec
                metric_specs["agent_staleness"] = aspec
            if use_ctrl:
                metric_specs["agent_lam"] = aspec
            if churn_arr is not None:
                metric_specs["agent_active"] = aspec
        in_specs = (P(), P(), P(), P(), P(),
                    aspec, aspec, aspec, aspec, aspec, aspec)
        operands = (
            state.params, state.opt_state, state.step, scale, chan_scale,
            batch, mem, ctrl, net, ix_arr, ratio_arr,
        )
        if churn_arr is not None:
            # churn-free programs keep the exact 11-operand signature
            in_specs = in_specs + (aspec,)
            operands = operands + (churn_arr,)
        out_specs = {
            "params": P(), "opt_state": P(), "mem": aspec,
            "ctrl": aspec, "net": aspec, "metrics": metric_specs,
        }
        out = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(*operands)
        new_state = TrainState(
            state.step + 1, out["params"], out["opt_state"],
            out["mem"] if has_mem else state.ef_memory,
            out["ctrl"] if use_ctrl else state.ctrl_state,
            out["net"] if use_net else state.net_state,
        )
        return new_state, out["metrics"]

    return train_step
