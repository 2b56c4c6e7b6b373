"""Stage banks — per-agent heterogeneous policies as a two-phase program.

A heterogeneous network gives every agent its own CommPolicy.  Unrolling
a Python loop over agents (the PR-1 path) traces the whole
trigger/compressor stack once per agent — fine at m=2, hopeless at m≥64.
A :class:`StageBank` instead *dedupes* the policies and splits each
agent's round into the two phases the train step dispatches separately:

**Phase 1 — the shared gradient prologue.**  The per-agent
``value_and_grad`` (plus anything else that is the same computation for
every policy) is policy-*independent*: nothing about it needs a
``lax.switch``.  :func:`batch_prologue` batches it over the agent axis
in ONE ``jax.vmap`` — agent-parallel gradient work, the half of the
round that dominates step time.  (The ``hetero_dispatch="switch"`` path
instead carries the prologue along inside its ``lax.scan``, serializing
it per agent; ``"hybrid"`` is the vmapped split.)

**Phase 2 — the comm epilogue.**  Everything that *differs* between
policies — trigger gate, controller update, error-feedback fold-in,
compressor chain, residual update — is built per DISTINCT policy by
:meth:`StageBank.epilogues` with one uniform call signature (the
``lax.switch`` branch contract):

    epilogue(params, grad, batch, local_loss, step, ef_mem
             [, ctrl[, scale[, pre[, net[, chan_scale]]]]])
        -> (alpha, gain, sent, new_ef_mem, new_ctrl)            # lossless
        -> (alpha, gain, sent, new_ef_mem, new_ctrl,
            delivered, new_net)                # net_state-carrying banks

The extended tail only exists when the bank carries a non-trivial
channel AND the TrainState holds a ``net_state`` slot (a static,
trace-time property — see :meth:`StageBank.epilogues`): ``net`` is one
agent's ``(NET_WIDTH,)`` row ``[staleness, aux, uid]``, ``chan_scale``
the frontier's channel-parameter grid coordinate, ``delivered = alpha ×
d`` the realized delivery (channel-free branches alias it to ``alpha``
— zero extra ops for lossless tiers inside a lossy bank).  When the
bank carries a ``delay`` channel (``net_depth > 0``) the net operand is
the enlarged ``(row, line)`` pair, a delay branch's ``sent`` output is
the MATURED payload dequeued from its FIFO line and ``delivered`` its
staleness-discounted application weight ``w ∈ [0, 1]`` — the same
7-tuple contract, with non-delay branches passing the line through
untouched so ``lax.switch`` keeps uniform branch pytrees.  ``retx``
branches ride the same enlarged slot (a 1-deep buffer holding the
payload awaiting retransmission): their ``alpha`` output is the
realized wire ATTEMPT (a re-offer transmits even when the trigger is
shut, unless ``fresh`` re-gates it), ``sent`` the payload the server
receives (buffered on re-offer rounds), and the EF fold of a lost
payload is deferred until its ``k`` re-offers are exhausted.

``ctrl`` is one agent's ``(CTRL_WIDTH,)`` controller row — the
closed-loop threshold state of the budget-adaptive triggers
(repro.comm.triggers) — or ``None`` when the TrainState carries no
controller slot.  ``scale`` is an optional traced f32 scalar: the
frontier engine's operating-point coordinate, multiplying a fixed
trigger's transmit threshold or an adaptive trigger's *target*.  Both
are trailing defaults so the bank keeps ONE branch list for every
caller — the plain train step (6 operands), the controller-carrying
step (7) and the knobbed frontier step (8); either way every branch
sees the same operand count, which is what ``lax.switch`` requires.
(``None`` is a leafless pytree, so a caller that needs ``scale`` but
has no controller state simply passes ``ctrl=None`` through.)

The train step consumes the branch list two ways.  The hybrid default
loops over the DISTINCT POLICIES — branch ``p`` vmaps its epilogue
over its own agents' contiguous sorted-by-policy block
(:meth:`StageBank.policy_blocks` supplies the static gather/merge
layout: correctly-sized blocks, never padded) — so comm work is
agent-parallel and only the policy axis is sequential.
The pre-hybrid ``"switch"`` path instead runs ``lax.switch(idx,
epilogues, ...)`` inside a ``lax.scan`` over the AGENT axis.  Either
way trace/compile cost is O(#distinct policies), not O(m), and because
a scalar switch index lowers to a conditional running exactly the ops
the unrolled loop ran — and vmapped per-agent programs produce
bit-equal results on CPU — the paths are bit-identical
(tests/test_sweep.py; tests/test_frontier.py and tests/test_adaptive.py
at m=64, with EF, controllers, and under the frontier grid vmap).

Why the error-feedback FOLD-IN lives in the epilogue, not the prologue:
``ef_add`` looks shared (an elementwise add), but whether it runs at
all is a property of the policy (``+ef``), and hoisting it into the
prologue would have non-EF agents compute ``g + 0`` — which is NOT a
bitwise no-op for IEEE floats (``-0.0 + 0.0 = +0.0``).  Keeping it per
branch preserves the bit-identity contract; it is O(payload) cheap.

``ef_mem`` is ONE agent's residual tree, or ``None`` when the
TrainState carries no EF memory (a static, trace-time property: every
branch then returns ``None`` and the pytree structures stay uniform).
Non-EF policies return a zeroed residual slot so silent bank members
never leak stale memory.  The controller slot follows the same
discipline: with ``has_ctrl_state=False`` every branch returns ``None``
(zero extra ops — plain policies compile unchanged); with it True,
adaptive branches return their updated row and plain branches pass
their (unused) row through untouched, keeping the ``(m, CTRL_WIDTH)``
carry structurally stable.  An adaptive branch running WITHOUT a
controller slot falls back to its static initial row (``trig.ctrl0`` —
open-loop ``lam0`` gating, no adaptation).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple

import jax

from repro.comm.compressors import CompressorChain
from repro.comm.error_feedback import ef_add, ef_residual
from repro.comm.policy import CommPolicy
from repro.comm.triggers import TriggerFn

# the uniform comm-epilogue signature (the lax.switch branch contract);
# "AgentStage" is the pre-hybrid name, kept as an alias
AgentEpilogue = Callable[..., tuple]
AgentStage = AgentEpilogue


def batch_prologue(grad_fn: Callable) -> Callable:
    """Phase 1 of the hybrid dispatch: ONE ``jax.vmap`` over agents.

    ``grad_fn(agent_batch) -> (local_loss, grad)`` is the shared,
    policy-independent gradient prologue for ONE agent (the train step's
    ``value_and_grad`` of the local objective).  The returned function
    maps the whole stacked batch to stacked ``(losses, grads)`` —
    agent-PARALLEL gradient work, where the scan-carried prologue of the
    ``"switch"`` path runs the same ops sequentially per agent.

    No ``optimization_barrier`` may live inside ``grad_fn`` (the
    primitive has no vmap batching rule); the caller pins the *stacked*
    outputs instead, which serves the same anti-CSE purpose because the
    scan over the epilogues materializes its inputs anyway.
    """
    return jax.vmap(grad_fn)


@dataclass(frozen=True)
class StageBank:
    """Deduped per-agent policies plus their built stages.

    ``policies`` is the bank (first-seen order); ``agent_index[i]`` maps
    agent ``i`` to its bank entry — the ``lax.switch`` index array.
    """

    policies: Tuple[CommPolicy, ...]
    agent_index: Tuple[int, ...]
    triggers: Tuple[TriggerFn, ...]
    chains: Tuple[CompressorChain, ...]
    ef_flags: Tuple[bool, ...]
    adaptive_flags: Tuple[bool, ...] = ()
    # per-branch built ChannelModel, None for channel-free branches AND
    # trivial (@ ideal) channels — they compile identically
    channels: Tuple[Optional[object], ...] = ()

    @property
    def needs_ef(self) -> bool:
        return any(self.ef_flags)

    @property
    def needs_ctrl(self) -> bool:
        """Any bank policy carrying closed-loop controller state?"""
        return any(self.adaptive_flags)

    @property
    def needs_net(self) -> bool:
        """Any bank policy carrying a non-trivial lossy channel?"""
        return any(c is not None for c in self.channels)

    @property
    def net_depth(self) -> int:
        """Max delay-line depth across the bank's channels (0 = no
        delay channels — ``net_state`` stays the bare rows array)."""
        return max(
            (c.depth for c in self.channels if c is not None), default=0
        )

    @property
    def num_agents(self) -> int:
        return len(self.agent_index)

    def agent_chains(self) -> Tuple[CompressorChain, ...]:
        """Per-AGENT compressor chains (for wire-byte accounting)."""
        return tuple(self.chains[i] for i in self.agent_index)

    @property
    def epilogue_batch_free(self) -> bool:
        """Can the epilogue scan run WITHOUT the per-agent batch?

        True when every bank trigger either exposes a prologue (its
        batch consumption moves into the vmapped phase 1, and with a
        precursor supplied it provably never touches ``batch``) or
        declares ``uses_batch = False`` (the scheduling baselines).
        The hybrid dispatch then feeds the switch a leafless ``None``
        batch operand, sparing the scan one per-iteration slice of the
        full data arrays.  A trigger registered without either marker
        conservatively keeps the batch in the scan.
        """
        return all(
            getattr(t, "prologue_key", None) is not None
            or getattr(t, "uses_batch", True) is False
            for t in self.triggers
        )

    def policy_blocks(self) -> Tuple[Tuple[Tuple[int, ...], ...],
                                     Tuple[int, ...]]:
        """Static sort-by-policy layout for the blocked epilogue dispatch.

        The hybrid dispatch runs each bank policy's epilogue vmapped
        over exactly the agents that carry it — a contiguous,
        correctly-sized block per policy.  Returns ``(block_rows,
        inv)``: ``block_rows[p]`` are branch ``p``'s agent indices
        (agent order within the block, never padded), and ``inv[i]`` is
        agent ``i``'s position in the concatenation of the blocks, so
        ``concat(outs)[inv]`` restores agent order.  Both gathers are
        static and arithmetic-free, so the merge is exact.

        This replaced the earlier padded-group layout (every group
        padded to the largest by repeating its first agent): padding is
        harmless at balanced m=64 but pathological for one-big-tier
        fleets, where a 90%-owner policy forces every other branch to
        materialize and compute ~0.9·m discarded duplicate rows.
        """
        rows: list = [[] for _ in self.policies]
        for i, p in enumerate(self.agent_index):
            rows[p].append(i)
        perm = [i for r in rows for i in r]
        inv = [0] * len(perm)
        for pos, i in enumerate(perm):
            inv[i] = pos
        return tuple(tuple(r) for r in rows), tuple(inv)

    def prologues(self) -> Tuple[Tuple[Callable, ...], Tuple[int, ...]]:
        """The bank's deduped trigger prologues (phase-1 gain precursors).

        Returns ``(fns, index)``: ``fns`` are the DISTINCT precursor
        computations (deduped by ``trig.prologue_key`` — valid because
        every bank trigger was built against the same TriggerContext, so
        e.g. all lookahead-probe triggers share ONE probe evaluation),
        and ``index[b]`` maps bank branch ``b`` to its entry in ``fns``
        (``-1`` for triggers with no precursor: always/never/periodic).

        The hybrid dispatch evaluates every ``fns`` entry for every
        agent inside its single prologue vmap — union-compute, the
        price of keeping the prologue un-switched.  It is bounded by
        the number of distinct precursor computations (≤ #distinct
        policies, usually 1) and runs agent-parallel, where the
        scan-carried alternative runs exactly one precursor per agent
        but serially.
        """
        keys: list = []
        fns: list = []
        index: list = []
        for trig in self.triggers:
            key = getattr(trig, "prologue_key", None)
            if key is None:
                index.append(-1)
                continue
            if key not in keys:
                keys.append(key)
                fns.append(trig.prologue)
            index.append(keys.index(key))
        return tuple(fns), tuple(index)

    def epilogues(self, has_ef_memory: bool, has_ctrl_state: bool = False,
                  has_net_state: bool = False) -> Tuple[AgentEpilogue, ...]:
        """Build the uniform-signature comm-epilogue branch per bank
        policy (phase 2 of the two-phase contract; the gradient
        prologue is shared and supplied by the caller — vmapped under
        ``hetero_dispatch="hybrid"``, scan-carried under ``"switch"``).

        ``has_ef_memory`` / ``has_ctrl_state`` / ``has_net_state`` say
        which optional slots the TrainState actually carries this trace
        — all static properties: with a slot absent, EF (resp. the
        controllers, the channels) is off for every branch and all
        branches return ``None`` for it (stable pytree carry, zero
        extra ops).  With ``has_net_state=True`` every branch speaks
        the extended 7-tuple contract ``(alpha, gain, sent, new_mem,
        new_ctrl, delivered, new_net)``; without it, the classic
        5-tuple — so channel-free (and ``@ ideal``) traces stay the
        exact pre-channel program.
        """
        adaptive = self.adaptive_flags or (False,) * len(self.triggers)
        channels = self.channels or (None,) * len(self.triggers)
        _, pre_index = self.prologues()
        return tuple(
            _make_epilogue(trig, chain, use_ef=ef and has_ef_memory,
                           adaptive=ad, use_ctrl=has_ctrl_state,
                           pre_index=pidx, channel=chan,
                           use_net=has_net_state)
            for trig, chain, ef, ad, pidx, chan in zip(
                self.triggers, self.chains, self.ef_flags, adaptive,
                pre_index, channels
            )
        )

    # pre-hybrid spelling of the branch list, kept for callers that
    # predate the prologue/epilogue split
    stages = epilogues


def _make_epilogue(trig: TriggerFn, chain: CompressorChain, *, use_ef: bool,
                   adaptive: bool = False, use_ctrl: bool = False,
                   pre_index: int = -1, channel=None,
                   use_net: bool = False) -> AgentEpilogue:
    def epilogue(params, grad, batch, local_loss, step, ef_mem, ctrl=None,
                 scale=None, pre=None, net=None, chan_scale=None):
        # ``pre`` is the hybrid dispatch's stacked (P,) gain-precursor
        # vector for this agent; the branch selects its own entry.  The
        # kwarg is only forwarded when this trigger declared a prologue
        # (pre_index >= 0), so pre-split trigger closures keep working.
        kw = {"pre": pre[pre_index]} if (
            pre is not None and pre_index >= 0
        ) else {}
        # the channel draw comes FIRST (independent of this round's
        # alpha) so the controllers can price delivered transmissions;
        # branches without a channel alias delivered to alpha below —
        # no extra ops, which keeps mixed banks' lossless tiers exact
        use_chan = use_net and channel is not None and net is not None
        # retx shares the payload-buffer slot (depth > 0) with delay but
        # runs its own round logic — retx_k is the dispatch discriminator
        use_retx = use_chan and channel.retx_k > 0
        use_delay = use_chan and channel.depth > 0 and not use_retx
        eff_scale = scale
        with jax.named_scope("channel"):
            if use_retx:
                from repro.net.channels import retx_round, stale_scale, tx_cost

                cost = tx_cost(grad, chain)
                d, stale, pending, commit = retx_round(
                    channel, net, step, chan_scale, cost
                )
                eff_scale = stale_scale(scale, channel.boost, stale, adaptive)
                if adaptive:
                    kw["delivered"] = d
            elif use_delay:
                from repro.net.channels import delay_round, stale_scale

                d, stale, commit = delay_round(channel, net, step, chan_scale)
                eff_scale = stale_scale(scale, channel.boost, stale, adaptive)
                if adaptive:
                    kw["delivered"] = d
            elif use_chan:
                from repro.net.channels import (
                    channel_round,
                    net_rows,
                    stale_scale,
                    tx_cost,
                )

                cost = tx_cost(grad, chain)
                d, stale, finalize = channel_round(
                    channel, net_rows(net), step, chan_scale, cost
                )
                eff_scale = stale_scale(scale, channel.boost, stale, adaptive)
                if adaptive:
                    kw["delivered"] = d
        with jax.named_scope("trigger"):
            if adaptive:
                # the controller reads its row (or its static init when
                # the state carries no slot — open-loop lam0 gating) and
                # emits the updated row only when there is a slot to
                # carry it
                row = ctrl if use_ctrl else trig.ctrl0
                (alpha, gain), new_row = trig(
                    params, grad, batch, local_loss, step, row, eff_scale,
                    **kw
                )
                new_ctrl = new_row if use_ctrl else None
            else:
                alpha, gain = trig(params, grad, batch, local_loss, step,
                                   eff_scale, **kw)
                new_ctrl = ctrl  # pass the (unused) row through unchanged
        with jax.named_scope("compress"):
            g_eff = ef_add(grad, ef_mem if use_ef else None)
            sent = chain.compress_tree(g_eff) if chain else g_eff
        if use_retx:
            # resolve the retransmit round: alpha becomes the realized
            # wire ATTEMPT (re-offers are priced in attempted bytes),
            # ``sent`` the payload the server actually receives, and
            # ``fold`` the expired buffered payload owed to EF
            with jax.named_scope("channel"):
                attempt, out_sent, delivered, fold, new_net = commit(
                    alpha, sent
                )
            if ef_mem is None:
                new_mem = None
            elif use_ef:
                # compression residual only when THIS round's gradient
                # went to the wire (empty buffer + open gate: the lost
                # payload survives in the buffer, so nothing more is
                # owed); a retransmitting round contributes nothing new;
                # the expired payload folds back WHOLE on final failure
                a_cur = alpha * (1.0 - pending)
                with jax.named_scope("compress"):
                    new_mem = jax.tree_util.tree_map(
                        lambda ge, se, f: (ge - se) * a_cur + f,
                        g_eff, sent, fold,
                    )
            else:
                new_mem = jax.tree_util.tree_map(
                    jax.numpy.zeros_like, ef_mem
                )
            return (attempt, gain, out_sent, new_mem, new_ctrl,
                    delivered, new_net)
        if use_delay:
            # enqueue the payload (iff alpha×d), dequeue the matured
            # head: ``sent`` becomes the MATURED payload and
            # ``delivered`` its staleness-discounted application
            # weight — masked_mean then aggregates old payloads with
            # discounted weights, no new aggregation primitive
            with jax.named_scope("channel"):
                out_sent, delivered, new_net = commit(alpha * d, sent)
        elif use_chan:
            delivered = alpha * d
            with jax.named_scope("channel"):
                new_row = finalize(delivered)
            # inside a delay-carrying bank the net operand is the
            # (row, line) pair; pass the (unused) line through so every
            # switch branch keeps a uniform output pytree
            new_net = (
                (new_row, net[1]) if isinstance(net, tuple) else new_row
            )
        else:
            delivered = alpha       # lossless: delivered IS the decision
            new_net = net           # pass the (unused) slot through
        if ef_mem is None:
            new_mem = None
        elif use_ef:
            # a dropped/rejected transmission folds its WHOLE payload
            # back (for delay lines d is the accept indicator: the EF
            # residual is priced on what entered the wire, not on what
            # matured this round)
            with jax.named_scope("compress"):
                new_mem = ef_residual(g_eff, sent, alpha,
                                      delivered=d if use_chan else None)
        else:
            new_mem = jax.tree_util.tree_map(jax.numpy.zeros_like, ef_mem)
        if use_delay:
            sent = out_sent
        if use_net:
            return alpha, gain, sent, new_mem, new_ctrl, delivered, new_net
        return alpha, gain, sent, new_mem, new_ctrl

    return epilogue


def build_stage_bank(
    policies: Sequence[CommPolicy],
    *,
    loss_fn: Optional[Callable] = None,
    probe_eps: float = 1e-2,
    oracle: Optional[tuple] = None,
) -> StageBank:
    """Dedupe per-agent policies and build their trigger/chain stages.

    Policies hash (frozen dataclasses), so agents sharing a policy share
    one built stage — the bank a 64-agent, 3-tier network compiles is
    exactly 3 branches.
    """
    if not policies:
        raise ValueError("empty policy list")
    bank: list = []
    index: list = []
    seen: dict = {}
    for p in policies:
        if p not in seen:
            seen[p] = len(bank)
            bank.append(p)
        index.append(seen[p])

    def built_channel(p: CommPolicy):
        # trivial (@ ideal) channels collapse to None — the branch then
        # compiles exactly as a channel-free one
        return p.channel_model() if p.needs_net else None

    return StageBank(
        policies=tuple(bank),
        agent_index=tuple(index),
        triggers=tuple(
            p.build_trigger(loss_fn=loss_fn, probe_eps=probe_eps, oracle=oracle)
            for p in bank
        ),
        chains=tuple(p.chain() for p in bank),
        ef_flags=tuple(p.needs_ef for p in bank),
        adaptive_flags=tuple(p.is_adaptive for p in bank),
        channels=tuple(built_channel(p) for p in bank),
    )
