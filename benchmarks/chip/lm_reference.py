"""Plain reference of triggered language-model training, for any model.

It imports nothing of the program, and nothing of any one model: a
configuration's reference module (``configs/<name>.ref.py``) writes out
its model's ``loss(cfg, p, tokens, labels, lowp)`` and hands it to
``train`` here, which follows the configuration's training step: each
agent's gradient of its mean token cross-entropy, the trigger of the
traffic's ``comm`` spec, per-tensor int8 compression with error
feedback, the mean over transmitting agents (arXiv:2103.04140 eq. 10)
and SGD, the parameters kept in the configuration's ``train.dtype``
(each step's -lr·g is cast to that type and added there).

Departures, on purpose: none in the mathematics.  Gradients, error
feedback and the aggregate stay in float32, where the program keeps
them in the parameters' type.

``mm`` is the one matmul a model's loss calls.  It runs at the highest
precision, or with ``lowp=True`` as the control, one precision below a
bfloat16 configuration: as fp8 training does, its operands rounded to
e4m3 and the cotangent it receives to e5m2, each under a per-tensor
scale that maps the tensor's largest magnitude to the type's largest
finite value (so small gradients keep their digits, not flush to 0).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x, dtype):
    """``x`` rounded to ``dtype`` under a per-tensor scale."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return _einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                   _fp8(b, jnp.float8_e4m3fn))


def _mm8_fwd(spec, a, b):
    a8, b8 = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return _einsum(spec, a8, b8), (a8, b8)


def _mm8_bwd(spec, res, ct):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_fp8(ct, jnp.float8_e5m2))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(spec, a, b, lowp):
    """``einsum(spec, a, b)`` at the highest precision, or in fp8."""
    return _mm8(spec, a, b) if lowp else _einsum(spec, a, b)


def parse_comm(spec: str) -> dict:
    """``trigger(k=v,...)|stage|...[+ef]`` into a plain description."""
    ef = spec.strip().endswith("+ef")
    parts = [s.strip() for s in spec.strip().removesuffix("+ef").split("|")]
    m = re.fullmatch(r"(\w+)(?:\((.*)\))?", parts[0])
    args = {}
    for kv in filter(None, (m.group(2) or "").split(",")):
        k, v = kv.split("=")
        args[k.strip()] = v.strip()
    return {"trigger": m.group(1), "args": args, "stages": parts[1:],
            "ef": ef}


def _int8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def train(cfg: dict, loss, params0: dict, batches, comm: str, steps: int,
          lowp: bool = False, gain_scale: float = 1.0) -> dict:
    """``steps`` triggered steps from ``params0`` (in ``train.dtype``, the
    layout ``loss`` reads) on ``batches`` (each ``{"tokens", "labels"}``
    of shape ``(m, B, S)``); ``loss(cfg, p, tokens, labels, lowp)`` is
    one agent's mean next-token cross-entropy.

    Returns per step the mean agent loss, the mean agent trigger gain
    (``-lr·‖g‖²`` for ``grad_norm``, the lookahead probe's loss change
    for the controllers), the norm of the aggregate the optimizer gets
    and, for a controller, each agent's row ``(λ, σ, ĝ)`` after the
    step; per leaf the norm of step 1's aggregate and of the parameters'
    change over all ``steps``.  ``gain_scale`` multiplies each gain as
    it is computed (1 here; a planted fault changes it)."""
    pol = parse_comm(comm)
    if pol["stages"] not in ([], ["int8"]):
        raise ValueError(f"reference has no wire format {pol['stages']}")
    lr = float(cfg["train"]["lr"])
    pdt = jnp.dtype(cfg["train"]["dtype"])
    trig, args = pol["trigger"], pol["args"]
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(cfg, p, t, y, lowp)))
    f_loss = jax.jit(lambda p, t, y: loss(cfg, p, t, y, lowp))
    to32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t))

    @jax.jit
    def wire(g, mem, alpha):
        g_eff = g if mem is None else jax.tree_util.tree_map(jnp.add, g, mem)
        sent = (jax.tree_util.tree_map(_int8, g_eff) if pol["stages"]
                else g_eff)
        resid = jax.tree_util.tree_map(lambda a, b: (a - b) * alpha, g_eff,
                                       sent)
        return sent, resid

    @jax.jit
    def apply(p32, agg):
        # SGD on parameters held in ``pdt``: the step -lr·g is cast to
        # the parameters' type and added there
        new = jax.tree_util.tree_map(
            lambda p, a: (p + (-lr * a).astype(pdt).astype(jnp.float32)
                          ).astype(pdt), p32, agg)
        return new, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                           new)

    def probe_gain(p32, g, t, y, l0):
        eps = lr
        probe = jax.tree_util.tree_map(
            lambda p, gg: (p - eps * gg).astype(pdt).astype(jnp.float32),
            p32, g)
        return float(f_loss(probe, t, y)) - l0

    agents = batches[0]["tokens"].shape[0]
    p32 = to32(params0)
    mems = [None] * agents
    ctrl = [[float(args.get("lam0", 0.0)), 0.0, 0.0] for _ in range(agents)]
    out = {"loss": [], "gnorm": [], "gain": [], "ctrl": []}
    for s in range(steps):
        b = batches[s]
        sents, alphas, losses, gains = [], [], [], []
        for i in range(agents):
            t, y = b["tokens"][i], b["labels"][i]
            l0, g = vg(p32, t, y)
            l0 = float(l0)
            losses.append(l0)
            if trig == "grad_norm":
                gsq = gain_scale * float(sum(
                    jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
                alpha = float(gsq >= float(args.get("mu", 0.0)))
                gains.append(-lr * gsq)
            elif trig == "budget_dual":
                lam, sig, gmag = ctrl[i]
                gain = gain_scale * probe_gain(p32, g, t, y, l0)
                gains.append(gain)
                eta = float(args.get("eta", 0.5))
                beta = float(args.get("beta", 0.1))
                rate = float(args["rate"])
                alpha = float(gain <= -lam)
                gmag = (1 - beta) * gmag + beta * abs(gain)
                lam = max(lam + eta * (gmag + 0.25 * lam) * (alpha - rate),
                          0.0)
                ctrl[i] = [lam, (1 - beta) * sig + beta * alpha, gmag]
            elif trig == "always":
                alpha = 1.0
                gains.append(0.0)
            else:
                raise ValueError(f"reference has no trigger {trig!r}")
            sent, resid = wire(g, mems[i], jnp.float32(alpha))
            if pol["ef"]:
                mems[i] = resid
            sents.append(sent)
            alphas.append(alpha)
            del g
        denom = max(sum(alphas), 1.0)
        agg = jax.tree_util.tree_map(
            lambda *xs: sum(a * x for a, x in zip(alphas, xs)) / denom, *sents)
        del sents
        if s == 0:
            out["agg_leaf_norm"] = {k: float(jnp.linalg.norm(v))
                                    for k, v in agg.items()}
        out["gnorm"].append(float(jnp.sqrt(sum(
            jnp.sum(x * x) for x in jax.tree_util.tree_leaves(agg)))))
        out["loss"].append(float(np.mean(losses)))
        out["gain"].append(float(np.mean(gains)))
        if trig == "budget_dual":
            out["ctrl"].append([list(row) for row in ctrl])
        params, p32 = apply(p32, agg)
        del agg
    p0 = to32(params0)
    out["dparam_leaf_norm"] = {
        k: float(jnp.linalg.norm(params[k].astype(jnp.float32) - p0[k]))
        for k in params}
    return out
