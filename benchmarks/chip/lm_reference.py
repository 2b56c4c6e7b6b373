"""Plain reference of triggered language-model training, for any model.

It imports nothing of the program, and nothing of any one model: a
configuration's reference module (``configs/<name>.ref.py``) writes out
its model's ``loss(cfg, p, tokens, labels, lowp)`` and hands it to
``train`` here, which follows the configuration's training step: each
agent's gradient of its mean token cross-entropy, the trigger of the
traffic's ``comm`` spec, per-tensor int8 compression with error
feedback, the mean over transmitting agents (arXiv:2103.04140 eq. 10)
and SGD, the parameters kept in the configuration's ``train.dtype``
(each step's -lr·g is rounded to that type and added there).

Departures, on purpose: none in the mathematics.  Gradients, error
feedback and the aggregate stay in float32, where the program keeps
them in the parameters' type.

What lives where, so that a model of about a billion parameters can be
followed on one 16 GB chip: the device holds the parameters in
``train.dtype``, one agent's f32 gradient, the lookahead probe's
parameters in ``train.dtype`` while its loss runs, and the f32 casts of
either only inside a jitted call: about 10 bytes a parameter, plus the
loss's activations.  The host holds each agent's error-feedback memory,
the running aggregate and the initial parameters.  The arithmetic and
its order are those of the loop that kept every tree on the device; the
step's rounding to ``train.dtype`` is a ``reduce_precision``, which the
compiler keeps, where it may fold a pair of casts into none.

``mm`` is the one matmul a model's loss calls.  It runs at the highest
precision, or with ``lowp=True`` as the control, one precision below a
bfloat16 configuration: as fp8 training does, its operands rounded to
e4m3 and the cotangent it receives to e5m2, each under a per-tensor
scale that maps the tensor's largest magnitude to the type's largest
finite value (so small gradients keep their digits, not flush to 0).
"""
from __future__ import annotations

import functools
import json
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


def _round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in its own type: a
    rounding the compiler keeps, where it may fold a pair of casts."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


@functools.lru_cache(maxsize=None)
def _wire(int8: bool):
    @jax.jit
    def wire(g, mem, alpha):
        # one leaf: the payload the agent sends and its residual
        g_eff = g if mem is None else g + mem
        sent = _int8(g_eff) if int8 else g_eff
        return sent, (g_eff - sent) * alpha
    return wire


@functools.lru_cache(maxsize=None)
def _apply(lr: float, pdt):
    @jax.jit
    def apply(p, agg):
        # SGD on parameters held in ``pdt``: the step -lr·g is rounded to
        # the parameters' type and added there
        return jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) + _round_to(-lr * b, pdt)
                          ).astype(pdt), p, agg)
    return apply


@functools.lru_cache(maxsize=None)
def _losses(loss, cfg_json: str, lowp: bool):
    """``value_and_grad`` of ``loss`` and ``loss`` itself, each taking the
    parameters in their own type and casting them up inside the call."""
    cfg = json.loads(cfg_json)

    def up(p):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)

    vg = jax.jit(lambda p, t, y: jax.value_and_grad(
        lambda q: loss(cfg, q, t, y, lowp))(up(p)))
    f_loss = jax.jit(lambda p, t, y: loss(cfg, up(p), t, y, lowp))
    return vg, f_loss


def train(cfg: dict, loss, params0: dict, batches, comm: str, steps: int,
          lowp: bool = False, gain_scale: float = 1.0) -> dict:
    """``steps`` triggered steps from ``params0`` (in ``train.dtype``, the
    layout ``loss`` reads; host arrays, so that the caller holds no copy
    on the device) on ``batches`` (each ``{"tokens", "labels"}`` of shape
    ``(m, B, S)``); ``loss(cfg, p, tokens, labels, lowp)`` is one agent's
    mean next-token cross-entropy.

    Returns per step the mean agent loss, the mean agent trigger gain
    (``-lr·‖g‖²`` for ``grad_norm``, the lookahead probe's loss change
    for the controllers), the norm of the aggregate the optimizer gets
    and, for a controller, each agent's row ``(λ, σ, ĝ)`` after the
    step; per leaf the norm of step 1's aggregate and of the parameters'
    change over all ``steps``.  ``gain_scale`` multiplies each gain as
    it is computed (1 here; a planted fault changes it).

    Each jitted call casts the parameters up itself, and the gradient is
    taken with respect to those f32 values.  The wire runs leaf by leaf:
    the agent's memory comes in from the host, the int8 payload and the
    residual are made, the residual goes back, and α·payload is added to
    the running sum on the host, ``0 + α₀·x₀ + α₁·x₁ …``: the same f32
    operations in the same order, with the products by α ∈ {0, 1} taken
    exactly (a payload is added or left out).  The device divides the sum
    by the transmitting count.  Jitted programs are made once a process,
    for all calls with the same loss, configuration and wire."""
    pol = parse_comm(comm)
    if pol["stages"] not in ([], ["int8"]):
        raise ValueError(f"reference has no wire format {pol['stages']}")
    lr = float(cfg["train"]["lr"])
    pdt = jnp.dtype(cfg["train"]["dtype"])
    trig, args = pol["trigger"], pol["args"]
    f32 = jnp.float32
    vg, f_loss = _losses(loss, json.dumps(cfg, sort_keys=True), lowp)
    wire, apply = _wire(bool(pol["stages"])), _apply(lr, pdt)

    def probe_gain(p, g, t, y, l0):
        # the lookahead probe: one SGD step of size lr, held in ``pdt``;
        # made leaf by leaf, op by op, so that no compiler fuses the step
        probe = {k: (p[k].astype(f32) - lr * g[k]).astype(pdt) for k in p}
        return float(f_loss(probe, t, y)) - l0

    host0 = jax.device_get(params0)
    shapes = {k: np.shape(v) for k, v in host0.items()}
    params = jax.device_put(host0)
    agents = batches[0]["tokens"].shape[0]
    mems = [None] * agents
    ctrl = [[float(args.get("lam0", 0.0)), 0.0, 0.0] for _ in range(agents)]
    out = {"loss": [], "gnorm": [], "gain": [], "ctrl": []}
    for s in range(steps):
        b = batches[s]
        acc, alphas, losses, gains = {}, [], [], []
        for i in range(agents):
            t, y = b["tokens"][i], b["labels"][i]
            l0, g = vg(params, t, y)
            l0 = float(l0)
            losses.append(l0)
            if trig == "grad_norm":
                gsq = gain_scale * float(sum(
                    jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
                alpha = float(gsq >= float(args.get("mu", 0.0)))
                gains.append(-lr * gsq)
            elif trig == "budget_dual":
                lam, sig, gmag = ctrl[i]
                gain = gain_scale * probe_gain(params, g, t, y, l0)
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
            mem, mems[i] = mems[i], ({} if pol["ef"] else None)
            keys, wired = list(g), {}
            for j, k in enumerate(keys + [None]):
                if k is not None:
                    # this leaf's wire runs while the one before comes home
                    wired[k] = wire(g.pop(k), None if mem is None
                                    else mem.pop(k), f32(alpha))
                    for x in wired[k]:
                        x.copy_to_host_async()
                if j:
                    done = keys[j - 1]
                    sent, resid = wired.pop(done)
                    if pol["ef"]:
                        # a copy: a CPU device's array shares its buffer
                        mems[i][done] = np.asarray(resid).copy()
                    if alpha and done in acc:
                        np.add(acc[done], np.asarray(sent), out=acc[done])
                    elif alpha:
                        acc[done] = np.asarray(sent).copy()
            alphas.append(alpha)
        denom = max(sum(alphas), 1.0)
        agg = {k: jnp.asarray(acc.pop(k) if k in acc
                              else np.zeros(shapes[k], np.float32)) / denom
               for k in shapes}
        if s == 0:
            out["agg_leaf_norm"] = {k: float(jnp.linalg.norm(v))
                                    for k, v in agg.items()}
        out["gnorm"].append(float(jnp.sqrt(sum(
            jnp.sum(x * x) for x in jax.tree_util.tree_leaves(agg)))))
        out["loss"].append(float(np.mean(losses)))
        out["gain"].append(float(np.mean(gains)))
        if trig == "budget_dual":
            out["ctrl"].append([list(row) for row in ctrl])
        params = apply(params, agg)
        del agg
    out["dparam_leaf_norm"] = {
        k: float(jnp.linalg.norm(params[k].astype(f32)
                                 - jnp.asarray(host0[k]).astype(f32)))
        for k in params}
    return out
