"""Plain reference of the tiered linear-regression fleet, in jax.numpy.

It imports nothing of the program.  It follows the configuration file:
the paper's data model (arXiv:2103.04140 sec. 4: x ~ N(0, diag(sigma)),
y = x.w* + noise, N fresh samples per agent per round), each agent's
gradient of its local squared loss, the lookahead gain
J_i(w - eps g_i) - J_i(w) (eq. 11), the tier's trigger and budget
controller, its wire format (fp16 cast, per-tensor int8, top-k) with
error feedback, eq. (10)'s mean over the transmitting agents, and SGD.

The inputs are regenerated from the seed by the same draws the traffic
uses (the served loop samples each round from ``key(seed)`` and
``fold_in(key(seed + 1), round)``), so both sides see the same data.
The model's own arithmetic runs in ``dtype`` with matmuls at the
highest precision; ``float32`` is the reference, and ``bfloat16`` is
the control, one precision below the configuration's float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the traffic's own draw of y = x.w* runs at the backend's default
# matmul precision; the reference regenerates it the same way
DATA_PRECISION = jax.lax.Precision.DEFAULT


def _wire_ratio(tier: dict) -> float:
    """Bytes of one transmission over the dense f32 payload."""
    frac, value_bits, index_bits = 1.0, 32.0, 0.0
    for stage in tier["compress"]:
        if stage[0] == "topk":
            frac, index_bits = frac * stage[1], 32.0
        elif stage[0] == "int8":
            value_bits = min(value_bits, 8.0)
        elif stage[0] == "fp16":
            value_bits = min(value_bits, 16.0)
    return frac * (value_bits + index_bits) / 32.0


def problem(cfg: dict, pseed):
    k1, k2 = jax.random.split(jax.random.key(pseed))
    lo, hi = cfg["cov_range"]
    sigma = jax.random.uniform(k1, (cfg["n"],), jnp.float32, lo, hi)
    w_star = jax.random.normal(k2, (cfg["n"],), jnp.float32) * cfg["w_star_scale"]
    return sigma, w_star


def round_batch(cfg: dict, sigma, w_star, pseed, k):
    """Round ``k``'s samples for every agent: ``(m, N, n)``, ``(m, N)``."""
    keys = jax.random.split(
        jax.random.fold_in(jax.random.key(pseed + 1), k), cfg["num_agents"])

    def one(key):
        kx, kn = jax.random.split(key)
        xs = jax.random.normal(
            kx, (cfg["samples_per_agent"], cfg["n"])) * jnp.sqrt(sigma)
        ys = jnp.matmul(xs, w_star, precision=DATA_PRECISION) + cfg[
            "noise_std"] * jax.random.normal(kn, (cfg["samples_per_agent"],))
        return xs, ys

    return jax.vmap(one)(keys)


def _compress(stages, x, dtype):
    """One agent's wire format applied to ``x`` (shape (n,))."""
    for stage in stages:
        if stage[0] == "fp16":
            if jnp.dtype(dtype).itemsize * 8 > 16:
                x = x.astype(jnp.float16).astype(dtype)
        elif stage[0] == "topk":
            k = max(1, int(stage[1] * x.shape[-1]))
            thresh = jax.lax.top_k(jnp.abs(x), k)[0][-1]
            x = x * (jnp.abs(x) >= thresh).astype(dtype)
        elif stage[0] == "int8":
            amax = jnp.max(jnp.abs(x))
            scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
            q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
            x = (q * scale).astype(dtype)
        else:
            raise ValueError(f"unknown wire stage {stage!r}")
    return x


def _trigger(trig: dict, gain, ctrl, cost: float, dtype):
    """Returns (alpha, new ctrl rows) for one tier's agents."""
    if trig["kind"] == "always":
        return jnp.ones_like(gain), ctrl
    lam, sig, gmag = ctrl[:, 0], ctrl[:, 1], ctrl[:, 2]
    eta, beta = trig["eta"], trig["beta"]
    alpha = (gain <= -lam).astype(dtype)
    gmag = (1.0 - beta) * gmag + beta * jnp.abs(gain)
    step = eta * (gmag + 0.25 * lam)
    if trig["kind"] == "budget_dual":
        lam = jnp.maximum(lam + step * (alpha - trig["rate"]), 0.0)
        sig = (1.0 - beta) * sig + beta * alpha
    elif trig["kind"] == "budget_window":
        sig = sig + (alpha * cost - sig) / max(float(trig["window"]), 1.0)
        lam = jnp.maximum(lam + step * (sig - trig["bytes"]) / cost, 0.0)
    else:
        raise ValueError(f"unknown trigger {trig['kind']!r}")
    return alpha, jnp.stack([lam, sig, gmag], axis=1).astype(dtype)


def run(cfg: dict, pseed: int, rounds: int, dtype=jnp.float32) -> dict:
    """The fleet after ``rounds`` rounds from ``w0``: final weights,
    per-tier transmissions and wire bytes (each transmission one dense
    f32 payload of ``4n`` bytes times the tier's wire ratio), and every
    round's transmit decisions ``(rounds, m)``."""
    n, m = cfg["n"], cfg["num_agents"]
    tiers = cfg["tiers"]
    bounds, a = [], 0
    for t in tiers:
        bounds.append((a, a + t["count"]))
        a += t["count"]
    dense = 4.0 * n
    ratios = [_wire_ratio(t) for t in tiers]
    eps = cfg["stepsize"]

    def body(k, carry, pseed, sigma, w_star):
        w, ctrl, mem, tx, seen = carry
        xs, ys = round_batch(cfg, sigma, w_star, pseed, k)
        xs, ys = xs.astype(dtype), ys.astype(dtype)
        r = jnp.einsum("mij,j->mi", xs, w, precision=HIGHEST) - ys
        loss = 0.5 * jnp.mean(r * r, axis=1)
        g = jnp.einsum("mij,mi->mj", xs, r, precision=HIGHEST) / xs.shape[1]
        probe = w[None, :] - eps * g
        rp = jnp.einsum("mij,mj->mi", xs, probe, precision=HIGHEST) - ys
        gain = 0.5 * jnp.mean(rp * rp, axis=1) - loss
        alphas, sents, ctrls, mems, tx_t = [], [], [], [], []
        for t, (lo, hi), ratio in zip(tiers, bounds, ratios):
            alpha, c = _trigger(t["trigger"], gain[lo:hi], ctrl[lo:hi],
                                dense * ratio, dtype)
            g_eff = g[lo:hi] + mem[lo:hi] if t["ef"] else g[lo:hi]
            sent = jax.vmap(lambda x: _compress(t["compress"], x, dtype))(
                g_eff)
            new_mem = ((g_eff - sent) * alpha[:, None] if t["ef"]
                       else jnp.zeros_like(g_eff))
            alphas.append(alpha)
            sents.append(sent)
            ctrls.append(c)
            mems.append(new_mem)
            tx_t.append(jnp.sum(alpha.astype(jnp.float32)))
        alpha = jnp.concatenate(alphas)
        sent = jnp.concatenate(sents)
        agg = jnp.sum(sent * alpha[:, None], axis=0) / jnp.maximum(
            jnp.sum(alpha), 1.0).astype(dtype)
        w = (w - eps * agg).astype(dtype)
        seen = jax.lax.dynamic_update_index_in_dim(
            seen, alpha.astype(jnp.float32), k, 0)
        return (w, jnp.concatenate(ctrls).astype(dtype),
                jnp.concatenate(mems).astype(dtype),
                tx + jnp.stack(tx_t), seen)

    # the round count and the seed are traced, so one compiled program
    # serves every seed and every count up to the buffer's length
    span = -(-max(rounds, 1) // 512) * 512

    @jax.jit
    def go(rounds, pseed):
        sigma, w_star = problem(cfg, pseed)
        w0 = jnp.full((n,), cfg["w0"], dtype)
        init = (w0, jnp.zeros((m, 3), dtype), jnp.zeros((m, n), dtype),
                jnp.zeros((len(tiers),), jnp.float32),
                jnp.zeros((span, m), jnp.float32))
        return jax.lax.fori_loop(
            0, rounds, lambda k, c: body(k, c, pseed, sigma, w_star), init)

    w, _, _, tx, seen = jax.device_get(
        go(jnp.int32(rounds), jnp.uint32(pseed)))
    tx = tx.astype("float64")
    return {"w": w.astype("float64"), "tier_tx": tx,
            "tier_bytes": tx * dense * np.asarray(ratios, "float64"),
            "decisions": seen[:rounds].astype("float64")}
