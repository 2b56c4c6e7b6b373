"""Plain reference of SmolLM-135M, in jax.numpy and float32.

It imports nothing of the program.  The model is the published Llama
architecture of SmolLM-135M (pre-norm RMSNorm blocks, rotary positions
with the rotate-half layout, grouped-query attention, SwiGLU MLP, tied
input and output embedding), written out per layer with every matmul
through ``lm_reference.mm``: at the highest precision, or in fp8 for the
control (``lowp=True``).  Training follows the configuration through
``lm_reference.train``; this module gives it the model's loss, and the
benchmark the model's counts.

Parameters use this file's own layout, stacked over layers:
``embed (V, D)``, ``final_norm (D,)`` and, with a leading layer axis,
``attn_norm``, ``wq (D, H, hd)``, ``wk``/``wv (D, KV, hd)``,
``wo (H, hd, D)``, ``ffn_norm``, ``w_gate``/``w_up (D, F)``,
``w_down (F, D)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts, lm_reference

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w_gate",
                "w_up", "w_down")
NORMS = ("attn_norm", "ffn_norm", "final_norm")


def shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = {"attn_norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd),
             "wv": (d, kv, hd), "wo": (h, hd, d), "ffn_norm": (d,),
             "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    out = {"embed": (v, d), "final_norm": (d,)}
    out.update({k: (cfg["num_hidden_layers"],) + s for k, s in layer.items()})
    return out


def init_params(cfg: dict, pseed: int, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the type they are trained in, made on the device
    in one jitted call: matrices N(0, initializer_range), norms ones."""
    shp = shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(seed):
        key = jax.random.fold_in(jax.random.key(seed), 11)
        out = {}
        for i, name in enumerate(sorted(shp)):
            if name in NORMS:
                out[name] = jnp.ones(shp[name], dtype)
            else:
                out[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shp[name],
                    jnp.float32)).astype(dtype)
        return out

    return make(jnp.uint32(pseed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(cfg: dict, p: dict, tokens, labels, lowp: bool = False):
    """Mean next-token cross-entropy of one agent's ``(B, S)`` batch."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    mm = functools.partial(lm_reference.mm, lowp=lowp)
    x = p["embed"][tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope(mm("bsd,dhk->bshk", h, lp["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", h, lp["wk"]), theta)
        v = mm("bsd,dhk->bshk", h, lp["wv"])
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = mm("bqhk,bshk->bhqs", q, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("bhqs,bshk->bqhk", w, v)
        x = x + mm("bqhk,hkd->bqd", o, lp["wo"])
        h = _rms(x, lp["ffn_norm"], eps)
        a = jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"]))
        x = x + mm("bsf,fd->bsd", a * mm("bsd,df->bsf", h, lp["w_up"]),
                   lp["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, {k: p[k] for k in LAYER_LEAVES})
    x = _rms(x, p["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, p["embed"])
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def train(cfg: dict, params0: dict, batches, comm: str, steps: int,
          lowp: bool = False, gain_scale: float = 1.0) -> dict:
    """``lm_reference.train`` of this model's ``loss``."""
    return lm_reference.train(cfg, loss, params0, batches, comm, steps,
                              lowp=lowp, gain_scale=gain_scale)


def train_flops_per_token(cfg: dict, seq_len: int,
                          extra_forwards: int = 0) -> float:
    """FLOPs a training step needs per token by design (``counts.py``)."""
    return counts.llama_train_flops_per_token(cfg, seq_len, extra_forwards)


def param_count(cfg: dict) -> int:
    """Every parameter of the model, the gradient's element count."""
    return counts.llama_param_count(cfg)
