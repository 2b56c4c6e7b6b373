"""A model configuration is added as files only.

The program's dense architecture with q/k RMSNorm (``qwen3-32b`` at the
repository's smoke-test cut) has a parameter tree that differs from
Llama's: two norms more in every attention block and an untied output
table.  Its configuration file and plain reference are written here, in
a temporary directory, and run through the LM runner and the harness
under each LM mix of ``BENCHMARK.json``, with no file of the benchmark
changed.  A reference that leaves the q/k norms out must fail.
"""
import copy
import json

import pytest
from chipbench_toy import BENCH, cells_of, run, toy_cut

from benchmarks.chip import harness
from repro.configs import get_config, reduced

REF = '''
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts, lm_reference

QK_NORM = {qk_norm}
NORMS = ("attn_norm", "ffn_norm", "final_norm", "q_norm", "k_norm")


def shapes(cfg):
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = {{"attn_norm": (d,), "wq": (d, h, hd), "wk": (d, kv, hd),
             "wv": (d, kv, hd), "wo": (h, hd, d), "q_norm": (hd,),
             "k_norm": (hd,), "ffn_norm": (d,), "w_gate": (d, f),
             "w_up": (d, f), "w_down": (f, d)}}
    out = {{"embed": (v, d), "head": (v, d), "final_norm": (d,)}}
    out.update({{k: (cfg["num_hidden_layers"],) + s
                for k, s in layer.items()}})
    return out


def init_params(cfg, pseed, dtype=jnp.bfloat16):
    shp, std = shapes(cfg), cfg["initializer_range"]
    key = jax.random.key(pseed)
    return {{k: jnp.ones(s, dtype) if k in NORMS else (std * jax.random.normal(
        jax.random.fold_in(key, i), s)).astype(dtype)
        for i, (k, s) in enumerate(sorted(shp.items()))}}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(cfg, p, tokens, labels, lowp=False):
    eps, theta, hd = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["head_dim"]
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    mm = functools.partial(lm_reference.mm, lowp=lowp)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = mm("bsd,dhk->bshk", h, lp["wq"])
        k = mm("bsd,dhk->bshk", h, lp["wk"])
        if QK_NORM:
            q, k = _rms(q, lp["q_norm"], eps), _rms(k, lp["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        v = mm("bsd,dhk->bshk", h, lp["wv"])
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = mm("bqhk,bshk->bhqs", q, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        x = x + mm("bqhk,hkd->bqd", mm("bhqs,bshk->bqhk", w, v), lp["wo"])
        h = _rms(x, lp["ffn_norm"], eps)
        a = jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"]))
        x = x + mm("bsf,fd->bsd", a * mm("bsd,df->bsf", h, lp["w_up"]),
                   lp["w_down"])
        return x, None

    layers = {{k: v for k, v in p.items()
              if k not in ("embed", "head", "final_norm")}}
    x, _ = jax.lax.scan(layer, p["embed"][tokens], layers)
    logits = mm("bsd,vd->bsv", _rms(x, p["final_norm"], eps), p["head"])
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def train(cfg, params0, batches, comm, steps, lowp=False, gain_scale=1.0):
    return lm_reference.train(cfg, loss, params0, batches, comm, steps,
                              lowp=lowp, gain_scale=gain_scale)


def train_flops_per_token(cfg, seq_len, extra_forwards=0):
    return counts.llama_train_flops_per_token(cfg, seq_len, extra_forwards)


def param_count(cfg):
    return (counts.llama_param_count(cfg)
            + 2 * cfg["head_dim"] * cfg["num_hidden_layers"])
'''


def _sizes(mc) -> dict:
    return {"hidden_size": mc.d_model, "intermediate_size": mc.d_ff,
            "num_hidden_layers": mc.num_layers,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads, "head_dim": mc.head_dim_,
            "vocab_size": mc.vocab_size}


def _configuration() -> dict:
    """The qk-norm configuration's file: the program's widths, what the
    program must show, where each leaf sits, and the toy cut."""
    full = get_config("qwen3-32b")
    keys = {"d_model": "hidden_size", "d_ff": "intermediate_size",
            "num_layers": "num_hidden_layers",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim_": "head_dim",
            "vocab_size": "vocab_size", "norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings"}
    fixed = {"arch_type": "dense", "qk_norm": True, "swa_window": None}
    block = {"attn_norm": "ln_attn", "ffn_norm": "ln_ff", "wq": "attn.wq",
             "wk": "attn.wk", "wv": "attn.wv", "wo": "attn.wo",
             "q_norm": "attn.q_norm", "k_norm": "attn.k_norm",
             "w_gate": "mlp.w_gate", "w_up": "mlp.w_up",
             "w_down": "mlp.w_down"}
    leaves = {"embed": ["embedding"], "head": ["out_embed"],
              "final_norm": ["final_norm"]}
    leaves.update({k: ["blocks", *v.split(".")] for k, v in block.items()})
    return dict(
        _sizes(full), kind="lm_train", rms_norm_eps=full.norm_eps,
        rope_theta=full.rope_theta, tie_word_embeddings=False,
        initializer_range=0.02, seq_len=1024,
        train={"agents": 2, "batch_per_agent": 1, "optimizer": "sgd",
               "lr": 0.05, "dtype": "bfloat16"},
        program={"arch": "qwen3-32b",
                 "expect": dict(keys, **{k: {"value": v}
                                         for k, v in fixed.items()})},
        leaves=leaves,
        toy={"sizes": dict(_sizes(reduced(full)), seq_len=32),
             "program": {"reduced": True}, "pool": 8})


def _cell(tmp_path, mix: str, qk_norm: bool):
    """The LM cell of ``BENCHMARK.json`` under ``mix``, its configuration
    replaced by the qk-norm one written to ``tmp_path``."""
    path = tmp_path / "qknorm.json"
    path.write_text(json.dumps(_configuration()))
    (tmp_path / "qknorm.ref.py").write_text(REF.format(qk_norm=qk_norm))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "qknorm", "file": str(path)})
    workload = next(w for w in bench["workloads"]
                    if w["name"] in cells_of("lm_train")
                    and w["traffic"] == mix)
    workload["config"] = "qknorm"
    return toy_cut(harness.resolve(workload["name"], bench))


MIXES = sorted({w["traffic"] for w in BENCH["workloads"]
                if w["name"] in cells_of("lm_train")})


@pytest.mark.parametrize("mix", MIXES)
def test_qk_norm_configuration_is_correct(tmp_path, mix):
    cell = _cell(tmp_path, mix, qk_norm=True)
    assert cell.ref.__file__ == str(tmp_path / "qknorm.ref.py")
    res = run(cell, seconds=0.5)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(cell.limits)


@pytest.mark.parametrize("mix", MIXES)
def test_reference_without_qk_norms_is_not_correct(tmp_path, mix):
    res = run(_cell(tmp_path, mix, qk_norm=False), seconds=0.5)
    assert res["correct"] is False, res["checks"]


def test_runner_refuses_a_program_that_differs_from_the_file(tmp_path):
    cell = _cell(tmp_path, MIXES[0], qk_norm=True)
    cell.cfg["program"]["expect"]["qk_norm"] = {"value": False}
    with pytest.raises(ValueError, match="differs from the configuration"):
        cell.kind.program_config(cell.cfg)
