"""Operations and bytes computed from shapes, kept with the benchmark.

These are the numerators of the utilization and roofline metrics.  They
depend only on a configuration's sizes, never on what the program
reports, so a change to the program cannot move them.
"""
from __future__ import annotations


def llama_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul, per token: every projection of
    every layer plus the (tied) LM head.  Norm weights and the embedding
    gather are not matmuls."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def llama_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs per token: 2 per matmul weight, plus the attention
    scores (QKᵀ) and their use on V at 2 FLOPs per multiply-add each,
    over the full key length.  The causal half is counted, as the
    program computes it (PaLM's convention, Chowdhery et al. 2022, B)."""
    attn = (2 * 2 * seq_len * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])
    return 2.0 * llama_matmul_params(cfg) + attn


def llama_train_flops_per_token(cfg: dict, seq_len: int,
                                extra_forwards: int = 0) -> float:
    """Forward and backward (backward = 2 × forward) plus any further
    forward passes the step needs by design, such as a trigger's
    lookahead probe.  Recomputation (checkpointed loss tiles) is not
    counted."""
    fwd = llama_forward_flops_per_token(cfg, seq_len)
    return (3 + extra_forwards) * fwd


def llama_param_count(cfg: dict) -> int:
    """Every parameter: matmul weights, norm weights, the tied table."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    norms = 2 * d * layers + d
    emb = 0 if cfg.get("tie_word_embeddings", True) else cfg["vocab_size"] * d
    return llama_matmul_params(cfg) + norms + emb


def gain_reduce_bytes(elements: int, agents: int) -> float:
    """Bytes one call of the fused gain reduction reads: two f32 inputs
    of ``elements`` entries per agent, each read once."""
    return 2.0 * 4.0 * elements * agents
