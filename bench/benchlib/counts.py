"""Operations the work requires, from the configuration's published
sizes: the numerator of model FLOP utilization."""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product per token: the attention
    projections, the SwiGLU matrices and the tied head.  The embedding
    gather is no product; biases and norm scales are not matrices."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return L * (q + kv + o + mlp) + cfg["vocab_size"] * d


def param_count(cfg: dict) -> int:
    """Every trained parameter (the tied embedding counted once)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    bias = 0
    if cfg.get("attention_bias", False):
        bias = (cfg["num_attention_heads"]
                + 2 * cfg["num_key_value_heads"]) * hd
    return (matmul_params(cfg) - cfg["vocab_size"] * d      # head is tied
            + cfg["vocab_size"] * d + L * (bias + 2 * d) + d)


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """FLOP one token needs in a training step, forward and backward:
    6 per matmul weight, plus causal attention's 6 * L * S * H * hd
    (scores and the weighted sum, each 2 * S * H * hd for the full
    square forward, halved by the causal mask, tripled by the backward).
    Recomputation under remat is not required, so it is not counted."""
    attn = (6 * cfg["num_hidden_layers"] * seq
            * cfg["num_attention_heads"] * cfg["head_dim"])
    return 6 * matmul_params(cfg) + attn

