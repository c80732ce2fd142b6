"""Model FLOPs of one training token, counted from the shapes.

Forward matmuls, times three for forward + backward: the attention
projections, the causal attention scores and values, the MLP or, for a
mixture of experts, the router and the ``experts_per_token`` routed experts
only, and the output head over the real (unpadded) vocabulary. Not the
embedding lookup, which is a gather, and not what rematerialisation
recomputes. (``configs.base.flops_per_token`` is 6 x all parameters: it
counts the lookup as a matmul and leaves attention out.)
"""
from __future__ import annotations


def forward_flops_per_token(cfg, seq_len: int) -> float:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    proj = 2 * d * (q + 2 * kv) + 2 * q * d
    # causal: query i attends to i + 1 keys, (S + 1) / 2 on average; one
    # multiply-add for the score and one for the value, per key and width
    core = 2 * 2 * q * (seq_len + 1) / 2
    mats = 3 if cfg.glu else 2
    if cfg.num_experts:
        mlp = 2 * d * cfg.num_experts \
            + cfg.experts_per_token * mats * 2 * d * cfg.d_ff
    else:
        mlp = mats * 2 * d * cfg.d_ff
    return cfg.num_layers * (proj + core + mlp) + 2 * d * cfg.vocab_size


def train_flops_per_token(cfg, seq_len: int) -> float:
    return 3 * forward_flops_per_token(cfg, seq_len)
