"""Model FLOPs of one training token: three times the forward count
(forward + backward), which each architecture's module under
``references/`` counts from its configuration file's own sizes
(``forward_flops_per_token``). (``configs.base.flops_per_token`` is 6 x all
parameters: it counts the embedding lookup as a matmul and leaves attention
out.)
"""
from __future__ import annotations


def train_flops_per_token(model, conf: dict, seq_len: int) -> float:
    return 3 * model.forward_flops_per_token(conf, seq_len)
