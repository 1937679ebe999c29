"""Operations a step needs, from a configuration's shapes alone.

Kept with the benchmark so that every change is judged by the same count,
whatever implements the step.  Recomputation (rematerialised layers) and
masked-out work (causal tiles computed and then discarded) do not count:
these are the operations the mathematics requires.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    attention and MLP projections of every layer and the output head.  The
    embedding is a lookup and the norms are elementwise, so neither counts."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    mlp = 3 * D * F
    return c["num_hidden_layers"] * (attn + mlp) + D * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a causal decoder at ``seq``.

    Each matrix parameter costs 2 FLOPs per token forward and 4 backward.
    Attention scores and the weighted sum cost 2 * 2 * seq * head_dim FLOPs
    per head and token forward over the full key range; causality halves
    that, and the backward pass doubles it again: 6 * L * seq * H * hd."""
    L, H, hd = (c["num_hidden_layers"], c["num_attention_heads"],
                c["head_dim"])
    return 6.0 * matmul_params(c) + 6.0 * L * seq * H * hd
