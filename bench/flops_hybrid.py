"""Operations of a Mamba-2/attention hybrid's step, and of its SSD chunk
kernel, from a configuration's shapes alone.

Kept with the benchmark, in `bench.flops`'s convention: recomputation and
masked-out work do not count, only the operations the mathematics
requires.  The configuration is a Hugging Face ``granitemoehybrid`` config
whose ``layer_types`` give each layer's mixer; every layer has a SwiGLU MLP
of ``shared_intermediate_size``, and the head is tied.
"""
from __future__ import annotations

F32_BYTES = 4   # the model path hands the SSD kernel float32 operands


def _mamba(c: dict) -> tuple[int, int, int, int]:
    """(heads, head width, state size, chunk) of a Mamba-2 mixer."""
    return (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_chunk_size"])


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token: each
    Mamba-2 mixer's in and out projections, each attention layer's q, k, v
    and o, every layer's MLP and the (tied) head.  The embedding lookup,
    the depthwise convolution and the norms are not matrix products."""
    D, F = c["hidden_size"], c["shared_intermediate_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    mh, P, N, _ = _mamba(c)
    d_inner = mh * P
    groups = c["mamba_n_groups"]
    mamba = D * (2 * d_inner + 2 * groups * N + mh) + d_inner * D
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    kinds = c["layer_types"]
    n_mamba = kinds.count("mamba")
    return (n_mamba * mamba + (len(kinds) - n_mamba) * attn
            + len(kinds) * 3 * D * F + D * c["vocab_size"])


def ssd_flops_per_token(c: dict) -> float:
    """Forward FLOPs per token of one Mamba-2 layer's SSD in its chunked
    dual form, the causal half of each chunk's square: ``C B^T`` over the
    chunk (Q N), its masked product with ``x dt`` per head (H Q P), the
    chunk's outgoing state (2 H P N) and ``C`` against the entering state
    (2 H P N)."""
    H, P, N, Q = _mamba(c)
    return Q * N + H * Q * P + 4 * H * P * N


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per token at ``seq``: 6 per matrix
    parameter, 6 L S H hd for the attention layers' causal scores and
    weighted sums, and three times each Mamba-2 layer's SSD forward."""
    H = c["num_attention_heads"]
    hd = c["hidden_size"] // H
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    return (6.0 * matmul_params(c) + 6.0 * n_attn * seq * H * hd
            + 3.0 * kinds.count("mamba") * ssd_flops_per_token(c))


def ssd_kernel_cost(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call of the SSD chunk kernel over one
    layer's (batch, seq): per chunk ``C B^T``, its masked product with
    ``x dt`` and the outgoing state, causal halves; one pass over x and its
    output (B S H P), dt and the within-chunk decay sums (B S H), B and C
    (B S N), and the chunk states written (B S/Q H P N), in float32."""
    H, P, N, Q = _mamba(c)
    chunks = batch * seq // Q
    flops = chunks * (Q * Q * N + H * Q * Q * P + 2 * H * Q * P * N)
    words = batch * seq * (2 * H * P + 2 * H + 2 * N) + chunks * H * P * N
    return float(flops), float(words * F32_BYTES)


def ssd_roofline_s(c: dict, batch: int, seq: int, peaks: dict) -> float:
    """The least time one kernel call can take on a chip of ``peaks``: the
    larger of its FLOPs over the bf16 peak and its bytes over HBM's
    bandwidth."""
    flops, nbytes = ssd_kernel_cost(c, batch, seq)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
