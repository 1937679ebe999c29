"""Architecture + shape configuration substrate.

Every assigned architecture provides an `ArchConfig` (full production config)
plus a `smoke()` reduced config of the same family for CPU tests.  The four
assigned input shapes are defined here once; `input_specs` builds
ShapeDtypeStruct stand-ins (no allocation) for the dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

CONV_K = 4  # Mamba-2's depthwise conv width (models/mamba2.py)


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | vlm | audio | ssm | hybrid | pattern
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_groups: int = 1         # grouped dispatch (align with token sharding)
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv_bias: bool = False  # the depthwise conv adds a bias
    # hybrid (zamba2)
    attn_every: int = 0         # shared attention block period
    # pattern: one mixer kind per layer, each mixer followed by its own MLP
    layer_types: tuple = ()     # "mamba" | "attention" per layer
    embedding_multiplier: float = 1.0   # embeddings scaled by this
    residual_multiplier: float = 1.0    # each block's output, before its add
    logits_scaling: float = 1.0         # logits divided by this
    attention_multiplier: float = 0.0   # score scale; 0 -> head_dim ** -0.5
    rope: bool = True           # False: no position embedding (NoPE)
    tie_embeddings: bool = False  # the head is the embedding's transpose
    # frontends
    n_codebooks: int = 0        # musicgen: parallel EnCodec codebooks
    n_patches: int = 0          # llava: image patch positions (frontend stub)
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    kv_dtype: str = ""          # decode KV-cache dtype ("" -> dtype; e.g. float8_e4m3fn)
    remat: str = "full"         # none | block | full (full = recompute blocks)
    q_block: int = 512          # attention q-block (memory-efficient scan)
    source: str = ""            # provenance note

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def score_scale(self) -> float | None:
        """Attention's score scale, None for ``head_dim ** -0.5``."""
        return self.attention_multiplier or None

    def runs(self) -> list[tuple[str, int]]:
        """``layer_types`` as maximal runs of one kind: [(kind, length)]."""
        out: list[list] = []
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return [(k, n) for k, n in out]

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        D, F, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        H, KV, hd = self.n_heads, self.n_kv_heads, self.hd
        n = V * D  # embed
        if self.n_codebooks:
            n = self.n_codebooks * V * D
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        mlp = 3 * D * F
        if self.layer_types:
            n_mamba = self.layer_types.count("mamba")
            n += n_mamba * self._mamba_params()
            n += (len(self.layer_types) - n_mamba) * attn
            n += len(self.layer_types) * (mlp + 2 * D) + D
            return n if self.tie_embeddings else n + D * V
        if self.family == "moe":
            per_layer = attn + self.n_experts * mlp + D * self.n_experts + 2 * D
            n += L * per_layer
        elif self.family == "ssm":
            n += L * self._mamba_params() + L * D
        elif self.family == "hybrid":
            n += L * self._mamba_params() + L * D
            n += attn + mlp + 2 * D  # one shared block
        else:
            n += L * (attn + mlp + 2 * D)
        n += D  # final norm
        n += D * V * max(self.n_codebooks, 1)  # head
        return n

    def _mamba_params(self) -> int:
        D = self.d_model
        d_inner = self.ssm_expand * D
        nheads = d_inner // self.ssm_headdim
        d_in_proj = 2 * d_inner + 2 * self.ssm_state + nheads
        conv = (CONV_K + self.ssm_conv_bias) * (d_inner + 2 * self.ssm_state)
        return D * d_in_proj + conv + 3 * nheads + d_inner + d_inner * D

    def active_param_count(self) -> int:
        """MoE: params touched per token (top-k experts only)."""
        if self.family != "moe":
            return self.param_count()
        D, F, L = self.d_model, self.d_ff, self.n_layers
        H, KV, hd = self.n_heads, self.n_kv_heads, self.hd
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        mlp = 3 * D * F
        n = self.vocab * D * 2
        n += L * (attn + self.top_k * mlp + D * self.n_experts + 2 * D)
        return n


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cell_is_runnable(arch: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether (arch x shape) is a defined dry-run cell (see DESIGN.md)."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "full quadratic attention at 524k context: skipped per assignment"
    return True, ""


def input_specs(arch: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = arch.jdtype
    if shape.kind in ("train", "prefill"):
        if arch.family == "vlm":
            n_img = arch.n_patches
            return {
                "tokens": jax.ShapeDtypeStruct((B, S - n_img), i32),
                "patches": jax.ShapeDtypeStruct((B, n_img, arch.d_model), f),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if arch.family == "audio":
            K = arch.n_codebooks
            return {
                "codes": jax.ShapeDtypeStruct((B, K, S), i32),
                "labels": jax.ShapeDtypeStruct((B, K, S), i32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
    # decode: one new token against a seq_len-deep cache
    if arch.family == "audio":
        tok = jax.ShapeDtypeStruct((B, arch.n_codebooks, 1), i32)
    else:
        tok = jax.ShapeDtypeStruct((B, 1), i32)
    return {"tokens": tok, "cache_len": jax.ShapeDtypeStruct((), i32)}


def smoke_shape(kind: str = "train") -> ShapeConfig:
    if kind == "decode":
        return ShapeConfig("smoke_decode", 64, 2, "decode")
    return ShapeConfig("smoke_train", 64, 2, "train")
