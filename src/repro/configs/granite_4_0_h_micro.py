"""granite-4.0-h-micro — Mamba-2 and NoPE GQA layers in a 9:1 pattern, every
layer followed by its own SwiGLU MLP; scaled embeddings, residuals and
logits; tied head.
[hf:ibm-granite/granite-4.0-h-micro config.json (granitemoehybrid); Mamba-2
/ SSD arXiv:2405.21060]"""
from .base import ArchConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
# attention at layers 5, 15, 25 and 35 of 40
LAYER_TYPES = _PERIOD * 3 + _PERIOD[:5] + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="pattern",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab=100352, head_dim=64,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256,
    ssm_conv_bias=True, layer_types=LAYER_TYPES,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=0.015625, rope=False,
    tie_embeddings=True, norm_eps=1e-5,
    source="hf:ibm-granite/granite-4.0-h-micro (granitemoehybrid); hf tier",
)


def smoke() -> ArchConfig:
    return ArchConfig(
        name="granite-4.0-h-micro-smoke", family="pattern",
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=256, head_dim=16,
        ssm_state=16, ssm_headdim=16, ssm_expand=2, ssm_chunk=16,
        ssm_conv_bias=True,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=1 / 16, rope=False,
        tie_embeddings=True, remat="none",
        source="reduced smoke variant",
    )
