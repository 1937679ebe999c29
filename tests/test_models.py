"""Per-architecture smoke tests: reduced configs, one forward/train step and
one decode step on CPU, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, get_smoke
from repro.models import decode_step, init_decode_cache, init_params, loss_fn
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state

B, S = 2, 64


def _batch(cfg):
    if cfg.family == "vlm":
        return {"tokens": jnp.ones((B, S - cfg.n_patches), jnp.int32),
                "patches": jnp.zeros((B, cfg.n_patches, cfg.d_model), cfg.jdtype),
                "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "audio":
        return {"codes": jnp.ones((B, cfg.n_codebooks, S), jnp.int32),
                "labels": jnp.ones((B, cfg.n_codebooks, S), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_loss(arch_id):
    cfg = get_smoke(arch_id)
    params, axes = init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    loss, metrics = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, _batch(cfg))
    assert jnp.isfinite(loss), arch_id
    assert float(loss) > 0


@pytest.mark.parametrize("arch_id", [
    pytest.param(a, marks=pytest.mark.slow) if a == "zamba2-1.2b" else a
    for a in ARCH_IDS])
def test_smoke_train_step_no_nans(arch_id):
    cfg = get_smoke(arch_id)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)

    @jax.jit
    def step(p, o, b):
        (loss, _), g = jax.value_and_grad(
            lambda pp: loss_fn(pp, b, cfg), has_aux=True)(p)
        return adamw_update(AdamWConfig(lr=1e-3), p, g, o) + (loss,)

    p2, o2, m, loss = step(params, opt, _batch(cfg))
    for leaf in jax.tree.leaves(p2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch_id
    assert jnp.isfinite(m["grad_norm"])
    assert float(m["grad_norm"]) > 0


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_decode_step(arch_id):
    cfg = get_smoke(arch_id)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    cache, _ = init_decode_cache(cfg, B, 32)
    tok = (jnp.ones((B, cfg.n_codebooks, 1), jnp.int32)
           if cfg.family == "audio" else jnp.ones((B, 1), jnp.int32))
    logits, cache2 = jax.jit(
        lambda p, c, t: decode_step(p, c, t, jnp.int32(3), cfg))(params, cache, tok)
    if cfg.family == "audio":
        assert logits.shape == (B, 1, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # cache structurally unchanged
    assert jax.tree.structure(cache2) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_configs_match_assignment(arch_id):
    """The full configs carry the exact assigned hyperparameters."""
    cfg = get_arch(arch_id)
    expect = {
        "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }[arch_id]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expect, (arch_id, got, expect)
    if arch_id == "granite-moe-3b-a800m":
        assert (cfg.n_experts, cfg.top_k) == (40, 8)
    if arch_id == "dbrx-132b":
        assert (cfg.n_experts, cfg.top_k) == (16, 4)
    if arch_id == "mamba2-1.3b":
        assert cfg.ssm_state == 128
    if arch_id == "zamba2-1.2b":
        assert cfg.ssm_state == 64 and cfg.attn_every == 6
    if arch_id == "qwen3-0.6b":
        assert cfg.qk_norm
    if arch_id == "granite-4.0-h-micro":
        assert (cfg.ssm_state, cfg.hd, cfg.layer_types.count("attention"),
                cfg.tie_embeddings, cfg.rope) == (128, 64, 4, True, False)


def test_param_count_sane():
    # analytic parameter counts should be in the right ballpark
    assert 13e9 < get_arch("phi3-medium-14b").param_count() < 16e9
    assert 0.9e9 < get_arch("tinyllama-1.1b").param_count() < 1.4e9
    assert 110e9 < get_arch("dbrx-132b").param_count() < 150e9
    dbrx = get_arch("dbrx-132b")
    assert dbrx.active_param_count() < dbrx.param_count() / 2


@pytest.mark.slow
def test_decode_matches_prefill_logits():
    """Decoding token-by-token must match teacher-forced forward logits."""
    from repro.models.lm import embed_inputs, forward
    cfg = get_smoke("tinyllama-1.1b")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    # teacher-forced
    x, pos = embed_inputs(params, cfg, {"tokens": toks})
    h, _ = forward(params, cfg, x, pos)
    full_logits = h @ params["lm_head"]
    # step-by-step
    cache, _ = init_decode_cache(cfg, 1, 16)
    outs = []
    for t in range(8):
        logits, cache = decode_step(params, cache, toks[:, t:t + 1],
                                    jnp.int32(t), cfg)
        outs.append(logits[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_fp8_kv_cache_decode_close_to_bf16():
    """Quantized (fp8) KV cache: half the decode memory, logits stay close."""
    import dataclasses
    from repro.configs import get_smoke
    cfg = get_smoke("musicgen-large")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.ones((2, cfg.n_codebooks, 1), jnp.int32)

    def run(kv_dtype):
        c = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        cache, _ = init_decode_cache(c, 2, 16)
        logits = None
        for t in range(4):
            logits, cache = decode_step(params, cache, toks, jnp.int32(t), c)
        return np.asarray(logits, np.float32)

    a = run("")                      # bf16 cache
    b = run("float8_e4m3fn")         # fp8 cache
    assert b.nbytes == a.nbytes      # logits same shape/dtype
    # fp8 quantization noise is visible but bounded
    np.testing.assert_allclose(a, b, rtol=0.35, atol=0.6)
    assert np.corrcoef(a.ravel(), b.ravel())[0, 1] > 0.98


@pytest.mark.parametrize("S,H,KV,hd,mesh,block", [
    (4096, 16, 8, 128, None, 512),       # qwen3-0.6b at the train cell's length
    (4096, 16, 8, 128, (1, 1), 512),     # the same under one-device rules
    (384, 16, 8, 128, None, 128),        # the largest tile that divides S
    (4096, 16, 8, 32, None, None),       # head width 32: the test configs
    (4000, 16, 8, 128, None, None),      # S not a multiple of 128
    (4096, 12, 8, 128, None, None),      # kv heads do not divide the heads
    (4096, 16, 8, 128, (2, 2), None),    # a mesh of four devices
], ids=["qwen3", "qwen3-rules", "s384", "hd32", "s4000", "gqa12-8", "mesh4"])
def test_attention_path_selection(S, H, KV, hd, mesh, block):
    """attention_block lowered for a TPU takes the flash kernel exactly where
    its preconditions hold, and ATTN_STATS counts the path it lowered."""
    from jax.sharding import AbstractMesh

    from repro.distributed.sharding import ShardingRules, use_rules
    from repro.kernels.flash_attention.kernel import causal_tiles
    from repro.models import layers

    d_model = 256
    params = jax.eval_shape(lambda key: layers.init_attention(
        key, d_model, H, KV, hd, True, jnp.bfloat16)[0], jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, S, d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((1, S), jnp.int32)
    rules = None if mesh is None else ShardingRules(
        mesh=AbstractMesh(mesh, ("data", "model")))

    def fwd(p, x, pos):
        return layers.attention_block(p, x, n_heads=H, n_kv=KV, head_dim=hd,
                                      positions=pos, qk_norm=True)

    with use_rules(rules):
        assert layers.flash_block(S, hd, H, KV) == block
        stats = dict(layers.reset_attn_stats())
        assert stats == dict.fromkeys(stats, 0)
        hlo = jax.jit(fwd).trace(params, x, pos).lower(
            lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in hlo) == (block is not None)
    if block is None:
        assert layers.ATTN_STATS == {"kernel_calls": 0, "xla_calls": 1,
                                     "tiles_run": 0, "tiles_skipped": 0}
    else:
        run, skipped = causal_tiles(S, block, block)
        assert layers.ATTN_STATS == {"kernel_calls": 1, "xla_calls": 0,
                                     "tiles_run": H * run,
                                     "tiles_skipped": H * skipped}
    # lowered for the CPU, the same step keeps the XLA path
    layers.reset_attn_stats()
    with use_rules(rules):
        jax.jit(fwd).trace(params, x, pos).lower(lowering_platforms=("cpu",))
    assert (layers.ATTN_STATS["kernel_calls"],
            layers.ATTN_STATS["xla_calls"]) == (0, 1)
