"""Granite-4.0-H's pieces on the CPU at a small size: the SSD's custom VJP
(chunk kernel in interpret mode) against autodiff of the XLA form, the
flash kernel at head width 64 with a passed score scale, the SSD's path
choice, and the per-layer pattern's decode through its two-kind cache
against the full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models import decode_step, init_decode_cache, init_params, mamba2
from repro.models.layers import causal_attention
from repro.models.lm import _logits, embed_inputs, forward

# float32 throughout.  The kernel and the XLA form compute the same sums in
# a different order (chunk by chunk against the whole square; online
# softmax against a full one), so they agree to float32 rounding: 2e-5 of
# the largest value.  A bfloat16 operand anywhere would be off by 4e-3.
RTOL = 2e-5


def _close(got, want, what, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    err = np.abs(got - want).max()
    assert err <= rtol * scale, (what, err, scale)


def _ssd_inputs(key, B=2, S=64, H=8, P=16, N=16):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("H", [8, 4], ids=["heads8", "heads4"])
def test_ssd_custom_vjp_matches_autodiff_of_xla_form(chunk, H):
    """Outputs and the gradients of all five inputs of `ops.ssd` (the chunk
    kernel forward, the chunk-by-chunk backward) against ``jax.grad`` of
    `mamba2.ssd_chunked`, under one random cotangent."""
    args = _ssd_inputs(jax.random.PRNGKey(chunk + H), H=H)
    dy = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)
    got, got_vjp = jax.vjp(
        lambda *a: ssd_ops.ssd(*a, chunk=chunk, interpret=True), *args)
    want, want_vjp = jax.vjp(
        lambda *a: mamba2.ssd_chunked(*a, chunk)[0], *args)
    _close(got, want, "y")
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got_vjp(dy),
                          want_vjp(dy)):
        assert g.shape == w.shape, name
        _close(g, w, "d" + name)


def test_ssd_backward_holds_one_chunk_square():
    """No (B, chunks, Q, Q, H) float32 tensor in the backward's program:
    its scan holds one chunk's (B, Q, Q, H)."""
    args = _ssd_inputs(jax.random.PRNGKey(0), S=128)
    f = jax.jit(jax.grad(lambda *a: ssd_ops.ssd(*a, chunk=16,
                                                interpret=True).sum()))
    hlo = f.lower(*args).as_text()
    assert "tensor<2x8x16x16x8xf32>" not in hlo
    assert "tensor<2x16x16x8xf32>" in hlo


@pytest.mark.parametrize("mesh,kernel", [(None, True), ((1, 1), True),
                                         ((2, 2), False)],
                         ids=["no-rules", "one-device", "mesh4"])
def test_ssd_path_selection(mesh, kernel):
    """The Mamba-2 block lowered for a TPU takes the chunk kernel on one
    device and the XLA form on a mesh of four; lowered for the CPU, always
    the XLA form.  ``SSD_STATS`` counts the path lowered."""
    from jax.sharding import AbstractMesh

    from repro.distributed.sharding import ShardingRules, use_rules

    cfg = get_smoke("granite-4.0-h-micro")
    params = jax.eval_shape(lambda k: mamba2.init_mamba2(
        k, cfg.d_model, cfg.ssm_state, cfg.ssm_headdim, cfg.ssm_expand,
        jnp.bfloat16, True)[0], jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 64, cfg.d_model), jnp.bfloat16)
    rules = None if mesh is None else ShardingRules(
        mesh=AbstractMesh(mesh, ("data", "model")))

    def fwd(p, x):
        return mamba2.mamba2_block(p, x, d_state=cfg.ssm_state,
                                   headdim=cfg.ssm_headdim,
                                   expand=cfg.ssm_expand, chunk=cfg.ssm_chunk)

    for platform, want_kernel in (("tpu", kernel), ("cpu", False)):
        mamba2.reset_ssd_stats()
        with use_rules(rules):
            hlo = jax.jit(fwd).trace(params, x).lower(
                lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in hlo) == want_kernel, platform
        assert mamba2.SSD_STATS == {"kernel_calls": int(want_kernel),
                                    "xla_calls": int(not want_kernel)}


@pytest.mark.parametrize("H,KV", [(4, 2), (4, 1)], ids=["gqa2", "mqa"])
def test_flash_attention_head_width_64_with_scale(H, KV):
    """The kernel at Granite's head width 64 and score scale 1/64 (not
    1/sqrt(64)): forward and (dq, dk, dv) against the XLA q-block scan
    given the same scale."""
    from repro.models.layers import flash_block

    S, d, scale = 256, 64, 1 / 64
    assert flash_block(S, d, H, KV) == 256
    ks = jax.random.split(jax.random.PRNGKey(H + KV), 4)
    q = jax.random.normal(ks[0], (1, H, S, d))
    k = jax.random.normal(ks[1], (1, KV, S, d))
    v = jax.random.normal(ks[2], (1, KV, S, d))
    do = jax.random.normal(ks[3], (1, H, S, d))

    def xla(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3)
        return t(causal_attention(t(q), t(k), t(v), q_block=128,
                                  scale=scale))

    got, got_vjp = jax.vjp(lambda *a: flash_attention(
        *a, bq=128, bk=128, interpret=True, scale=scale), q, k, v)
    want, want_vjp = jax.vjp(xla, q, k, v)
    _close(got, want, "o")
    for name, g, w in zip(("dq", "dk", "dv"), got_vjp(do), want_vjp(do)):
        _close(g, w, name)
    # the scale is used: the default's 1/8 gives another answer
    other = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    assert np.abs(np.asarray(other - got)).max() > 1e-2


def test_pattern_decode_matches_forward_logits():
    """Token by token through the two-kind cache (conv and SSM state for
    the Mamba-2 runs, K/V for the attention run) against the full forward's
    logits.  Float32: the decode's recurrence and the forward's chunked SSD
    agree to rounding, 1e-5 of the largest logit (a bfloat16 state would be
    off by 1e-2)."""
    cfg = dataclasses.replace(get_smoke("granite-4.0-h-micro"),
                              dtype="float32")
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, cfg.vocab)
    x, pos = embed_inputs(params, cfg, {"tokens": toks})
    h, _ = forward(params, cfg, x, pos)
    full = _logits(params, cfg, h)
    cache, axes = init_decode_cache(cfg, 2, 24)
    assert [sorted(c) for c in cache["layers"]] == [
        ["conv", "ssm"], ["k", "v"], ["conv", "ssm"]]
    assert jax.tree.structure(cache) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    step = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, cfg))
    outs = []
    for i in range(toks.shape[1]):
        logits, cache = step(params, cache, toks[:, i:i + 1], jnp.int32(i))
        outs.append(logits[:, 0])
    _close(jnp.stack(outs, 1), full, "logits", rtol=1e-5)


def test_pattern_runs_and_parameters():
    """Runs follow ``layer_types``; the parameter count matches the leaves
    and the published config's count; the head is tied."""
    from repro.configs import get_arch

    cfg = get_smoke("granite-4.0-h-micro")
    assert cfg.runs() == [("mamba", 2), ("attention", 1), ("mamba", 1)]
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    assert "lm_head" not in params and len(params["layers"]) == 3
    assert cfg.param_count() == sum(a.size for a in jax.tree.leaves(params))
    full = get_arch("granite-4.0-h-micro")
    assert full.runs()[:3] == [("mamba", 5), ("attention", 1), ("mamba", 9)]
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert 3.1e9 < full.param_count() < 3.3e9
