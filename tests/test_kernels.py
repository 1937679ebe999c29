"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracles, in Pallas interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import causal_tiles
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ltrf_matmul.ops import (
    VMEM_LIMIT, ltrf_matmul, matmul_plan, pick_blocks, vmem_bytes,
)
from repro.kernels.ltrf_matmul.ref import matmul_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.models.layers import causal_attention


def _tol(dtype):
    return dict(rtol=3e-2, atol=8e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ltrf_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (300, 500, 200), (64, 1024, 96)])
def test_matmul_shapes_dtypes(shape, dtype):
    M, K, N = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)).astype(dtype)
    got = ltrf_matmul(x, w, bm=128, bk=128, bn=128, interpret=True)
    want = matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("blocks", [(128, 128, 128), (128, 256, 128)])
def test_matmul_block_sweep(blocks):
    bm, bk, bn = blocks
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 512)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (512, 256)).astype(jnp.bfloat16)
    got = ltrf_matmul(x, w, bm=bm, bk=bk, bn=bn, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(matmul_ref(x, w), np.float32),
                               **_tol(jnp.bfloat16))


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 3), k=st.integers(1, 4), n=st.integers(1, 3),
       seed=st.integers(0, 100))
@pytest.mark.slow
def test_matmul_property(m, k, n, seed):
    M, K, N = m * 64 + 32, k * 64, n * 64 + 16
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (K, N), jnp.float32)
    got = ltrf_matmul(x, w, bm=128, bk=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(matmul_ref(x, w)),
                               rtol=1e-3, atol=1e-3)


def test_matmul_plan_conflict_free():
    plan, blocks = matmul_plan(4096, 17920, 5120)  # phi3 MLP down-proj scale
    assert plan.num_intervals >= 1
    plan.validate()
    # every prefetch round fits the budget
    assert plan.max_interval_bytes() <= plan.vmem_budget


def test_pick_blocks_mxu_aligned():
    bm, bk, bn = pick_blocks(4096, 5120, 17920)
    assert bm % 128 == bk % 128 == bn % 128 == 0
    assert vmem_bytes(bm, bk, bn, 2) <= VMEM_LIMIT
    # f32 at a 14336-wide K: the double-buffered tiles count at 4 bytes
    bm, bk, bn = pick_blocks(4096, 14336, 4096, dtype_bytes=4)
    assert vmem_bytes(bm, bk, bn, 4) <= VMEM_LIMIT


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [
    dict(B=1, H=2, KV=2, S=128, d=64),   # MHA
    dict(B=2, H=4, KV=2, S=128, d=64),   # GQA 2:1
    dict(B=1, H=8, KV=1, S=256, d=32),   # MQA
])
def test_flash_attention_configs(cfg, dtype):
    B, H, KV, S, d = cfg["B"], cfg["H"], cfg["KV"], cfg["S"], cfg["d"]
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, d)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, KV, S, d)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, KV, S, d)).astype(dtype)
    got = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_non_causal():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 128, 32))
    got = flash_attention(q, k, v, bq=64, bk=64, causal=False, interpret=True)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 50), h=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2]), blocks=st.sampled_from([32, 64]))
@pytest.mark.slow
def test_flash_attention_property(seed, h, g, blocks):
    B, S, d = 1, 128, 32
    H, KV = h * g, h
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, H, S, d))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, KV, S, d))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (B, KV, S, d))
    got = flash_attention(q, k, v, bq=blocks, bk=blocks, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(attention_ref(q, k, v)),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_rows_sum_to_one_property():
    """Causal first row attends only to itself: out[0] == v[0]."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 64, 32))
    got = flash_attention(q, k, v, bq=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got[0, 0, 0]), np.asarray(v[0, 0, 0]),
                               rtol=1e-4, atol=1e-4)


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)


def _xla_path(q, k, v):
    """The model's XLA q-block scan, in the kernel's (B, H, S, d) layout."""
    return _bhsd(causal_attention(_bhsd(q), _bhsd(k), _bhsd(v), q_block=128))


def _value_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(do))]


@pytest.mark.parametrize("S,block", [(256, 128), (512, 128), (256, 256),
                                     (512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,KV", [(2, 2), (4, 2), (4, 1)],
                         ids=["mha", "gqa2", "mqa"])
def test_flash_attention_grad(H, KV, dtype, S, block):
    """Forward and (dq, dk, dv) of the custom VJP against the naive oracle
    and against the model's XLA path, at head width 128."""
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(S + block + H * 7 + KV), 4)
    q = jax.random.normal(ks[0], (1, H, S, d)).astype(dtype)
    k = jax.random.normal(ks[1], (1, KV, S, d)).astype(dtype)
    v = jax.random.normal(ks[2], (1, KV, S, d)).astype(dtype)
    do = jax.random.normal(ks[3], (1, H, S, d)).astype(dtype)
    got = _value_and_grads(
        lambda q, k, v: flash_attention(q, k, v, bq=block, bk=block,
                                        interpret=True), q, k, v, do)
    for want_fn in (attention_ref, _xla_path):
        want = _value_and_grads(want_fn, q, k, v, do)
        for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
            assert g.shape == w.shape, name
            np.testing.assert_allclose(g, w, err_msg=name, **_tol(dtype))


@pytest.mark.parametrize("S,bq,bk,run,skipped", [
    (256, 128, 128, 3, 1),         # n = 2 square tiles: n(n+1)/2 run
    (512, 128, 128, 10, 6),        # n = 4
    (512, 256, 256, 3, 1),
    (4096, 512, 512, 36, 28),      # the train cell: 36 of 64 tiles run
    (512, 128, 256, 6, 2),         # oblong: a tile runs if any pair is causal
])
def test_flash_attention_causal_tiles(S, bq, bk, run, skipped):
    assert causal_tiles(S, bq, bk) == (run, skipped)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 32, 96])
@pytest.mark.parametrize("S", [96, 160])
def test_ssd_chunk_sizes(S, chunk):
    B, H, P, N = 2, 3, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H)))
    A = -jnp.exp(jnp.linspace(0.0, 1.5, H))
    Bm = jax.random.normal(jax.random.PRNGKey(2), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(3), (B, S, N)) * 0.3
    y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, finr = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), rtol=3e-3, atol=3e-3)


def test_ssd_bf16_inputs():
    B, S, H, P, N = 1, 64, 2, 8, 8
    x = (jax.random.normal(jax.random.PRNGKey(0), (B, S, H, P)) * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H))).astype(jnp.bfloat16)
    A = -jnp.exp(jnp.linspace(0.0, 1.0, H))
    Bm = (jax.random.normal(jax.random.PRNGKey(2), (B, S, N)) * 0.3).astype(jnp.bfloat16)
    Cm = (jax.random.normal(jax.random.PRNGKey(3), (B, S, N)) * 0.3).astype(jnp.bfloat16)
    y, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    yr, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=1e-1, atol=1e-1)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 30), chunk=st.sampled_from([8, 16, 32]))
def test_ssd_property_matches_recurrence(seed, chunk):
    B, S, H, P, N = 1, 64, 2, 4, 8
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, H)))
    A = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(seed + 2), (H,)))
    Bm = jax.random.normal(jax.random.PRNGKey(seed + 3), (B, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(seed + 4), (B, S, N)) * 0.3
    y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, finr = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), rtol=5e-3, atol=5e-3)


def test_ssd_decay_monotone_property():
    """With C == B == const and positive x, later states accumulate decay:
    the scan must equal the recurrence even for long horizons (stability)."""
    B, S, H, P, N = 1, 128, 1, 4, 4
    x = jnp.ones((B, S, H, P)) * 0.1
    dt = jnp.ones((B, S, H)) * 0.5
    A = jnp.array([-1.0])
    Bm = jnp.ones((B, S, N)) * 0.2
    Cm = jnp.ones((B, S, N)) * 0.2
    y, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    yr, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4, atol=1e-5)
