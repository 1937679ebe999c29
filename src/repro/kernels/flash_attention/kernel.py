"""Blocked causal attention (FlashAttention-style online softmax) for TPU,
forward and backward.

Forward grid (bh, qi, ki) with the KV axis innermost ('arbitrary'): running
max / sum / accumulator tiles live in VMEM scratch across KV steps, so HBM
traffic is one pass over Q, K, V and one write of O and of the per-row
log-sum-exp — the attention analogue of the LTRF working-set guarantee
(everything the inner loop touches is VMEM-resident; K/V tiles stream
through the pipeline's buffer slots).  No score tile ever reaches HBM.

The backward is two kernels on the same tiles: ``dq`` (grid (bh, qi, ki),
KV innermost) and ``dk``/``dv`` (grid (b·kv, ki, g·qi), the query heads of
one kv head and their q blocks innermost, so the GQA group's sum happens in
the VMEM accumulator).  Both recompute the probabilities from the saved
log-sum-exp; ``di = rowsum(do * o)`` comes in from XLA.

GQA is handled in the index maps: query head h reads kv head h // group.

Causality skips whole tiles: a tile whose first key lies past its last
query (``ki * bk > qi * bq + bq - 1``) does no work, and the index maps
clamp to the last (or first) tile that is needed, so a skipped step fetches
no new block.  Only tiles that cross the diagonal build the element mask.

Products run on the MXU in the inputs' dtype (bf16 on the model path) with
float32 accumulation; the probabilities and their gradients are cast to
that dtype for their products.  The running max, sum, accumulators and
``exp`` stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128          # row statistics are kept lane-broadcast, (rows, 128)
NT = (((1,), (1,)), ((), ()))     # a @ b.T
NN = (((1,), (0,)), ((), ()))     # a @ b


def tile_runs(qi, ki, bq: int, bk: int):
    """Tile (qi, ki) holds at least one unmasked (query, key) pair."""
    return ki * bk <= qi * bq + bq - 1


def tile_crosses_diagonal(qi, ki, bq: int, bk: int):
    """Tile (qi, ki) holds at least one masked pair: it needs the mask."""
    return ki * bk + bk - 1 > qi * bq


def causal_tiles(S: int, bq: int, bk: int) -> tuple[int, int]:
    """(tiles run, tiles skipped) of one head's causal (S // bq) x (S // bk)
    grid."""
    n_q, n_k = S // bq, S // bk
    run = sum(bool(tile_runs(qi, ki, bq, bk))
              for qi in range(n_q) for ki in range(n_k))
    return run, n_q * n_k - run


def _last_k(qi, bq: int, bk: int):
    return (qi * bq + bq - 1) // bk


def _first_q(ki, bq: int, bk: int):
    return (ki * bk) // bq


def _causal_when(causal: bool, qi, ki, bq: int, bk: int, tile):
    """Run ``tile(masked)`` where the tile holds unmasked pairs, building
    the element mask only on tiles that cross the diagonal."""
    if not causal:
        tile(False)
        return
    run = tile_runs(qi, ki, bq, bk)
    cross = tile_crosses_diagonal(qi, ki, bq, bk)
    pl.when(run & cross)(lambda: tile(True))
    pl.when(run & jnp.logical_not(cross))(lambda: tile(False))


def _lanes(stat, n: int):
    """A row statistic kept lane-broadcast, (rows, 128), as (rows, n)."""
    if n % LANES == 0:
        return jnp.tile(stat, (1, n // LANES))
    assert n < LANES, n
    return stat[:, :n]


def _mask(s, q0, k0, rows_are_queries: bool):
    """``s`` with the pairs whose key follows its query set to NEG_INF."""
    r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos, k_pos = (q0 + r, k0 + c) if rows_are_queries else (q0 + c, k0 + r)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, bq: int, bk: int, n_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask(s, qi * bq, ki * bk, True)
        m_prev = m_ref[...]                              # (bq, 128)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - _lanes(m_cur, bk))
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * _lanes(alpha, v.shape[-1]) + \
            jax.lax.dot_general(p.astype(v.dtype), v, NN,
                                preferred_element_type=jnp.float32)

    _causal_when(causal, qi, ki, bq, bk, tile)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, acc_ref.shape[-1])
                      ).astype(o_ref.dtype)
        # (bq, 128) lane-broadcast -> one (1, bq) row, lane-dense in HBM
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1, :]


def flash_forward(q, k, v, *, group: int, bq: int, bk: int,
                  causal: bool = True, interpret: bool = False,
                  scale: float | None = None):
    """q: (BH, S, d); k/v: (BKV, S, d) with BH = BKV * group; scores
    scaled by ``scale``, ``d ** -0.5`` when None.

    Returns ``o`` (BH, S, d) in q's dtype and the per-row log-sum-exp of
    the scaled scores, (BH, 1, S) float32."""
    BH, S, d = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_k = S // bk
    scale = 1.0 / (d ** 0.5) if scale is None else scale

    def kv_map(bh, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_k(qi, bq, bk))
        return bh // group, ki, 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk, n_k=n_k,
                          causal=causal),
        grid=(BH, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, bk, d), kv_map),
            pl.BlockSpec((None, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, S, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, S), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, LANES), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, scale: float, bq: int, bk: int, n_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        k = k_ref[...]
        s = jax.lax.dot_general(q_ref[...], k, NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask(s, qi * bq, ki * bk, True)
        # the (1, bq) rows of lse and di as (bq, 1) columns
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    _causal_when(causal, qi, ki, bq, bk, tile)

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk, dv (the GQA group summed in VMEM)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, bq: int, bk: int, n_q: int,
                n_inner: int, causal: bool):
    ki = pl.program_id(1)
    j = pl.program_id(2)
    qi = j % n_q

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        # transposed scores (bk, bq): the row statistics broadcast as rows
        q, do = q_ref[...], do_ref[...]
        st = jax.lax.dot_general(k_ref[...], q, NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _mask(st, qi * bq, ki * bk, False)
        pt = jnp.exp(st - lse_ref[...])
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[...], do, NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    _causal_when(causal, qi, ki, bq, bk, tile)

    @pl.when(j == n_inner - 1)
    def _flush():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def flash_backward(q, k, v, o, lse, do, *, group: int, bq: int, bk: int,
                   causal: bool = True, interpret: bool = False,
                   scale: float | None = None):
    """Gradients (dq, dk, dv) of ``flash_forward``'s ``o`` given ``do``.

    Shapes and ``scale`` as ``flash_forward``; ``lse`` is its (BH, 1, S)
    output."""
    BH, S, d = q.shape
    BKV = k.shape[0]
    n_q, n_k = S // bq, S // bk
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, None, :]                          # (BH, 1, S)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def kv_map(bh, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_k(qi, bq, bk))
        return bh // group, ki, 0

    q_spec = pl.BlockSpec((None, bq, d), lambda bh, qi, ki: (bh, qi, 0))
    row_spec = pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk, n_k=n_k,
                          causal=causal),
        grid=(BH, n_q, n_k),
        in_specs=[q_spec, pl.BlockSpec((None, bk, d), kv_map),
                  pl.BlockSpec((None, bk, d), kv_map), q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, di)

    def q_block(ki, j):
        qi = j % n_q
        if causal:
            qi = jnp.maximum(qi, _first_q(ki, bq, bk))
        return qi

    q_spec = pl.BlockSpec(
        (None, bq, d), lambda b, ki, j: (b * group + j // n_q, q_block(ki, j), 0))
    row_spec = pl.BlockSpec(
        (None, 1, bq), lambda b, ki, j: (b * group + j // n_q, 0, q_block(ki, j)))
    kv_spec = pl.BlockSpec((None, bk, d), lambda b, ki, j: (b, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, n_q=n_q,
                          n_inner=group * n_q, causal=causal),
        grid=(BKV, n_k, group * n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv
