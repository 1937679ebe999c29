"""Mamba2 SSD chunk kernel: the intra-chunk dual form on the MXU.

Per grid step (b, c, head block) the kernel computes, entirely in VMEM and
for a block of heads at once:
  * within-chunk decay L[i,j] = exp(cum[i]-cum[j]) (i>=j), where cum is the
    dt*A cumulative sum within the chunk (computed outside: Mosaic has no
    cumsum);
  * Y_intra = ((C B^T) . L) (x*dt)       — two (Q x Q)/(Q x P) matmuls;
  * the chunk's outgoing state  sum_j exp(cum[end]-cum[j]) B_j (x*dt)_j.

The inter-chunk recurrence (a tiny (H,P,N) scan over chunks), the decay
operators exp(cum) and the Y_inter = C . h_prev correction stay outside in
ops.py: they are O(S/Q) sequential work or O(S) elementwise work on small
tensors, while all O(S*Q) math runs here.  This is the paper's interval
structure again: a chunk = one interval whose working set (x, B, C, dt tiles
+ the Q x Q decay) is VMEM-resident; the HBM stream is a single pass.

Heads are blocked 8 at a time (`head_block`) so that every block's last two
dimensions are (8, 128)-aligned or span the whole array, as the TPU
lowering requires.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, state_ref):
    x = x_ref[0, 0].astype(jnp.float32)            # (hb, Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (hb, Q)
    cum = cum_ref[0, 0]                            # (hb, Q) f32
    Bm = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    hb, Q, _ = x.shape

    seg = cum[:, :, None] - cum[:, None, :]        # (hb, Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 2)
    L = jnp.where(ii >= jj, jnp.exp(jnp.clip(seg, -60.0, 0.0)), 0.0)

    xdt = x * dt[:, :, None]                       # (hb, Q, P)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(G[None] * L, xdt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # (hb, Q, P)

    decay_end = jnp.exp(jnp.clip(cum[:, -1:] - cum, -60.0, 0.0))  # (hb, Q)
    state = jax.lax.dot_general(
        xdt * decay_end[:, :, None], jnp.broadcast_to(Bm, (hb, Q, Bm.shape[1])),
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                       # (hb, P, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state


def head_block(H: int) -> int:
    """Heads per grid step: 8 (the sublane tile) when it divides H, else
    all of them, so every block is (8, 128)-aligned or spans its array."""
    return 8 if H % 8 == 0 else H


def ssd_chunk_kernel(x, dt, cum, Bm, Cm, *, chunk: int,
                     interpret: bool = False):
    """x: (B,S,H,P); dt, cum: (B,S,H) (cum: dt*A summed within each chunk);
    Bm/Cm: (B,S,N).

    Returns (y_intra: (B,nc,H,Q,P) f32, states: (B,nc,H,P,N) f32)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q
    hb = head_block(H)

    xc = x.reshape(Bsz, nc, Q, H, P).transpose(0, 1, 3, 2, 4)   # (B,nc,H,Q,P)
    dtc = dt.reshape(Bsz, nc, Q, H).transpose(0, 1, 3, 2)       # (B,nc,H,Q)
    cumc = cum.reshape(Bsz, nc, Q, H).transpose(0, 1, 3, 2)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    grid = (Bsz, nc, H // hb)
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hb, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda b, c, h: (b, c, h, 0)),
            pl.BlockSpec((1, 1, hb, Q), lambda b, c, h: (b, c, h, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, hb, P, N), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nc, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, nc, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(xc, dtc, cumc, Bc, Cc)
