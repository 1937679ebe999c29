"""LTRF-planned blocked matmul — the paper's prefetch scheme as a TPU kernel.

Mapping (DESIGN.md §2B): the weight matrix lives in HBM (the paper's big/slow
main register file); each (bk x bn) tile is a "register"; VMEM is the
register cache.  Pallas's software pipeline emits the HBM->VMEM copy of tile
t+1 while the MXU consumes tile t — that is exactly the paper's "prefetch
overlapped with other warps' execution", with the grid's K-innermost
iteration order playing the role of the interval schedule and the pipeline's
buffer slots the role of register-cache banks.  `repro.core.plan` chooses
tile shapes so one interval (two in-flight tiles + operand/accumulator
blocks) fits the VMEM budget, and verifies the tile->slot assignment is
conflict-free (no DMA ever targets a slot still being read).

Block shapes must be MXU-aligned (multiples of 128 in the matmul dims); the
wrapper in ops.py pads as needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the kernel requests (``vmem_limit_bytes``): twice the 16 MiB
# default, a quarter of a v5e core's 128 MiB.  `ops.pick_blocks` keeps the
# blocks' allocation under it.
VMEM_LIMIT = 32 * 2 ** 20


def _ltrf_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """Grid (i, j, k): accumulate x[i,k] @ w[k,j] into acc; flush at k end."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ltrf_matmul_kernel(
    x: jax.Array,          # (M, K)
    w: jax.Array,          # (K, N)
    *,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (
        f"unpadded shapes {(M, K, N)} vs blocks {(bm, bk, bn)}")
    out_dtype = out_dtype or x.dtype
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_ltrf_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, w)
