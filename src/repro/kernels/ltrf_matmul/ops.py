"""Jit wrapper: LTRF-planned matmul with interval-derived tile sizes.

`ltrf_matmul(x, w)` chooses (bm, bk, bn) so the VMEM that Pallas really
allocates — double-buffered x, w and out tiles plus the fp32 accumulator —
fits the scoped-VMEM limit the kernel requests, then pads to MXU-aligned
blocks and calls the Pallas kernel.  `matmul_plan` gives the matching
`repro.core.plan.plan_for_matmul` interval plan for the weight stream.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.plan import plan_for_matmul

from .kernel import VMEM_LIMIT, ltrf_matmul_kernel
from .ref import matmul_ref


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def vmem_bytes(bm: int, bk: int, bn: int, dtype_bytes: int) -> int:
    """VMEM the kernel allocates: Pallas double-buffers every blocked
    operand (x, w and out tiles), plus the single fp32 accumulator."""
    return (2 * (bm * bk + bk * bn + bm * bn) * dtype_bytes
            + bm * bn * 4)


def pick_blocks(M: int, K: int, N: int, dtype_bytes: int = 2,
                vmem_limit: int = VMEM_LIMIT) -> tuple[int, int, int]:
    """Choose MXU-aligned (bm, bk, bn), largest weight tile first, whose
    `vmem_bytes` fits ``vmem_limit``."""
    bm = min(_round_up(min(M, 256), 128), _round_up(M, 128))
    best = None
    for bk in (2048, 1024, 512, 256, 128):
        for bn in (1024, 512, 256, 128):
            if vmem_bytes(bm, bk, bn, dtype_bytes) <= vmem_limit:
                cand = (bk * bn, bk, bn)
                if best is None or cand > best:
                    best = cand
    assert best is not None
    _, bk, bn = best
    return bm, min(bk, _round_up(K, 128)), min(bn, _round_up(N, 128))


@partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret", "use_plan"))
def ltrf_matmul(x, w, bm: int = 0, bk: int = 0, bn: int = 0,
                interpret: bool = False, use_plan: bool = True):
    """x: (M, K) @ w: (K, N) -> (M, N) via the LTRF-planned Pallas kernel."""
    M, K = x.shape
    _, N = w.shape
    if bm == 0 or bk == 0 or bn == 0:
        bm, bk, bn = pick_blocks(M, K, N, x.dtype.itemsize)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K))) if (Mp, Kp) != (M, K) else x
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else w
    out = ltrf_matmul_kernel(xp, wp, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:M, :N]


def matmul_plan(M: int, K: int, N: int, dtype_bytes: int = 2,
                vmem_budget: int = VMEM_LIMIT):
    """The explicit IntervalPlan for this matmul's weight stream (for
    inspection/validation: one prefetch round per interval, slots
    conflict-free)."""
    bm, bk, bn = pick_blocks(M, K, N, dtype_bytes)
    plan = plan_for_matmul(M, K, N, bk, bn, vmem_budget=vmem_budget,
                           num_slots=2, dtype_bytes=dtype_bytes)
    plan.validate()
    return plan, (bm, bk, bn)


__all__ = ["ltrf_matmul", "matmul_plan", "matmul_ref", "pick_blocks",
           "vmem_bytes"]
