"""Traced workloads: the repo's own kernels and model layers as sim inputs.

Each entry names a real JAX computation (the Pallas kernels' reference
implementations, plus model-layer slices from `repro.models.layers`), the
example shapes to trace it at, and the memory behaviour the SM model should
assume.  `build_traced_workload` traces + lifts + register-allocates it into
a `Workload` the full pipeline (intervals -> ICG -> renumber -> prefetch ->
both sim engines) consumes like any synthetic kernel.

This module imports jax *lazily*: `TRACED_NAMES` and the spec table are
importable from jax-free paths (the workload registry, CLI arg parsing), and
tracing only happens inside the builders.  Lifts are memoized in
`repro.core.plan_cache` keyed by (name, maxregcount, LIFT_REV) so a sweep
traces each kernel once per process.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING

from repro.core.plan_cache import cached_value

if TYPE_CHECKING:  # real import stays lazy: repro.workloads imports us back
    from repro.workloads.suite import Workload

DEFAULT_MAXREGCOUNT = 64


@dataclass(frozen=True)
class TracedSpec:
    """What to trace and how the memory system should treat it."""

    name: str
    builder: object          # () -> (fn, example_args)
    l1_hit: float = 0.85
    while_trips: int = 8


# -- example builders (jax imported inside; shapes via ShapeDtypeStruct) -----

def _matmul():
    import jax
    import jax.numpy as jnp

    from repro.kernels.ltrf_matmul.ref import matmul_ref

    sd = jax.ShapeDtypeStruct
    return matmul_ref, (sd((64, 128), jnp.bfloat16), sd((128, 64), jnp.bfloat16))


def _attention():
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ref import attention_ref

    sd = jax.ShapeDtypeStruct
    return attention_ref, (sd((1, 4, 64, 32), jnp.float32),
                           sd((1, 2, 64, 32), jnp.float32),
                           sd((1, 2, 64, 32), jnp.float32))


def _ssd():
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssd_scan.ref import ssd_ref

    sd = jax.ShapeDtypeStruct
    return ssd_ref, (sd((1, 32, 2, 8), jnp.float32),
                     sd((1, 32, 2), jnp.float32),
                     sd((2,), jnp.float32),
                     sd((1, 32, 8), jnp.float32),
                     sd((1, 32, 8), jnp.float32))


def _rmsnorm():
    import jax
    import jax.numpy as jnp

    from repro.models.layers import rms_norm

    sd = jax.ShapeDtypeStruct
    return rms_norm, (sd((8, 64), jnp.float32), sd((64,), jnp.float32))


def _mlp():
    import jax
    import jax.numpy as jnp

    from repro.models.layers import mlp_block

    sd = jax.ShapeDtypeStruct
    params = {"w_gate": sd((64, 128), jnp.float32),
              "w_up": sd((64, 128), jnp.float32),
              "w_down": sd((128, 64), jnp.float32)}
    return mlp_block, (params, sd((1, 8, 64), jnp.float32))


def _attn_layer():
    import jax
    import jax.numpy as jnp

    from repro.models.layers import causal_attention

    sd = jax.ShapeDtypeStruct

    def layer(q, k, v):
        return causal_attention(q, k, v, q_block=32)

    return layer, (sd((1, 64, 4, 32), jnp.float32),
                   sd((1, 64, 2, 32), jnp.float32),
                   sd((1, 64, 2, 32), jnp.float32))


TRACED_SPECS: dict[str, TracedSpec] = {
    s.name: s for s in (
        TracedSpec("traced_matmul", _matmul, l1_hit=0.9),
        TracedSpec("traced_attention", _attention, l1_hit=0.85),
        TracedSpec("traced_ssd", _ssd, l1_hit=0.8),
        TracedSpec("traced_rmsnorm", _rmsnorm, l1_hit=0.85),
        TracedSpec("traced_mlp", _mlp, l1_hit=0.9),
        TracedSpec("traced_attn_layer", _attn_layer, l1_hit=0.85),
    )
}
TRACED_NAMES: tuple[str, ...] = tuple(TRACED_SPECS)


def build_traced_workload(name: str,
                          maxregcount: int = DEFAULT_MAXREGCOUNT) -> Workload:
    """Trace, lift, and register-allocate one traced workload (memoized)."""
    spec = TRACED_SPECS[name]

    def build() -> "Workload":
        from repro.workloads.suite import Workload

        from .jaxpr_lift import lift_fn
        from .regalloc import allocate_registers

        fn, args = spec.builder()
        lifted = lift_fn(fn, args, name=name, while_trips=spec.while_trips)
        alloc = allocate_registers(lifted.prog, maxregcount=maxregcount)
        return Workload(
            name=name,
            program=alloc.prog,
            trips=lifted.trips,
            register_sensitive=alloc.regs_per_thread > 32,
            regs_per_thread=alloc.regs_per_thread,
            suite="traced",
            l1_hit=spec.l1_hit,
        )

    from .jaxpr_lift import LIFT_REV

    return cached_value(("traced_workload", name, maxregcount, LIFT_REV), build)


def traced_suite(maxregcount: int = DEFAULT_MAXREGCOUNT) -> dict[str, Workload]:
    """All traced workloads (traces on first call, memoized afterwards)."""
    return {n: build_traced_workload(n, maxregcount) for n in TRACED_NAMES}
