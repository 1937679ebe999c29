"""Which path each call of a kernel-or-XLA choice took, counted per lowering.

A call that may run a Pallas kernel on a TPU and an XLA form elsewhere picks
its path with ``jax.lax.platform_dependent``, which traces every branch;
only the lowering for a platform keeps one of them.  So each branch tags an
input with an identity primitive whose lowering counts the branch in a
named counter: once per lowering of each call site, not per execution.  A
call site inside a rematerialised layer is lowered twice (the forward and
its recompute), and one inside a layer scan once for all its layers.

A counter is a dict made by `counter`: ``kernel_calls`` and ``xla_calls``,
plus any keys of its own that a branch adds to (``counts``).
"""
from __future__ import annotations

from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

_COUNTERS: dict[str, dict] = {}


def counter(name: str, *keys: str) -> dict:
    """A new zeroed counter named ``name``, with ``kernel_calls``,
    ``xla_calls`` and ``keys``."""
    stats = dict.fromkeys(("kernel_calls", "xla_calls", *keys), 0)
    _COUNTERS[name] = stats
    return stats


def reset(stats: dict) -> dict:
    for key in stats:
        stats[key] = 0
    return stats


def _count(counter, path, counts):
    stats = _COUNTERS[counter]
    stats[f"{path}_calls"] += 1
    for key, n in counts:
        stats[key] += n


# The tag sits on a branch's input, not its output: a gradient that drops
# the output still feeds the input to the kernel.
_path_p = Primitive("kernel_path")
_path_p.def_abstract_eval(lambda x, **_: x)
_path_p.def_impl(lambda x, **kw: (_count(**kw), x)[1])
ad.primitive_jvps[_path_p] = (
    lambda primals, tangents, **kw: (_path_p.bind(primals[0], **kw),
                                     tangents[0]))
batching.primitive_batchers[_path_p] = (
    lambda args, dims, **kw: (_path_p.bind(args[0], **kw), dims[0]))


def _lowering(ctx, x, **kw):
    _count(**kw)
    return [x]


mlir.register_lowering(_path_p, _lowering)


def tag(x, counter: str, path: str, **counts: int):
    """``x`` unchanged; lowering it counts ``path`` (``"kernel"`` or
    ``"xla"``) and adds ``counts`` in the counter named ``counter``."""
    return _path_p.bind(x, counter=counter, path=path,
                        counts=tuple(sorted(counts.items())))
