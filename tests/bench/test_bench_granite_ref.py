"""The hybrid program against its plain float32 reference
(``bench/refs/granite_hybrid.py``) on the CPU at a tiny size, on seeded
weights: the loss, each leaf's gradient and one AdamW step."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from hybrid_tiny import TINY_SEQ, tiny_hybrid_cell

from bench.drivers import hybrid_train


@pytest.fixture
def tiny_hybrid():
    return tiny_hybrid_cell()


def _f32_setup(tiny):
    from repro.models import init_params

    c = tiny.config
    arch = dataclasses.replace(hybrid_train.arch_of(c), dtype="float32",
                               remat="none")
    shapes = jax.eval_shape(lambda k: init_params(arch, k)[0],
                            jax.random.PRNGKey(0))
    init = jax.jit(hybrid_train.with_mamba_init(
        hybrid_train.train.make_init(shapes, 0.02), shapes))
    params = init(hybrid_train.train.weight_key(11))
    tokens = jnp.asarray(hybrid_train.tokens_for(11, 0, 2, TINY_SEQ,
                                                 c["vocab_size"]))
    return c, arch, params, tokens


def _leaf_gaps(got, want):
    return {k: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for (k, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree.leaves(want))}


# Program and reference compute the same float32 mathematics in a
# different order and form (chunked SSD against the quadratic one, flash-
# or q-block attention against a full softmax): the loss agrees to 1e-6 and
# each leaf's gradient and update to 1e-4 of its norm.  Computed in
# bfloat16 the program misses both by more than ten times (checked below).
LOSS_RTOL, LEAF_RTOL = 1e-6, 1e-4


def _program_loss_and_grad(arch, params, tokens):
    from repro.models import loss_fn

    return jax.jit(jax.value_and_grad(lambda p: loss_fn(
        p, {"tokens": tokens, "labels": tokens}, arch)[0]))(params)


def test_program_matches_reference_loss_and_gradients(tiny_hybrid):
    from bench.refs import granite_hybrid as ref

    c, arch, params, tokens = _f32_setup(tiny_hybrid)
    loss, grad = _program_loss_and_grad(arch, params, tokens)
    with jax.default_matmul_precision("highest"):
        want_loss, want_grad = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(c, ref.exact, p, tokens)))(params)
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    gaps = _leaf_gaps(grad, want_grad)
    assert max(gaps.values()) <= LEAF_RTOL, gaps
    # tight enough that bfloat16 fails: the same program in bfloat16
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                        if x.ndim > 1 else x, params)
    loss16, grad16 = _program_loss_and_grad(
        dataclasses.replace(arch, dtype="bfloat16"), bf16, tokens)
    gaps16 = _leaf_gaps(jax.tree.map(lambda x: x.astype(jnp.float32), grad16),
                        want_grad)
    assert (abs(float(loss16) - float(want_loss))
            > 10 * LOSS_RTOL * float(want_loss)
            or max(gaps16.values()) > 10 * LEAF_RTOL), gaps16


def test_program_matches_reference_adamw_step(tiny_hybrid):
    """One AdamW step of the program's train step against the reference's
    (`bench.refs.qwen3`'s update, on groups of leaves), from the same
    float32 weights: each leaf's new value."""
    from bench.refs import granite_hybrid as ref
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_host_mesh
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.runtime.train_step import build_train_step

    c, arch, params, tokens = _f32_setup(tiny_hybrid)
    opt = tiny_hybrid.traffic["optimizer"]
    rules = default_rules(make_host_mesh(devices=jax.devices("cpu")[:1]))
    step = jax.jit(build_train_step(arch, rules, AdamWConfig(**opt)))
    state, _ = step({"params": params, "opt": init_opt_state(params)},
                    {"tokens": tokens, "labels": tokens})
    _, _, change = ref.train_readings(
        c, dict(opt), lambda: jax.tree.map(jnp.copy, params), [tokens])
    got = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                       state["params"], params)
    for (k, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                         jax.tree.leaves(change)):
        if w > 0:
            assert abs(g - w) <= 1e-3 * w, (jax.tree_util.keystr(k), g, w)
