"""The train cell driven end to end on the CPU at a tiny size: a sound run
is correct and prints the contract's keys; the control and each fault the
cell can have come out not correct."""
from __future__ import annotations

import io
import json
import math

import jax
import jax.numpy as jnp
import pytest

from bench import faults, harness
from bench.drivers import train

SEEDS = (3, 2 ** 33 + 17, 4_294_967_311)


def _run(cell, cpu, seed=SEEDS[0], traced=False):
    return harness.run_cell(cell, seed, 0.5, traced, cpu, log=io.StringIO())


@pytest.mark.parametrize("traced", [False, True])
def test_sound_run_is_correct_with_the_contract_keys(tiny_train, cpu, traced):
    res = _run(tiny_train, cpu, traced=traced)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res) == keys + (["breakdown"] if traced else []) + ["compared"]
    json.dumps(res)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {"loss_gap", "grad_norm_gap",
                                    "change_norm_gap"}
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if traced:
        assert set(res["device"]) >= {"busy_s", "window_s"}
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_each_fault_is_not_correct(tiny_train, cpu, monkeypatch, fault):
    faults.plant("train", fault, monkeypatch.setattr)
    res = _run(tiny_train, cpu)
    assert not res["correct"], res["compared"]


def test_control_is_not_correct(tiny_train, cpu):
    """The reference in float8, put in the program's place, departs from
    the float32 reference by several times what the program does and by
    more than a limit, on every seed."""
    limits = tiny_train.limits
    for seed in SEEDS:
        r = harness.Run(tiny_train, seed, 0.0, False, cpu, 0.0)
        got = train.readings(r)
        assert all(math.isfinite(v) for v in got["control"].values())
        assert any(got["control"][k] >= 3 * got["program"][k]
                   for k in got["program"]), got
        assert all(v <= limits[k] for k, v in got["program"].items()), got
        assert any(v > limits[k] for k, v in got["control"].items()), got


def test_tokens_are_the_programs_and_seeded():
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import batch_for_step

    arch = train.arch_of({**train_config(), "vocab_size": 512})
    shape = ShapeConfig("t", 64, 2, "train")
    for seed in SEEDS:
        for step in range(3):
            want = batch_for_step(arch, shape, step, seed)["tokens"]
            got = train.tokens_for(seed, step, 2, 64, 512)
            assert (got == want).all()
    assert not (train.tokens_for(1, 0, 2, 64, 512)
                == train.tokens_for(2, 0, 2, 64, 512)).all()


def train_config():
    from bench import cells

    return cells.resolve("qwen3-train-4k").config


def test_weights_are_seeded(tiny_train):
    shapes = {"w": jax.ShapeDtypeStruct((4, 3), jnp.bfloat16),
              "norm1": jax.ShapeDtypeStruct((3,), jnp.float32)}
    init = jax.jit(train.make_init(shapes, 0.02))
    a, b = init(train.weight_key(7)), init(train.weight_key(7))
    c = init(train.weight_key(8))
    assert (a["w"] == b["w"]).all() and not (a["w"] == c["w"]).all()
    assert a["w"].dtype == jnp.bfloat16 and (a["norm1"] == 1).all()


def test_reference_rounds_weights_to_their_stored_dtype():
    """The reference's update rounds to the stored dtype as a cast would,
    by an operation that XLA may not drop."""
    import numpy as np

    from bench.refs import qwen3 as ref

    x = jnp.asarray(np.random.default_rng(0).normal(0, 0.02, 4096),
                    jnp.float32)
    want = x.astype(jnp.bfloat16).astype(jnp.float32)
    assert bool(jnp.all(ref.rounded(x, jnp.bfloat16) == want))
    assert bool(jnp.any(want != x))
    assert bool(jnp.all(ref.rounded(x, jnp.float32) == x))
