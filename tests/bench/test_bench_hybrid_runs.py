"""The hybrid train cell driven end to end on the CPU at a tiny size: a
sound run is correct and prints the contract's keys; the configuration maps
to the program's pattern and its Mamba-2 leaves are drawn as Mamba-2 draws
them."""
from __future__ import annotations

import io
import json

import jax
import numpy as np
import pytest
from hybrid_tiny import SEEDS, TINY_LIMITS, tiny_hybrid_cell

from bench import cells, harness
from bench.drivers import hybrid_train


@pytest.fixture
def tiny_hybrid():
    return tiny_hybrid_cell()


def _run(cell, cpu, seed=SEEDS[0], traced=False):
    return harness.run_cell(cell, seed, 0.5, traced, cpu, log=io.StringIO())


@pytest.mark.parametrize("traced", [False, True])
def test_sound_run_is_correct_with_the_contract_keys(tiny_hybrid, cpu,
                                                     traced):
    res = _run(tiny_hybrid, cpu, traced=traced)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res) == keys + (["breakdown"] if traced else []) + ["compared"]
    json.dumps(res)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0, \
        res["compared"]
    assert set(res["compared"]) == set(TINY_LIMITS)
    if traced:
        # on the CPU the SSD and attention take the XLA path, and no chip
        # trace holds a kernel or a peak to divide by
        assert res["metrics"]["ssd_kernel_share.hybrid"]["value"] == 0.0
        assert "ssd_roofline.hybrid" not in res["metrics"]
        assert "mfu.hybrid" not in res["metrics"]
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_arch_of_reads_the_published_pattern():
    c = cells.resolve("granite-h-train-8k").config
    arch = hybrid_train.arch_of(c)
    assert arch.runs() == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.hd,
            arch.d_ff, arch.vocab) == (2048, 32, 8, 64, 8192, 100352)
    assert (arch.ssm_state, arch.ssm_headdim, arch.ssm_chunk,
            arch.ssm_expand) == (128, 64, 256, 2)
    assert arch.tie_embeddings and not arch.rope and arch.ssm_conv_bias
    assert arch.score_scale == 1 / 64
    assert round(arch.param_count() / 1e6, 1) == 952.0
    with pytest.raises(ValueError):
        hybrid_train.arch_of({**c, "position_embedding_type": "rope"})


def test_mamba_init_draws_decays_and_skip(tiny_hybrid):
    """A_log, dt_bias and D at Mamba-2's initialisation, from the seed."""
    from repro.models import init_params

    arch = hybrid_train.arch_of(tiny_hybrid.config)
    shapes = jax.eval_shape(lambda k: init_params(arch, k)[0],
                            jax.random.PRNGKey(0))
    init = jax.jit(hybrid_train.with_mamba_init(
        hybrid_train.train.make_init(shapes, 0.02), shapes))
    p = init(hybrid_train.train.weight_key(5))
    q = init(hybrid_train.train.weight_key(5))
    m = p["layers"][0]["mixer"]
    A = np.exp(np.asarray(m["A_log"]))
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (A >= 1).all() and (A <= 16).all() and A.std() > 1
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert (np.asarray(m["D"]) == 1).all()
    assert (np.asarray(q["layers"][0]["mixer"]["A_log"])
            == np.asarray(m["A_log"])).all()
