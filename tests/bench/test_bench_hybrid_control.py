"""The hybrid train cell's control on the CPU at a tiny size: the plain
reference computed in float8, put in the program's place, comes out not
correct on every seed, while the program comes out correct."""
from __future__ import annotations

import math

import pytest
from hybrid_tiny import SEEDS, TINY_LIMITS, tiny_hybrid_cell

from bench import harness
from bench.drivers import hybrid_train


@pytest.fixture
def tiny_hybrid():
    return tiny_hybrid_cell()


def test_control_is_not_correct(tiny_hybrid, cpu):
    """The reference in float8, put in the program's place, departs from
    the float32 reference by several times what the program does and by
    more than a limit, on every seed."""
    for seed in SEEDS:
        r = harness.Run(tiny_hybrid, seed, 0.0, False, cpu, 0.0)
        got = hybrid_train.readings(r)
        assert all(math.isfinite(v) for v in got["control"].values())
        assert any(got["control"][k] >= 3 * got["program"][k]
                   for k in got["program"]), got
        assert all(v <= TINY_LIMITS[k] for k, v in got["program"].items()), got
        assert any(v > TINY_LIMITS[k] for k, v in got["control"].items()), got
