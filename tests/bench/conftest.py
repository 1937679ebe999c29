"""Shared pieces of the benchmark's own tests: the repository root on the
import path, and the cells at a size a CPU test run can hold."""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# widths and depth of the train cell for a CPU test; everything else, the
# optimizer and the check, as the cell states them
TINY_DECODER = dict(num_hidden_layers=2, hidden_size=64,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, intermediate_size=128, vocab_size=512)
TINY_SEQ = 64
# the cell's limit on the change after three steps is set from readings at
# the cell's size; at the tiny size a leaf holds a few thousand weights and
# their bfloat16 rounding reads higher: the program 2.5e-3 to 3.9e-3, the
# float8 control 1.3e-2 to 3.5e-2 on the CPU over the train tests' seeds
TINY_LIMITS = {"change_norm_gap": 8e-3}


@pytest.fixture
def tiny_train():
    from bench import cells

    cell = cells.resolve("qwen3-train-4k")
    return dataclasses.replace(
        cell, config={**cell.config, **TINY_DECODER},
        traffic={**cell.traffic, "seq": TINY_SEQ},
        limits={**cell.limits, **TINY_LIMITS})


@pytest.fixture
def tiny_sim():
    """The sim cell with 4 warps per design point and a three-request deck
    of the suite's three shortest kernels."""
    from bench import cells

    cell = cells.resolve("sim-fig14")
    points = {k: {**v, "num_warps": 4}
              for k, v in cell.config["design_points"].items()}
    deck = [{"design_point": dp, "kernels": ["bfs", "kmeans", "pathfinder"]}
            for dp in ("BL", "RFC", "LTRF")]
    return dataclasses.replace(
        cell, config={**cell.config, "design_points": points},
        traffic={**cell.traffic, "requests": deck})


@pytest.fixture
def cpu():
    import jax

    return jax.devices("cpu")[:1]
