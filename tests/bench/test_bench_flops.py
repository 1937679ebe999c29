"""The FLOP count that mfu.train divides by, against a count by hand."""
from __future__ import annotations

from bench import cells
from bench.flops import matmul_params, train_flops_per_token


def test_qwen3_matmul_params_by_hand():
    c = cells.resolve("qwen3-train-4k").config
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024,
    # gate/up 1024x3072 each, down 3072x1024; then the 1024x151936 head
    per_layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                 + 3 * 1024 * 3072)
    assert per_layer == 15_728_640
    assert matmul_params(c) == 28 * per_layer + 1024 * 151936 == 595_984_384


def test_qwen3_train_flops_per_token_at_4096():
    c = cells.resolve("qwen3-train-4k").config
    dense = 6 * 595_984_384                     # 2 forward + 4 backward
    attention = 6 * 28 * 4096 * 16 * 128        # causal half of 12 L S H hd
    assert attention == 1_409_286_144
    assert train_flops_per_token(c, 4096) == dense + attention
    assert round(train_flops_per_token(c, 4096) / 1e9, 2) == 4.99
