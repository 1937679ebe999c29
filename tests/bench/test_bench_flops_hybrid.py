"""The counts that mfu.hybrid and ssd_roofline.hybrid divide by, against
counts by hand for the granite-h-train-8k configuration."""
from __future__ import annotations

import pytest

from bench import cells
from bench.flops_hybrid import (
    matmul_params, ssd_flops_per_token, ssd_kernel_cost, ssd_roofline_s,
    train_flops_per_token,
)


@pytest.fixture
def granite():
    return cells.resolve("granite-h-train-8k").config


def test_matmul_params_by_hand(granite):
    # a Mamba-2 mixer: in_proj 2048 x (z 4096 + xBC 4096 + 2 x 128 + dt 64),
    # out_proj 4096 x 2048; attention: q and o 2048 x 2048, k and v
    # 2048 x 512 (8 heads of 64); every layer's MLP 3 x 2048 x 8192; the
    # tied head 2048 x 100352
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    assert (mamba, attn, mlp) == (25_821_184, 10_485_760, 50_331_648)
    want = 9 * mamba + attn + 10 * mlp + 2048 * 100352
    assert matmul_params(granite) == want == 951_713_792
    # Mamba-2 layers with their MLPs: 72% of the matrix parameters
    assert round(9 * (mamba + mlp) / want, 3) == 0.720


def test_train_flops_per_token_at_8192(granite):
    ssd = 256 * 128 + 64 * 256 * 64 + 4 * 64 * 64 * 128
    assert ssd_flops_per_token(granite) == ssd == 3_178_496
    dense = 6 * 951_713_792
    attention = 6 * 1 * 8192 * 32 * 64       # one NoPE layer, causal half
    want = dense + attention + 3 * 9 * ssd
    assert train_flops_per_token(granite, 8192) == want
    assert round(want / 1e9, 2) == 5.90
    assert round(want * 8192 / 1e12, 1) == 48.3


def test_ssd_kernel_cost_and_roofline(granite):
    # 32 chunks of 256: C B^T, its masked product with x dt (64 heads of
    # 64), the outgoing state (64 x 64 x 128), causal halves
    flops = 32 * (256 * 256 * 128 + 64 * 256 * 256 * 64
                  + 2 * 64 * 256 * 64 * 128)
    # float32: x and y_intra (8192 x 4096), dt and the decay sums
    # (8192 x 64), B and C (8192 x 128), 32 states of 64 x 64 x 128
    words = 8192 * (2 * 4096 + 2 * 64 + 2 * 128) + 32 * 64 * 64 * 128
    assert ssd_kernel_cost(granite, 1, 8192) == (flops, 4 * words)
    assert (flops, 4 * words) == (17_448_304_640, 348_127_232)
    peaks = cells.load_peaks("TPU v5 lite")
    # bound by HBM: 425 us against 89 us of bf16 compute
    assert ssd_roofline_s(granite, 1, 8192, peaks) == pytest.approx(
        348_127_232 / 819e9)
