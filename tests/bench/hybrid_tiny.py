"""The hybrid train cell at a size a CPU test run can hold, shared by the
hybrid cell's test files (``test_bench_hybrid_*.py``)."""
from __future__ import annotations

import dataclasses

SEEDS = (3, 2 ** 33 + 17, 4_294_967_311)
# one layer of each kind and a second Mamba-2 run (Mamba-2, attention,
# Mamba-2), with the cell's multipliers, NoPE attention and tied head
TINY_HYBRID = dict(num_hidden_layers=3,
                   layer_types=["mamba", "attention", "mamba"],
                   hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
                   mamba_d_state=16, mamba_chunk_size=16,
                   intermediate_size=128, shared_intermediate_size=128,
                   vocab_size=512, attention_multiplier=1 / 16)
TINY_SEQ = 64
# the cell's limits are set from readings at the cell's size; at the tiny
# size a leaf holds a few hundred to a few thousand weights whose bfloat16
# rounding reads higher.  Readings on the CPU over SEEDS: program loss
# 1.2e-6 to 4.1e-6, gradient 1.8e-3 to 2.0e-3, change 6.1e-3 to 9.5e-3;
# the float8 control 1.65e-5 to 2.7e-5, 1.0e-2 to 2.3e-2, 2.3e-2 to
# 4.2e-2; half the positions dropped 5.6e-4 to 1.1e-3 on the loss
TINY_LIMITS = {"loss_gap": 1e-5, "grad_norm_gap": 5e-3,
               "change_norm_gap": 1.6e-2}


def tiny_hybrid_cell():
    from bench import cells

    cell = cells.resolve("granite-h-train-8k")
    return dataclasses.replace(
        cell, config={**cell.config, **TINY_HYBRID},
        traffic={**cell.traffic, "seq": TINY_SEQ}, limits=TINY_LIMITS)
