"""Model FLOP/s utilisation of a Mamba-2/attention hybrid's training step.

The FLOPs per token that the forward and backward passes require
(`bench.flops_hybrid.train_flops_per_token`, from the configuration's
``layer_types`` and shapes; no recomputation, no masked work) times the
tokens completed in the window, over the window and the chips' published
bf16 peak."""

from bench.flops_hybrid import train_flops_per_token


def read(rec):
    peaks, c = rec["peaks"], rec["counters"]
    if peaks is None or not c.get("tokens"):
        return None
    flops = train_flops_per_token(rec["config"], c["seq"]) * c["tokens"]
    return 100.0 * flops / rec["window_s"] / (
        peaks["bf16_flops_per_s"] * c["chips"])
