"""Model FLOP/s utilisation of the whole training step.

The FLOPs per token that the forward and backward passes require
(`bench.flops.train_flops_per_token`, from the configuration's shapes; no
recomputation, no masked work) times the tokens completed in the window,
over the window and the chips' published bf16 peak."""

from bench.flops import train_flops_per_token


def read(rec):
    peaks, c = rec["peaks"], rec["counters"]
    if peaks is None or not c.get("tokens"):
        return None
    flops = train_flops_per_token(rec["config"], c["seq"]) * c["tokens"]
    return 100.0 * flops / rec["window_s"] / (
        peaks["bf16_flops_per_s"] * c["chips"])
