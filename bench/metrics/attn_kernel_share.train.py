"""Share of the train step's self-attention calls that run the fused flash
kernel.

The program counts, each time it lowers a step, which path each attention
call took on the platform it was lowered for
(``repro.models.layers.ATTN_STATS``: ``kernel_calls`` and ``xla_calls``).
The reader takes 100 x kernel / (kernel + xla) over the process, where the
window ran steps; None where nothing was stepped, in a program without the
counter, or where no attention was lowered."""


def read(rec):
    if not rec["counters"].get("steps"):
        return None
    try:
        from repro.models.layers import ATTN_STATS
    except ImportError:
        return None
    n = ATTN_STATS["kernel_calls"] + ATTN_STATS["xla_calls"]
    return 100.0 * ATTN_STATS["kernel_calls"] / n if n else None
