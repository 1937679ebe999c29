"""Share of the train step's SSD calls that run the chunk kernel.

The program counts, each time it lowers a step, which path each SSD call
took on the platform it was lowered for (``repro.models.mamba2.SSD_STATS``:
``kernel_calls`` and ``xla_calls``).  The reader takes 100 x kernel /
(kernel + xla) over the process, where the window ran steps; None where
nothing was stepped, in a program without the counter, or where no SSD was
lowered."""


def read(rec):
    if not rec["counters"].get("steps"):
        return None
    try:
        from repro.models.mamba2 import SSD_STATS
    except ImportError:
        return None
    n = SSD_STATS["kernel_calls"] + SSD_STATS["xla_calls"]
    return 100.0 * SSD_STATS["kernel_calls"] / n if n else None
