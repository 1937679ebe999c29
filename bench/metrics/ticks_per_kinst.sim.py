"""Fused-loop ticks per thousand simulated warp instructions.

A count: it repeats exactly for the same requests.  Lane chunking (the
longest lane sets a launch's ticks) and the event-horizon time skip move
it; per-tick speed does not."""


def read(rec):
    c = rec["counters"]
    if not c.get("instructions"):
        return None
    return 1000.0 * c["ticks"] / c["instructions"]
