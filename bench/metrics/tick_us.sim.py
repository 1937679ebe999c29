"""Wall time of the fused loop per simulated tick.

Microseconds of the batch engine's launches (``RUN_STATS["run_s"]``:
launch, the device loop and the readback, on the host's clock) over the
ticks they ran (``RUN_STATS["ticks"]``) in the window."""


def read(rec):
    c = rec["counters"]
    if not c.get("ticks") or not c.get("run_s"):
        return None
    return 1e6 * c["run_s"] / c["ticks"]
