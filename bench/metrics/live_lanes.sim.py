"""Share of the fused loop's lane-ticks that carried a live lane.

The batch engine counts, per launch, the ticks each real lane entered
alive (``RUN_STATS["lane_ticks"]``) against the real lanes times the
launch's ticks (``RUN_STATS["lane_slots"]``); the shape bucket's padding
lanes are in neither.  A finished lane rides along until its chunk's
longest lane ends, so 100 less this share is the lockstep waste that chunk
retuning and live-lane compaction remove.  A count: the same requests read
the same.

The driver's counters hold the window's ``ticks`` but not these two, so
the reader takes the process's totals from the program, and only where the
window ran at least 99% of the process's ticks (set-up's one-cycle warm-up
runs a few); elsewhere, and in a program without the counters, None."""

SETUP_SHARE = 0.01


def read(rec):
    ticks = rec["counters"].get("ticks")
    if not ticks:
        return None
    try:
        from repro.sim.batch import RUN_STATS
    except ImportError:
        return None
    slots = RUN_STATS.get("lane_slots")
    if not slots or RUN_STATS["ticks"] - ticks > SETUP_SHARE * ticks:
        return None
    return 100.0 * RUN_STATS["lane_ticks"] / slots
