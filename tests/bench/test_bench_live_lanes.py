"""The reader of ``live_lanes.sim``: the batch engine's lane counters, read
from the program only where the window ran nearly all of the process's
ticks."""
from __future__ import annotations

import pytest

from bench import cells

WINDOW_TICKS = 10_000


@pytest.fixture
def stats(monkeypatch):
    from repro.sim import batch

    s = dict(batch.RUN_STATS, ticks=WINDOW_TICKS + 40, lane_ticks=48_000,
             lane_slots=80_000)
    monkeypatch.setattr(batch, "RUN_STATS", s)
    return s


def _read(ticks):
    return cells.load_reader("live_lanes.sim")(
        {"counters": {"ticks": ticks}, "window_s": 10.0})


def test_share_of_lane_ticks(stats):
    assert _read(WINDOW_TICKS) == pytest.approx(60.0)


def test_process_ticks_outside_the_window_are_at_most_one_percent(stats):
    stats["ticks"] = WINDOW_TICKS + 101
    assert _read(WINDOW_TICKS) is None


@pytest.mark.parametrize("missing", ["window", "slots", "keys"])
def test_nothing_to_read_is_none(stats, missing):
    if missing == "window":
        assert _read(0) is None
        return
    if missing == "slots":
        stats["lane_slots"] = 0
    else:       # a program without the counters
        del stats["lane_slots"], stats["lane_ticks"]
    assert _read(WINDOW_TICKS) is None
