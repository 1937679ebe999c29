"""The batch engine's own accounting: the host phases' seconds and the
lockstep lane counters in `repro.sim.batch.RUN_STATS`.

``lane_ticks`` counts the ticks each real lane entered alive and
``lane_slots`` the ticks real lanes were carried; their ratio is the
chunk's lockstep occupancy.  Lanes advance independently, so a lane's alive
ticks in a chunk equal its ticks when run alone."""
from __future__ import annotations

import time

from repro.sim import batch as B
from repro.sim import design_config
from repro.workloads import WORKLOADS

NEW_KEYS = ("encode_s", "build_s", "extract_s", "lane_ticks", "lane_slots")


def _jobs():
    """Two lanes of one shape bucket (same program, same warps) whose runs
    differ in length: the Table-2 #7 latency point and a faster one."""
    w = WORKLOADS["kmeans"]
    return [(w, design_config("LTRF", table2_config=7, num_warps=4)),
            (w, design_config("LTRF", mrf_latency_mult=1.0, num_warps=4))]


def test_reset_zeroes_the_new_keys():
    stats = B.RUN_STATS
    for k in NEW_KEYS:
        stats[k] += 3
    B.reset_run_stats()
    assert all(stats[k] == 0 for k in NEW_KEYS)
    assert isinstance(stats["lane_ticks"], int)
    assert isinstance(stats["encode_s"], float)


def test_lane_ticks_are_each_lanes_ticks_alone():
    jobs = _jobs()
    alone, results = [], []
    for job in jobs:
        stats = B.reset_run_stats()
        results.append(B.run_batch([job], fallback=False)[0])
        # one real lane (the bucket's padding lane is never alive): alive
        # on every tick the loop ran
        assert stats["launches"] == 1
        assert stats["lane_ticks"] == stats["lane_slots"] == stats["ticks"]
        alone.append(stats["ticks"])
    assert alone[0] != alone[1]

    stats = B.reset_run_stats()
    assert B.run_batch(jobs, fallback=False) == results
    assert stats["launches"] == 1
    assert stats["ticks"] == max(alone)
    assert stats["lane_ticks"] == sum(alone)
    assert stats["lane_slots"] == len(jobs) * stats["ticks"]
    assert stats["lane_ticks"] < stats["lane_slots"]


def test_phase_seconds_fit_in_the_calls_wall_time():
    stats = B.reset_run_stats()
    t = time.perf_counter()
    B.run_batch(_jobs(), fallback=False)
    wall = time.perf_counter() - t
    phases = (stats["encode_s"] + stats["build_s"] + stats["compile_s"]
              + stats["run_s"] + stats["extract_s"])
    assert all(stats[k] > 0 for k in ("encode_s", "build_s", "run_s",
                                      "extract_s"))
    assert phases <= wall


def test_phases_are_profiler_spans(monkeypatch):
    """Each phase opens its span; the launch's span is the one `run_s`
    times."""
    import jax

    opened = []
    real = jax.profiler.TraceAnnotation

    def spy(name, **kw):
        opened.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spy)
    B.run_batch(_jobs()[:1], fallback=False)
    assert opened == ["repro.sim.encode", "repro.sim.build",
                      "repro.sim.launch", "repro.sim.extract"]


def test_no_per_tick_hook():
    assert not hasattr(B, "_DEBUG_HOOK")
