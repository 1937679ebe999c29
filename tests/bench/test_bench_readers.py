"""Each per-layer metric's reader, on a run's record made by hand; a
reader with nothing to read returns None, never 0."""
from __future__ import annotations

import pytest

from bench import cells


def _read(name, rec):
    return cells.load_reader(name)(rec)


def _rec(**kw):
    rec = {"counters": {}, "spans": {}, "window_s": 50.0, "trace": None,
           "memory_peak_bytes": None, "metrics": {}, "peaks": None,
           "config": cells.resolve("qwen3-train-4k").config, "traffic": {}}
    rec.update(kw)
    return rec


TRACE = {"window_s": 50.0, "busy_s": 40.0, "devices": 1,
         "modules": {"jit__run_jax(123)": 30.0, "jit_other": 5.0},
         "breakdown": {"device_ops": [], "idle_gaps": []}}


def test_sim_readers():
    c = {"requests": 4, "request_s": 48.0, "run_s": 46.0, "ticks": 60_000,
         "instructions": 600_000}
    rec = _rec(counters=c, trace=TRACE)
    assert _read("host_share.sim", rec) == pytest.approx(4.0)
    assert _read("tick_us.sim", rec) == pytest.approx(1e6 * 46.0 / 60_000)
    assert _read("ticks_per_kinst.sim", rec) == pytest.approx(100.0)
    assert _read("device_idle.sim", rec) == pytest.approx(20.0)


def test_train_readers():
    c = {"tokens": 8192 * 20, "seq": 4096, "batch": 2, "steps": 20,
         "chips": 1}
    rec = _rec(counters=c, trace=TRACE, spans={"bench.loader_get": 0.5},
               memory_peak_bytes=9 * 2 ** 30,
               peaks=cells.load_peaks("TPU v5 lite"))
    mfu = 100 * 4.985192448e9 * 8192 * 20 / 50.0 / 197e12
    assert _read("mfu.train", rec) == pytest.approx(mfu)
    assert _read("loader_wait_share.train", rec) == pytest.approx(1.0)
    assert _read("device_idle.train", rec) == pytest.approx(20.0)
    assert _read("peak_hbm_gib.train", rec) == pytest.approx(9.0)


@pytest.mark.parametrize("name", [e["name"] for e in
                                  cells.load_benchmark()["per_layer"]])
def test_nothing_to_read_is_none(name):
    assert _read(name, _rec()) is None


def test_no_loop_executable_is_none():
    """Ticks with no launch time: nothing ran the loop to read."""
    rec = _rec(counters={"ticks": 10, "run_s": 0.0}, trace=TRACE)
    assert _read("tick_us.sim", rec) is None
