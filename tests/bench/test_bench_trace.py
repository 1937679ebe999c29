"""The reduction from a profiler trace to busy time, idle gaps named by the
host's spans, top operations and executable seconds."""
from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return NS(name=name, events=events)


def _profile():
    """A window 0..1000 ns: the chip runs ops in 100..300 and 500..900;
    the host is in bench.loader_get over 300..500 and bench.other later."""
    host = NS(name="/host:CPU", lines=[_line("python", [
        _ev("bench.window", 0, 1000),
        _ev("bench.loader_get", 290, 220),
        _ev("PjitFunction(step)", 880, 100),
        _ev("bench.request", 950, 40),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Ops", [_ev("fusion.1", 100, 150), _ev("dot.2", 200, 100),
                          _ev("fusion.1", 500, 400), _ev("late", 1200, 50)]),
        _line("XLA Modules", [_ev("jit_step", 100, 200),
                              _ev("jit__run_jax(1)", 500, 400)]),
    ])
    other = NS(name="/host:metadata", lines=[])
    return NS(planes=[other, host, dev])


def test_busy_idle_and_named_gaps():
    got = trace.reduce_profile(_profile(), "bench.window")
    assert got["window_s"] == pytest.approx(1000e-9)
    # busy: 100..300 (overlapping ops merged) and 500..900
    assert got["busy_s"] == pytest.approx(600e-9)
    gaps = dict((n, s) for n, s in got["breakdown"]["idle_gaps"])
    assert gaps["bench.loader_get"] == pytest.approx(200e-9)   # 300..500
    assert gaps["bench.request"] == pytest.approx(100e-9)      # 900..1000
    # 0..100: nothing of ours at t=50; the window span itself is not a name
    assert gaps["bench.window"] == pytest.approx(100e-9)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 550e-9, "dot.2": 100e-9})
    assert got["modules"] == pytest.approx({"jit_step": 200e-9,
                                            "jit__run_jax(1)": 400e-9})


def test_gaps_of_an_unnamed_span_take_its_name():
    got = trace.reduce_profile(_profile(), "bench.window", name_gaps=False)
    assert got["busy_s"] == pytest.approx(600e-9)
    gaps = got["breakdown"]["idle_gaps"]
    assert {n for n, _ in gaps} == {"bench.window"}
    assert sum(s for _, s in gaps) == pytest.approx(400e-9)


def test_window_span_must_be_there_once():
    p = _profile()
    p.planes[1].lines[0].events.pop(0)
    with pytest.raises(RuntimeError):
        trace.reduce_profile(p, "bench.window")


def test_no_device_reads_busy_zero():
    p = _profile()
    p.planes.pop()
    got = trace.reduce_profile(p, "bench.window")
    assert got["devices"] == 0 and got["busy_s"] == 0


def test_recorded_chip_trace():
    """A trace recorded on one v5e: a window span around three requests of
    a 1024x1024 bf16 matmul and tanh, 10 ms of host sleep between them."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(DATA / "v5e_small.xplane.pb"))
    got = trace.reduce_profile(prof, "bench.window")
    assert got["devices"] == 1
    assert 0 < got["busy_s"] < got["window_s"] < 1.0
    gaps = got["breakdown"]["idle_gaps"]
    # the three 10 ms sleeps between requests are the longest idle gaps,
    # named by the host's event there
    assert [n for n, _ in gaps[:3]] == ["$time sleep"] * 3
    assert all(0.01 < s < 0.02 for _, s in gaps[:3])
    assert sum(s for _, s in gaps) <= got["window_s"] - got["busy_s"] + 1e-9
    assert got["breakdown"]["device_ops"]
    assert got["modules"]
