"""The readers of mfu.hybrid, ssd_kernel_share.hybrid and
ssd_roofline.hybrid on synthetic records, and the hybrid driver's count of
the SSD kernel's events in a trace."""
from __future__ import annotations

import types

import pytest

from bench import cells
from bench.drivers import hybrid_train
from bench.flops_hybrid import ssd_roofline_s, train_flops_per_token

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def granite():
    return cells.resolve("granite-h-train-8k").config


def _rec(config, peaks=PEAKS, **counters):
    base = {"steps": 10, "tokens": 81920, "seq": 8192, "batch": 1,
            "chips": 1}
    return {"counters": {**base, **counters}, "window_s": 10.0,
            "config": config, "peaks": peaks, "trace": None}


def test_mfu_hybrid(granite):
    read = cells.load_reader("mfu.hybrid")
    want = 100 * train_flops_per_token(granite, 8192) * 81920 / 10 / 197e12
    assert read(_rec(granite)) == pytest.approx(want)
    assert round(want, 2) == 24.52
    assert read(_rec(granite, peaks=None)) is None
    assert read(_rec(granite, tokens=0)) is None


@pytest.fixture
def ssd_stats(monkeypatch):
    from repro.models import mamba2

    s = dict(mamba2.SSD_STATS)
    monkeypatch.setattr(mamba2, "SSD_STATS", s)
    return s


@pytest.mark.parametrize("kernel,xla,share", [(2, 0, 100.0), (1, 1, 50.0),
                                              (0, 3, 0.0)])
def test_ssd_kernel_share(granite, ssd_stats, kernel, xla, share):
    ssd_stats.update(kernel_calls=kernel, xla_calls=xla)
    read = cells.load_reader("ssd_kernel_share.hybrid")
    assert read(_rec(granite)) == pytest.approx(share)


@pytest.mark.parametrize("missing", ["steps", "calls", "counter"])
def test_ssd_kernel_share_none(granite, ssd_stats, monkeypatch, missing):
    read = cells.load_reader("ssd_kernel_share.hybrid")
    ssd_stats.update(kernel_calls=2, xla_calls=0)
    if missing == "steps":
        assert read(_rec(granite, steps=0)) is None
        return
    if missing == "calls":
        ssd_stats.update(kernel_calls=0)
    else:       # a program without the counter
        from repro.models import mamba2
        monkeypatch.delattr(mamba2, "SSD_STATS")
    assert read(_rec(granite)) is None


def test_ssd_roofline(granite):
    read = cells.load_reader("ssd_roofline.hybrid")
    least = ssd_roofline_s(granite, 1, 8192, PEAKS)      # 425 us a call
    # 18 calls a step (9 layers, forward and recompute) at 1.2 ms each
    got = read(_rec(granite, ssd_kernel_calls=18, ssd_kernel_s=18 * 1.2e-3))
    assert got == pytest.approx(100 * least / 1.2e-3)
    assert 35 < got < 36


@pytest.mark.parametrize("counters", [
    {},                                              # no profile taken
    {"ssd_kernel_calls": 0, "ssd_kernel_s": 0.0},     # no kernel ran
    {"ssd_kernel_calls": 18, "ssd_kernel_s": 0.0},
], ids=["unprobed", "no-kernel", "no-time"])
def test_ssd_roofline_none(granite, counters):
    read = cells.load_reader("ssd_roofline.hybrid")
    assert read(_rec(granite, **counters)) is None
    assert read(_rec(granite, peaks=None, ssd_kernel_calls=18,
                     ssd_kernel_s=0.02)) is None


def _event(name, ns):
    return types.SimpleNamespace(name=name, duration_ns=ns)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=ev) for n, ev in lines.items()])


def test_ssd_kernel_events_counts_every_call_on_every_chip(monkeypatch):
    """Every ``%ssd``/``%ssd.N`` custom call on a device's op line counts,
    whatever its text after the name; nothing else does: not other ops,
    not a name that only starts alike, not the host's or a module's
    events.  No top-N cut: a partial sum would overstate the share."""
    from bench import trace

    cc = " = f32[1,32,64,256,64]{4,3,2,1,0} custom-call(f32[...] %x)"
    ops = [_event("%ssd" + cc, 1_000_000), _event("%ssd.4" + cc, 2_000_000),
           _event("%ssd_fused.3 = f32[] fusion()", 7_000_000),
           _event("%fusion.12 = f32[] fusion()", 9_000_000)]
    ops += [_event(f"%ssd.{i}" + cc, 500_000) for i in range(6, 40, 2)]
    prof = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", {"XLA Ops": ops,
                                 "XLA Modules": [_event("%ssd.2", 5)]}),
        _plane("/device:TPU:1", {"XLA Ops": [_event("%ssd.2" + cc, 1_500_000)]}),
        _plane("/host:CPU", {"main": [_event("%ssd.8" + cc, 3_000_000)]}),
    ])
    monkeypatch.setattr(trace, "load", lambda tdir: prof)
    seconds, events = hybrid_train.ssd_kernel_events("unused")
    assert events == 2 + 17 + 1
    assert seconds == pytest.approx(1e-3 + 2e-3 + 17 * 5e-4 + 1.5e-3)
