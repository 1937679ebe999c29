"""The reader of ``attn_kernel_share.train``: the program's attention-path
counters, read only where the window ran steps."""
from __future__ import annotations

import pytest

from bench import cells


@pytest.fixture
def stats(monkeypatch):
    from repro.models import layers

    s = dict(layers.ATTN_STATS, kernel_calls=3, xla_calls=1)
    monkeypatch.setattr(layers, "ATTN_STATS", s)
    return s


def _read(steps=7):
    return cells.load_reader("attn_kernel_share.train")(
        {"counters": {"steps": steps}, "window_s": 10.0})


@pytest.mark.parametrize("kernel,xla,share", [(3, 1, 75.0), (2, 0, 100.0),
                                              (0, 4, 0.0)])
def test_share_of_lowered_calls(stats, kernel, xla, share):
    stats.update(kernel_calls=kernel, xla_calls=xla)
    assert _read() == pytest.approx(share)


@pytest.mark.parametrize("missing", ["steps", "calls", "counter"])
def test_nothing_to_read_is_none(stats, monkeypatch, missing):
    if missing == "steps":
        assert _read(steps=0) is None
        return
    if missing == "calls":
        stats.update(kernel_calls=0, xla_calls=0)
    else:       # a program without the counter
        from repro.models import layers
        monkeypatch.delattr(layers, "ATTN_STATS")
    assert _read() is None
