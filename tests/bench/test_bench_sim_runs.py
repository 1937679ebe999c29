"""The sim cell driven end to end on the CPU at a tiny size: a sound run
is correct; the control and each fault the cell can have are not."""
from __future__ import annotations

import dataclasses
import io

import pytest

from bench import faults, harness
from bench.drivers import sim


def _run(cell, cpu, seed=5, traced=False):
    return harness.run_cell(cell, seed, 0.1, traced, cpu, log=io.StringIO())


def test_sound_run_is_correct(tiny_sim, cpu):
    res = _run(tiny_sim, cpu)
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] % 9 == 0
    assert res["compared"] == {"mismatched_jobs": {"value": 0, "limit": 0}}
    assert set(res["metrics"]) == {"sim_inst_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.SIM))
def test_each_fault_is_not_correct(tiny_sim, cpu, monkeypatch, fault):
    faults.plant("sim", fault, monkeypatch.setattr)
    assert not _run(tiny_sim, cpu)["correct"]


def test_control_is_not_correct(tiny_sim, cpu, monkeypatch):
    """The reference in float32, put in the program's place, on a job where
    float32 rounding moves the counters: RFC over pathfinder at the
    configuration's own 64 warps."""
    from repro.sim import SimResult

    config = dict(tiny_sim.config)
    config["design_points"] = {"RFC": {**config["design_points"]["RFC"],
                                       "num_warps": 64}}
    cell = dataclasses.replace(
        tiny_sim, config=config,
        traffic={**tiny_sim.traffic, "requests": [
            {"design_point": "RFC", "kernels": ["pathfinder"]}]})
    key = ("pathfinder", "RFC")
    ctl = sim.reference(config, [key], lower=True)[key]

    def control(jobs, outs):
        return [SimResult(design=c.design, workload=w.name, **ctl)
                if isinstance(o, SimResult) else o
                for (w, c), o in zip(jobs, outs)]

    from repro.sim import batch

    real = batch.run_batch
    monkeypatch.setattr(batch, "run_batch",
                        lambda jobs, **k: control(jobs, real(jobs, **k)))
    res = _run(cell, cpu)
    assert not res["correct"]
    assert res["compared"]["mismatched_jobs"]["value"] >= 1


def test_passes_are_seeded_orders_of_one_deck():
    t = {"requests": [{"design_point": d, "kernels": ["a", "b", "c"]}
                      for d in ("X", "Y", "Z")]}
    first = [next(sim.passes(t, s)) for s in (1, 1, 2 ** 40 + 3)]
    assert first[0] == first[1]
    assert first[0] != first[2]
    canon = sorted(map(sorted, first[0]))
    assert sorted(map(sorted, first[2])) == canon == sorted(
        map(sorted, sim.deck(t)))


def test_traced_run_reads_a_slice_of_the_window(tiny_sim, cpu, monkeypatch):
    """A traced run records the driver's slice of the window, not all of
    it, and still reports its per-layer metrics and the traced window."""
    monkeypatch.setattr(sim, "TRACE_SLICE", (0.0, 0.05))
    res = _run(tiny_sim, cpu, traced=True)
    assert res["correct"], res
    assert 0.04 < res["device"]["window_s"] < 0.5
    assert res["device"]["busy_s"] == 0     # no TPU plane on the CPU
    assert {"host_share.sim", "tick_us.sim",
            "ticks_per_kinst.sim"} <= set(res["metrics"])
