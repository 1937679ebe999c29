"""The sim configuration's design points and kernels are the Fig-14 grid's
jobs as `benchmarks/sweep_subset.py` builds them, and its reference copy
reproduces the program's seed oracle."""
from __future__ import annotations

import dataclasses

import pytest

from bench import cells
from bench.drivers import sim


@pytest.fixture(scope="module")
def cell():
    return cells.resolve("sim-fig14")


def test_design_points_are_the_fig14_table2_7_grid(cell):
    from benchmarks.sweep_subset import sweep_jobs
    from repro.sim import SimConfig

    grid = sweep_jobs(table2_configs=(7,))
    names = [k["name"] for k in cell.config["kernels"]]
    points = {n: SimConfig(**f)
              for n, f in cell.config["design_points"].items()}
    assert sorted(names) == sorted({w for w, _ in grid})
    assert set(points.values()) == {c for _, c in grid}
    assert len(grid) == len(names) * len(points)
    for k, dp in {job for req in sim.deck(cell.traffic) for job in req}:
        assert (k, points[dp]) in grid


def test_kernel_specs_build_the_programs_kernels(cell):
    from repro.workloads import get_workload

    from bench.refs.ltrfsim import build_workload

    for spec in cell.config["kernels"]:
        ours, theirs = build_workload(spec), get_workload(spec["name"])
        assert ours.trips == theirs.trips
        assert (ours.regs_per_thread, ours.l1_hit, ours.register_sensitive) \
            == (theirs.regs_per_thread, theirs.l1_hit,
                theirs.register_sensitive)
        assert repr(ours.program.blocks) == repr(theirs.program.blocks)


@pytest.mark.parametrize("kernel", ["bfs", "pathfinder"])
@pytest.mark.parametrize("point", ["BL_1x", "RFC", "SHRF", "LTRF_conf",
                                   "LTRF_plus"])
def test_reference_copy_equals_the_programs_oracle(cell, kernel, point):
    from repro.sim import SimConfig
    from repro.sim.golden import golden_simulate
    from repro.workloads import get_workload

    fields = {**cell.config["design_points"][point], "num_warps": 16}
    spec = next(k for k in cell.config["kernels"] if k["name"] == kernel)
    want = golden_simulate(get_workload(kernel), SimConfig(**fields))
    got = sim.reference_counters((spec, fields, False))
    assert got == {f.name: getattr(want, f.name)
                   for f in dataclasses.fields(want)
                   if f.name not in ("design", "workload")}
