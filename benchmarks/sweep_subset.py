"""The simulator's canonical sweep grids.

`sweep_jobs` is the Fig. 14-shaped grid (both Table-2 design points x all
designs x the workload suite, plus the per-workload normalization
baselines): the benchmark's `sim-fig14` cell checks its job list against
it, and ``python -m benchmarks.run calibrate`` fits the analytic tier on
it.  `screening_jobs` is the analytic tier's screening grid and
`interval_sweep_jobs` the interval-formation ablation.

The default job list is pinned to the synthetic suite (`workload_names()`
with no suite): lazily-registered suites like ``traced`` never change the
tracked benchmark.  Pass ``suite="traced"`` (or ``"all"``) to sweep the
real lifted kernels instead.
"""
from __future__ import annotations

from repro.sim import SimConfig, baseline_config, design_config
from repro.sim.designs import TOLERANCE_MULTS
from repro.workloads import get_workload, workload_names

SWEEP_DESIGNS = ("BL", "RFC", "SHRF", "LTRF", "LTRF_conf", "LTRF_plus", "Ideal")

# The interval-formation ablation (ISSUE 5): the paper's algorithm vs the
# capacity-clamped variant vs naive fixed-length intervals, swept at an
# interval_cap deliberately larger than the default design's RFC
# entries-per-warp (128 entries / 8 active slots = 16) so the capacity
# strategy actually clamps.
INTERVAL_STRATEGIES_SWEPT = ("paper", "capacity", "fixed:8")
INTERVAL_SWEEP_CAP = 48

# The cycle-attribution comparison points (ISSUE 7): the baseline that eats
# the slow-MRF latency raw, vs the two paper designs that hide it behind
# interval prefetch.  Pinned at Table-2 config #7 (DWM, 6.3x latency) — the
# design point where latency tolerance matters most — and deliberately a
# subset of `sweep_jobs`' tc7 grid, so the figure harness shares sim-cache
# entries with Fig. 14.
BREAKDOWN_DESIGNS = ("BL", "LTRF", "LTRF_conf")


def interval_sweep_jobs(workloads=None, table2_config: int = 7,
                        strategies=INTERVAL_STRATEGIES_SWEPT,
                        interval_cap: int = INTERVAL_SWEEP_CAP,
                        designs=SWEEP_DESIGNS,
                        suite: str | None = None) -> list[tuple[str, SimConfig]]:
    """The interval-strategy ablation.  Defaults to the *high-register-pressure*
    (register-sensitive) workloads of the suite — the kernels whose working
    sets the strategies actually shape.  Single-SM configs: run them
    through `SimRunner.sim` like the main sweep."""
    if workloads is None:
        workloads = [n for n in workload_names(suite)
                     if get_workload(n).register_sensitive]
    return [
        (name, design_config(d, table2_config=table2_config,
                             interval_cap=interval_cap, interval_strategy=s))
        for name in workloads for d in designs for s in strategies
    ]


def sweep_jobs(workloads=None, designs=SWEEP_DESIGNS,
               table2_configs=(6, 7),
               suite: str | None = None) -> list[tuple[str, SimConfig]]:
    """(workload name, SimConfig) pairs for the tracked sweep subset."""
    names = list(workloads) if workloads else list(workload_names(suite))
    jobs: list[tuple[str, SimConfig]] = []
    for tc in table2_configs:
        for name in names:
            jobs.append((name, baseline_config()))
            for d in designs:
                jobs.append((name, design_config(d, table2_config=tc)))
    return jobs


def screening_jobs(workloads=None, designs=SWEEP_DESIGNS,
                   rf_sizes_kb=(256, 2048),
                   mults=TOLERANCE_MULTS,
                   schedulers=("two_level", "gto"),
                   suite: str | None = None) -> list[tuple[str, SimConfig]]:
    """The *screening-scale* grid for the analytical fast tier (ISSUE 9).

    `sweep_jobs`' design x workload matrix crossed with the full
    tolerated-latency axis, the RF-capacity axis, and the single-SM
    scheduler axis — thousands of unique points, far past what the
    cycle-accurate engine can sweep on the tracked host.  Meant for
    ``SimRunner.prefill(jobs, tier="analytic"|"hybrid")``; running it at
    ``tier="engine"`` is possible but takes hours, not milliseconds."""
    names = list(workloads) if workloads else list(workload_names(suite))
    # dict.fromkeys: designs that pin an axis (Ideal forces mult 1.0)
    # collapse to one point instead of repeating it per swept value
    return list(dict.fromkeys(
        (name, design_config(d, table2_config=7, rf_size_kb=kb,
                             mrf_latency_mult=float(m), scheduler=s))
        for kb in rf_sizes_kb for name in names for d in designs
        for m in mults for s in schedulers
    ))

