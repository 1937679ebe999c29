"""Interval-formation strategies + prefetch-stall accounting (ISSUE 5).

Three layers, mirroring the bank-arbitration suite:

* **no-op guarantee**: ``interval_strategy="paper"`` (the default) is
  bit-identical to the frozen golden engine — the hard invariant the
  pipeline refactor must respect;
* **determinism pins**: exact `prefetch_stall_cycles` for the paper's
  Listing-1 program, so the new counter cannot drift silently;
* **the acceptance verdicts**: on the high-register-pressure workloads
  with an oversized ``interval_cap``, the ``capacity`` strategy yields
  strictly fewer aggregate prefetch-stall cycles than ``paper`` on the
  paper's full compile pipeline (LTRF_conf) with no per-workload IPC
  regression.
"""
from dataclasses import replace

import pytest

from repro.sim import (
    DESIGNS, INTERVAL_STRATEGIES, SimConfig, Simulator, design_config,
    simulate, simulate_gpu,
)
from repro.sim.golden import golden_simulate
from repro.workloads import WORKLOADS, workload_names
from repro.workloads.suite import Workload, listing1_program

# The interval_sweep acceptance parameters (benchmarks.sweep_subset).
SWEEP_CAP = 48
VERDICT_DESIGN = "LTRF_conf"


def listing1_workload() -> Workload:
    return Workload(name="listing1", program=listing1_program(),
                    trips={"L1": 100}, register_sensitive=False,
                    regs_per_thread=8, suite="paper")


def _sensitive_names():
    return [n for n in workload_names() if WORKLOADS[n].register_sensitive]


# ------------------------------------------------------------ config plumbing

def test_paper_strategy_is_default():
    cfg = SimConfig()
    assert cfg.interval_strategy == "paper"
    assert INTERVAL_STRATEGIES == ("paper", "capacity", "fixed")


def test_unknown_strategy_raises():
    w = WORKLOADS["bfs"]
    with pytest.raises(ValueError):
        simulate(w, SimConfig(interval_strategy="strands", num_warps=4))


# ----------------------------------------------------------- no-op guarantee

@pytest.mark.parametrize("design", DESIGNS)
def test_paper_strategy_bit_identical_to_golden(design):
    """ISSUE 5 acceptance pin: the default strategy is a strict no-op —
    bit-identical to the frozen golden oracle (which predates the knob)."""
    w = WORKLOADS["srad"]
    cfg = design_config(design, table2_config=7, num_warps=12)
    explicit = replace(cfg, interval_strategy="paper")
    r = simulate(w, explicit)
    assert r == golden_simulate(w, cfg), design
    assert r == simulate(w, cfg)


def test_strategies_retire_identical_instruction_stream():
    """Interval formation only reshapes prefetch boundaries: every strategy
    retires the same dynamic instructions with the same occupancy."""
    for name in ("srad", "sgemm"):
        w = WORKLOADS[name]
        base = design_config("LTRF", table2_config=7, num_warps=8,
                             interval_cap=SWEEP_CAP)
        ref = simulate(w, base)
        for strat in ("capacity", "fixed:8"):
            r = simulate(w, replace(base, interval_strategy=strat))
            assert r.instructions == ref.instructions, (name, strat)
            assert r.resident_warps == ref.resident_warps, (name, strat)


def test_strategy_noop_on_uncached_designs():
    """BL/RFC/Ideal compile no intervals and SHRF is strand-bounded: the
    knob cannot change their results (they share one cached plan)."""
    w = WORKLOADS["btree"]
    for design in ("BL", "RFC", "Ideal", "SHRF"):
        cfg = design_config(design, table2_config=7, num_warps=8)
        ref = simulate(w, cfg)
        for strat in ("capacity", "fixed:8"):
            assert simulate(w, replace(cfg, interval_strategy=strat)) == ref, \
                (design, strat)


# ---------------------------------------------------------- determinism pins

# Exact (prefetch_ops, prefetch_stall_cycles) for Listing 1 at Table-2
# config #7, 16 warps.  LTRF_plus fetches only live subsets — empty at every
# Listing-1 interval header, so it never blocks on a prefetch here.
LISTING1_STALLS = {
    "BL":        (0, 0),
    "RFC":       (0, 0),
    "SHRF":      (98, 2484),
    "LTRF":      (26, 676),
    "LTRF_conf": (26, 676),
    "LTRF_plus": (0, 0),
    "Ideal":     (0, 0),
}


@pytest.mark.parametrize("design", DESIGNS)
def test_listing1_prefetch_stalls_pinned(design):
    w = listing1_workload()
    cfg = design_config(design, table2_config=7, num_warps=16)
    r = simulate(w, cfg)
    assert (r.prefetch_ops, r.prefetch_stall_cycles) == \
        LISTING1_STALLS[design], design
    # the golden engine counts the new counter identically
    assert golden_simulate(w, cfg) == r


def test_stall_cycles_consistent_with_prefetch_activity():
    w = WORKLOADS["srad"]
    r = simulate(w, design_config("LTRF", table2_config=7, num_warps=16))
    assert r.prefetch_ops > 0
    # every prefetch blocks for at least its own latency's worth of cycles
    assert r.prefetch_stall_cycles >= r.prefetch_cycles > 0
    none = simulate(w, design_config("BL", table2_config=7, num_warps=16))
    assert none.prefetch_stall_cycles == 0


# -------------------------------------------------- the acceptance verdicts

def _strategy_pair(name: str, design: str = VERDICT_DESIGN):
    w = WORKLOADS[name]
    paper = simulate(w, design_config(design, table2_config=7,
                                      interval_cap=SWEEP_CAP))
    cap = simulate(w, design_config(design, table2_config=7,
                                    interval_cap=SWEEP_CAP,
                                    interval_strategy="capacity"))
    return paper, cap


@pytest.mark.parametrize("name", sorted(_sensitive_names()))
def test_capacity_never_worse_per_workload(name):
    """Per high-register-pressure workload: the capacity strategy never
    loses IPC vs the paper strategy on the full compile pipeline."""
    paper, cap = _strategy_pair(name)
    assert cap.ipc >= paper.ipc, name


@pytest.mark.slow
def test_capacity_strictly_fewer_stall_cycles_in_aggregate():
    """ISSUE-5 acceptance: strictly fewer aggregate prefetch-stall cycles
    across the high-register-pressure workloads."""
    tot_paper = tot_cap = 0
    for name in _sensitive_names():
        paper, cap = _strategy_pair(name)
        tot_paper += paper.prefetch_stall_cycles
        tot_cap += cap.prefetch_stall_cycles
    assert tot_cap < tot_paper


def test_capacity_working_sets_respect_rfc_capacity():
    """Under ``capacity`` every compiled interval's estimated working set
    fits the RFC's entries-per-warp, so a prefetch round can never
    overflow the cache."""
    for name in _sensitive_names():
        w = WORKLOADS[name]
        cfg = design_config("LTRF", table2_config=7, num_warps=8,
                            interval_cap=SWEEP_CAP,
                            interval_strategy="capacity")
        s = Simulator(cfg, w)
        bound = cfg.rfc_entries_per_warp
        assert all(len(op.bitvector) <= bound
                   for op in s.pf_ops.values()), name


@pytest.mark.slow
def test_interval_sweep_section_verdicts():
    """The interval ablation's sweep grid (`interval_sweep_jobs`, on a
    reduced workload slice), run through the sweep service, meets the
    three verdicts this suite pins: capacity strictly fewer aggregate
    prefetch-stall cycles than paper on LTRF_conf, no per-workload IPC
    regression, and the strategy a no-op on BL."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    from benchmarks.sweep_subset import interval_sweep_jobs
    from repro.serving.sweep import SimRunner

    jobs = interval_sweep_jobs(workloads=("srad", "sgemm"),
                               designs=("BL", "LTRF", VERDICT_DESIGN))
    assert {c.interval_strategy for _, c in jobs} == \
        {"paper", "capacity", "fixed:8"}
    assert all(c.interval_cap == SWEEP_CAP for _, c in jobs)
    runner = SimRunner(processes=1, disk_cache=False)
    assert runner.prefill(jobs).ok
    res = {(n, c.design, c.interval_strategy): runner.sim(n, c)
           for n, c in jobs}
    names = ("srad", "sgemm")
    paper = [res[n, VERDICT_DESIGN, "paper"] for n in names]
    capacity = [res[n, VERDICT_DESIGN, "capacity"] for n in names]
    assert sum(r.prefetch_stall_cycles for r in capacity) < \
        sum(r.prefetch_stall_cycles for r in paper)
    assert all(c.ipc >= p.ipc for p, c in zip(paper, capacity))
    for n in names:
        assert len({(r.ipc, r.prefetch_ops, r.prefetch_stall_cycles,
                     r.mrf_accesses)
                    for (w, d, _), r in res.items()
                    if w == n and d == "BL"}) == 1, n


# ----------------------------------------------------------------- GPU scale

def test_gpu_aggregates_prefetch_stall_cycles():
    w = WORKLOADS["srad"]
    cfg = design_config("LTRF", table2_config=7, num_warps=16, num_sms=2)
    g = simulate_gpu(w, cfg)
    assert g.prefetch_stall_cycles == \
        sum(r.prefetch_stall_cycles for r in g.per_sm)
    assert g.prefetch_stall_cycles > 0


def test_gpu_num_sms1_passes_strategy_through():
    w = WORKLOADS["sgemm"]
    cfg = design_config("LTRF", table2_config=7, num_warps=16,
                        interval_cap=SWEEP_CAP, interval_strategy="capacity")
    g = simulate_gpu(w, cfg)
    r = simulate(w, cfg)
    assert g.per_sm == (r,)
    assert g.prefetch_stall_cycles == r.prefetch_stall_cycles
