"""Tests for the vectorized batch simulation engine (`repro.sim.batch`).

Three layers:

* cheap structural tests (the `batch_supported` gate, scalar fallback,
  chunk grouping, sweep-service batch-mode policy) that never touch jax;
* bit-identity pins on the jitted path, including the FMA-contraction
  regression case that originally diverged;
* slow-lane A/B matrices (heterogeneous batches, the sweep service's
  batch prefill path) that run the full lockstep loop.

The bit-identity contract these enforce: for every `batch_supported`
config, `run_batch` produces `SimResult`s equal — every counter AND the
full `cycle_breakdown` — to the event-heap engine, which is itself pinned
bit-identical to the frozen `golden.py` oracle.
"""
from __future__ import annotations

from dataclasses import replace

import pytest

from repro.sim import (
    DESIGNS, SimBudgetExceeded, SimConfig, batch_supported, design_config,
    run_batch, simulate, simulate_batch, simulate_one,
)
from repro.workloads import WORKLOADS


# ------------------------------------------------------------ gate + fallback

def test_batch_supported_gate():
    """Exactly the golden-pinned domain: two-level scheduler, no bank
    arbitration, untraced, single SM.  Compile-side knobs (design,
    interval strategy, renumbering) never disqualify a config."""
    base = design_config("LTRF", table2_config=7, num_warps=8)
    assert batch_supported(base)
    for d in DESIGNS:
        assert batch_supported(replace(base, design=d)), d
    assert batch_supported(replace(base, interval_strategy="fixed:4"))
    assert batch_supported(replace(base, renumber="identity"))
    assert not batch_supported(replace(base, scheduler="gto"))
    assert not batch_supported(replace(base, scheduler="lrr"))
    assert not batch_supported(replace(base, bank_model="arbitrated"))
    assert not batch_supported(replace(base, trace=True))
    assert not batch_supported(replace(base, num_sms=2))


def test_run_batch_falls_back_to_scalar_engine():
    """Unsupported configs ride the event-heap engine job by job (same
    results), or raise when the caller forbids the fallback."""
    w = WORKLOADS["kmeans"]
    cfg = replace(design_config("LTRF", table2_config=7, num_warps=4),
                  scheduler="gto")
    assert not batch_supported(cfg)
    assert run_batch([(w, cfg)]) == [simulate(w, cfg)]
    with pytest.raises(ValueError):
        run_batch([(w, cfg)], fallback=False)


def test_chunk_lanes_groups_by_shape():
    """Chunking keeps cheap lanes out of expensive shapes: a BL lane (all
    resident warps active) must not share a chunk with an LTRF lane (8
    active slots), and every lane survives chunking exactly once."""
    from repro.sim import batch as B

    w = WORKLOADS["kmeans"]
    lanes = []
    for d in ("BL", "LTRF", "LTRF_plus", "Ideal"):
        cfg = design_config(d, table2_config=7, num_warps=16)
        lanes.append(B._Lane(w, cfg, B._encode_plan(w, cfg),
                             B._occupancy(w, cfg)))
    chunks = list(B._chunk_lanes(lanes, list(range(len(lanes)))))
    seen = sorted(i for _, idxs in chunks for i in idxs)
    assert seen == list(range(len(lanes)))
    for chunk, idxs in chunks:
        assert len(chunk) == len(idxs) <= B._MAX_LANES
        acaps = {B._bucket(B._acap(ln), 2) for ln in chunk}
        assert len(acaps) == 1  # one active-width bucket per chunk
    by_design = {ln.cfg.design: ci for ci, (chunk, _) in enumerate(chunks)
                 for ln in chunk}
    assert by_design["BL"] != by_design["LTRF"]
    assert by_design["LTRF"] == by_design["LTRF_plus"]


# --------------------------------------------------------- jitted-path pins

def test_fma_contraction_regression_pin():
    """BL/kmeans at Table-2 #7, 16 warps: the exact case where XLA's CPU
    FMA contraction, and later a TPU's emulated f64, silently changed a
    token-bucket float compare.  The engine now tabulates the products on
    the host and adds on f64 bit patterns (`repro.sim.f64bits`).
    Full-structure equality (breakdown included) with the event engine."""
    w = WORKLOADS["kmeans"]
    cfg = design_config("BL", table2_config=7, num_warps=16)
    assert simulate_one(w, cfg) == simulate(w, cfg)


@pytest.mark.parametrize("name", ["kmeans", "btree"])
def test_prefetch_keeps_load_flag(name):
    """LTRF, Table-2 #7, 4 warps: interval prefetches land on registers
    whose last write was a load.  A prefetch maxes the register's ready
    time and leaves its from-memory flag set (golden's `_start_prefetch`),
    so a source still waiting on that load counts as a memory stall and
    may swap the warp out.  The batch engine keeps the flag in a plane of
    its own that the prefetch scatter never writes; every counter and the
    breakdown equal golden's.  The control clears the flags a prefetch
    fetches, and golden's counters then move: the case exercises the
    rule."""
    from repro.sim.golden import GoldenSimulator, golden_simulate

    class FlagClearingGolden(GoldenSimulator):
        def _start_prefetch(self, wp, cycle, force=False):
            before = self.result.prefetch_ops
            super()._start_prefetch(wp, cycle, force)
            if self.result.prefetch_ops > before:
                for r in self.pf_ops[wp.interval].bitvector:
                    wp.reg_from_mem[r] = False

    w = WORKLOADS[name]
    cfg = design_config("LTRF", table2_config=7, num_warps=4)
    ref = golden_simulate(w, cfg)
    assert simulate_one(w, cfg) == ref
    assert FlagClearingGolden(cfg, w).run() != ref


def test_budget_outcomes_returned_not_raised():
    """`run_batch` reports watchdog trips as `SimBudgetExceeded` instances
    in the outcome list (the sweep service records them as job outcomes);
    `simulate_batch` re-raises to match the scalar `simulate` contract."""
    w = WORKLOADS["kmeans"]
    cfg = design_config("BL", table2_config=7, num_warps=16)
    ref = simulate(w, cfg)
    tight = replace(cfg, max_cycles=max(1, ref.cycles // 2))
    ok, tripped = run_batch([(w, cfg), (w, tight)])
    assert ok == ref
    assert isinstance(tripped, SimBudgetExceeded)
    with pytest.raises(SimBudgetExceeded) as event_exc:
        simulate(w, tight)
    assert tripped.args == event_exc.value.args
    with pytest.raises(SimBudgetExceeded):
        simulate_batch([(w, cfg), (w, tight)])


@pytest.mark.slow
def test_heterogeneous_batch_bit_identical():
    """One `run_batch` call over a mixed pile — every design, two
    workloads, differing latency multipliers — matches per-job `simulate`
    bit-for-bit.  This is the acceptance shape of the tracked sweep."""
    jobs = []
    for d in DESIGNS:
        for name in ("srad", "btree"):
            jobs.append((WORKLOADS[name],
                         design_config(d, table2_config=7, num_warps=8)))
    jobs.append((WORKLOADS["srad"],
                 design_config("LTRF", mrf_latency_mult=2.8, rf_size_kb=256,
                               num_warps=8)))
    for (w, cfg), got in zip(jobs, run_batch(jobs, fallback=False)):
        assert got == simulate(w, cfg), (cfg.design, w.name)


# --------------------------------------- BATCH_REV 2: stats + time skipping

def test_run_stats_compile_run_split():
    """`RUN_STATS` attributes XLA compile wall and launch wall separately —
    the `compile_s` split the perf ledger reports — and counts fused-loop
    ticks.  A cached executable legitimately reports zero compile wall, but
    never zero launches or ticks."""
    from repro.sim import batch as B

    w = WORKLOADS["kmeans"]
    cfg = design_config("LTRF", table2_config=7, num_warps=4)
    stats = B.reset_run_stats()
    assert stats == {"compile_s": 0.0, "run_s": 0.0,
                     "compiles": 0, "launches": 0, "ticks": 0,
                     "encode_s": 0.0, "build_s": 0.0, "extract_s": 0.0,
                     "lane_ticks": 0, "lane_slots": 0}
    res, = B.run_batch([(w, cfg)], fallback=False)
    assert stats["launches"] == 1
    assert stats["run_s"] > 0.0
    assert stats["ticks"] > 0
    # in-process executable cache hits skip compilation entirely; either
    # way the wall and the counter must agree
    assert (stats["compiles"] == 0) == (stats["compile_s"] == 0.0)
    assert res == simulate(w, cfg)


def test_time_skip_finishes_under_cycle_count():
    """Event-horizon skipping: on a stall-heavy LTRF config (2 warps, the
    Table-2 #7 latency point) whole stretches of cycles pass with no lane
    able to issue, so the fused loop must converge in strictly fewer ticks
    than simulated cycles — while staying bit-identical to the event
    engine, breakdown included."""
    from repro.sim import batch as B

    w = WORKLOADS["kmeans"]
    cfg = design_config("LTRF", table2_config=7, num_warps=2)
    stats = B.reset_run_stats()
    res, = B.run_batch([(w, cfg)], fallback=False)
    assert res == simulate(w, cfg)
    assert 0 < stats["ticks"] < res.cycles, (stats["ticks"], res.cycles)


def test_mixed_supported_and_fallback_positions():
    """A single `run_batch` call mixing batch-supported configs with every
    out-of-domain axis (gto/lrr schedulers, arbitrated banks): fallback
    jobs ride the event heap in place, positions preserved, everything
    bit-identical per job."""
    w = WORKLOADS["kmeans"]
    base = design_config("LTRF", table2_config=7, num_warps=4)
    jobs = [
        (w, base),
        (w, replace(base, scheduler="gto")),
        (w, design_config("BL", table2_config=7, num_warps=4)),
        (w, replace(base, scheduler="lrr")),
        (w, replace(base, bank_model="arbitrated")),
    ]
    assert [batch_supported(c) for _, c in jobs] == \
        [True, False, True, False, False]
    for (wk, cfg), got in zip(jobs, run_batch(jobs)):
        assert got == simulate(wk, cfg), \
            (cfg.design, cfg.scheduler, cfg.bank_model)


def test_watchdog_parity_across_budgets():
    """Budget trips stay bit-identical across several watchdog budgets —
    including budgets that land inside a dead-time gap, where the dt-jump
    must not overshoot the recorded trip cycle."""
    w = WORKLOADS["kmeans"]
    cfg = design_config("LTRF", table2_config=7, num_warps=2)
    ref = simulate(w, cfg)
    for frac in (0.2, 0.5, 0.9):
        tight = replace(cfg, max_cycles=max(1, int(ref.cycles * frac)))
        got, = run_batch([(w, tight)])
        assert isinstance(got, SimBudgetExceeded), frac
        with pytest.raises(SimBudgetExceeded) as event_exc:
            simulate(w, tight)
        assert got.args == event_exc.value.args, frac


# ------------------------------------------------------ sweep-service path

def _runner(tmp_path, **kw):
    from repro.serving.sweep import SimRunner
    return SimRunner(processes=1, cache_dir=tmp_path / "cache", **kw)


def test_sweep_batch_mode_policy(tmp_path, monkeypatch):
    """Explicit flag beats env var beats auto; fault plans force it off
    (the chaos harness targets the per-job classic path)."""
    from repro.serving import faults

    r = _runner(tmp_path)
    monkeypatch.delenv("REPRO_SIM_BATCH", raising=False)
    assert r._batch_mode() == "auto"
    monkeypatch.setenv("REPRO_SIM_BATCH", "1")
    assert r._batch_mode() == "on"
    monkeypatch.setenv("REPRO_SIM_BATCH", "0")
    assert r._batch_mode() == "off"
    assert _runner(tmp_path, batch=True)._batch_mode() == "on"
    monkeypatch.setenv("REPRO_SIM_BATCH", "1")
    assert _runner(tmp_path, batch=False)._batch_mode() == "off"
    on = _runner(tmp_path, batch=True)
    monkeypatch.setattr(faults, "active_plan", lambda: faults.FaultPlan())
    assert on._batch_mode() == "off"


def test_auto_batch_threshold_platform_policy(monkeypatch):
    """'auto' mode's engage bar: low on a loaded non-CPU jax backend, and
    no bar at all (never batch) on CPU or before jax is loaded — and the
    probe itself must never import jax (a cache lookup should not pay a
    multi-second import)."""
    import sys

    from repro.serving import sweep as S

    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert S._auto_batch_threshold() is None
    assert "jax" not in sys.modules  # probe did not import it

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    class _FakeJax:
        def __init__(self, platform):
            self._d = _Dev(platform)

        def devices(self):
            return [self._d]

    monkeypatch.setitem(sys.modules, "jax", _FakeJax("gpu"))
    assert S._auto_batch_threshold() == S._MIN_AUTO_BATCH
    monkeypatch.setitem(sys.modules, "jax", _FakeJax("cpu"))
    assert S._auto_batch_threshold() is None


@pytest.mark.slow
def test_sweep_runner_batch_prefill(tmp_path):
    """`SimRunner(batch=True)` computes cache misses through the batch
    engine — same results as the classic path, `batched` stat accounted,
    report coherent, and everything lands in the disk cache."""
    cfgs = [design_config(d, table2_config=7, num_warps=4)
            for d in ("BL", "LTRF")]
    jobs = [(name, cfg) for name in ("kmeans", "bfs") for cfg in cfgs]

    batched = _runner(tmp_path / "b", batch=True)
    rep = batched.prefill(jobs)
    assert rep.ok and rep.computed == len(jobs)
    assert batched.stats["batched"] == len(jobs)
    assert batched.stats["computed"] == len(jobs)

    classic = _runner(tmp_path / "c", batch=False)
    classic.prefill(jobs)
    assert classic.stats["batched"] == 0
    for name, cfg in jobs:
        assert batched.sim(name, cfg) == classic.sim(name, cfg) \
            == simulate(WORKLOADS[name], cfg), (name, cfg.design)

    # a second prefill is pure cache: nothing recomputed, nothing batched
    rep2 = batched.prefill(jobs)
    assert rep2.cached == len(jobs) and rep2.computed == 0
    assert batched.stats["batched"] == len(jobs)
