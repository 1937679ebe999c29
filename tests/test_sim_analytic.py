"""Trust suite for the calibrated analytical fast tier (repro.sim.analytic).

The fast tier is only usable for screening million-point sweeps if it is
*tested into trustworthiness* (ISSUE 9).  This suite pins:

* property tests — estimates are finite/non-negative on fuzzed programs and
  configs, monotone non-decreasing in RF access latency and in working-set
  size at fixed design, the Ideal twin lower-bounds every design, and the
  model matches the engine *exactly* on degenerate single-interval,
  no-conflict programs;
* a schema regression test — the `CompiledPlan.pass_stats` pass names,
  execution order, and counter keys the model consumes cannot silently
  drift when `core.pipeline` changes (the failure message points at
  `src/repro/sim/analytic.py`);
* the differential rank-correlation acceptance — both tiers run in-process
  over sweep domains, Spearman rho / Pareto-frontier recall are asserted,
  and the hybrid tier returns engine-verdict results bit-identical to
  fresh engine runs for every confirmed frontier point;
* calibration — the NNLS fitter returns non-negative coefficients,
  calibrations round-trip through disk, and stale-revision or corrupt
  files raise `CalibrationError` instead of silently skewing estimates.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import time
from dataclasses import replace

import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks.sweep_subset import screening_jobs, sweep_jobs
from repro.core.pipeline import sim_passes
from repro.core.plan_cache import compile_for_sim
from repro.obs.attribution import CYCLE_CATEGORIES
from repro.core.ir import parse_asm
from repro.serving.sweep import SimRunner, analytic_sim_key, sim_key
from repro.sim import DESIGNS, SimConfig, simulate
from repro.sim.analytic import (
    ANALYTIC_PASS_ORDER, ANALYTIC_PASS_SCHEMA, ANALYTIC_REV, CALIB_REV,
    DEFAULT_CALIBRATION, AnalyticModelError, Calibration, CalibrationError,
    analytic_supported, calibration_from_dict, calibration_to_dict,
    check_pass_stats, estimate, fit_calibration, load_calibration,
    pareto_frontier, required_passes, save_calibration, spearman_rho,
)
from repro.sim.designs import design_config
from repro.workloads import get_workload, workload_names
from repro.workloads.suite import Workload
from repro.workloads.synth import SynthSpec, synthesize

TOL_MULTS = (1.0, 4.0, 6.3)


def _degen_workload(n: int) -> Workload:
    """Degenerate single-interval no-conflict program: straight-line movs
    with no register sources and bank-distinct destinations — no RAW/WAW
    hazards, no memory, no bank conflicts, one basic block, one interval."""
    lines = [f"mov r{i % 16}, {i}" for i in range(n)]
    prog = parse_asm("\n".join(lines), name=f"degen{n}")
    return Workload(name=f"degen{n}", program=prog, trips={},
                    register_sensitive=False, regs_per_thread=16,
                    suite="synth", l1_hit=1.0)


def _ws_workload(k: int, n: int = 24) -> Workload:
    """Fixed instruction count, working set growing with ``k`` (distinct
    source registers) — the axis the monotonicity property sweeps."""
    lines = [f"add r0, r{1 + i % k}, r{1 + (i + 1) % k}" for i in range(n)]
    prog = parse_asm("\n".join(lines), name=f"ws{k}")
    return Workload(name=f"ws{k}", program=prog, trips={},
                    register_sensitive=False, regs_per_thread=max(8, k + 1),
                    suite="synth", l1_hit=1.0)


def _fuzz_workload(seed: int, n_regs: int, loop_depth: int, body_len: int,
                   mem_ratio: float, diamonds: int) -> Workload:
    spec = SynthSpec(name=f"afuzz{seed}", seed=seed, n_regs=n_regs,
                     loop_depth=loop_depth, body_len=body_len,
                     mem_ratio=mem_ratio, diamonds=diamonds,
                     trips=tuple([3] * loop_depth),
                     regs_per_thread=max(24, n_regs))
    prog, trips = synthesize(spec)
    return Workload(name=spec.name, program=prog, trips=trips,
                    register_sensitive=True, regs_per_thread=spec.regs_per_thread,
                    suite="synth", l1_hit=0.85)


# ------------------------------------------------------------- properties

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000),
       n_regs=st.integers(8, 48),
       loop_depth=st.integers(1, 2),
       body_len=st.integers(4, 24),
       mem_ratio=st.floats(0.0, 0.5),
       diamonds=st.integers(0, 2),
       design=st.sampled_from(DESIGNS),
       mult=st.sampled_from(TOL_MULTS),
       warps=st.sampled_from((1, 4, 16)))
def test_estimates_finite_nonnegative_fuzzed(seed, n_regs, loop_depth,
                                             body_len, mem_ratio, diamonds,
                                             design, mult, warps):
    w = _fuzz_workload(seed, n_regs, loop_depth, body_len, mem_ratio,
                       diamonds)
    res = estimate(w, SimConfig(design=design, mrf_latency_mult=mult,
                                num_warps=warps))
    assert math.isfinite(res.cycles) and res.cycles >= 0
    assert math.isfinite(res.ipc) and res.ipc >= 0
    assert res.instructions > 0
    assert res.est_prefetch_events >= 0 and res.est_mrf_accesses >= 0
    assert set(res.cycle_breakdown) == set(CYCLE_CATEGORIES)
    for cat, v in res.cycle_breakdown.items():
        assert math.isfinite(v) and v >= 0, (cat, v)


@pytest.mark.parametrize("design", DESIGNS)
def test_monotone_in_rf_latency(design):
    w = get_workload("srad")
    prev = -1.0
    for m in (1.0, 2.0, 4.0, 6.3, 8.0, 16.0):
        c = estimate(w, SimConfig(design=design, mrf_latency_mult=m)).cycles
        assert c >= prev, (design, m, c, prev)
        prev = c


@pytest.mark.parametrize("design", DESIGNS)
def test_monotone_in_working_set_size(design):
    prev = -1.0
    for k in (2, 4, 8, 12, 15):
        c = estimate(_ws_workload(k),
                     SimConfig(design=design, mrf_latency_mult=6.3)).cycles
        assert c >= prev, (design, k, c, prev)
        prev = c


@pytest.mark.parametrize("design", [d for d in DESIGNS if d != "Ideal"])
def test_ideal_twin_lower_bounds_every_design(design):
    for name in ("srad", "kmeans", "bfs"):
        w = get_workload(name)
        for m in TOL_MULTS:
            cfg = SimConfig(design=design, mrf_latency_mult=m)
            twin = replace(cfg, design="Ideal", mrf_latency_mult=1.0,
                           add_rfc_to_main=True)
            assert estimate(w, twin).cycles <= estimate(w, cfg).cycles, \
                (name, design, m)


@pytest.mark.parametrize("design", DESIGNS)
def test_degenerate_programs_exact_vs_engine(design):
    """On single-interval no-conflict straight-line programs the closed form
    *is* the engine: identical cycles, instructions, and IPC."""
    for n in (6, 12, 33):
        w = _degen_workload(n)
        for mult in TOL_MULTS:
            for warps in (1, 4, 8):
                cfg = SimConfig(design=design, mrf_latency_mult=mult,
                                num_warps=warps)
                eng = simulate(w, cfg)
                est = estimate(w, cfg)
                assert est.cycles == eng.cycles, (n, design, mult, warps)
                assert est.instructions == eng.instructions
                assert est.ipc == pytest.approx(eng.ipc)


def test_unsupported_configs_raise_model_error():
    w = get_workload("kmeans")
    with pytest.raises(AnalyticModelError):
        estimate(w, SimConfig(design="BL", num_sms=2))
    assert not analytic_supported(SimConfig(design="BL", num_sms=2))
    assert analytic_supported(SimConfig(design="BL"))


# ------------------------------------------------ pass_stats schema pinning

def test_pass_stats_schema_pinned_against_pipeline():
    """The exact pass names and execution order the model consumes must
    exist in `core.pipeline.sim_passes()` — in the same relative order."""
    pipeline_names = [p.name for p in sim_passes()]
    assert set(ANALYTIC_PASS_ORDER) <= set(pipeline_names), \
        "pipeline lost a pass the analytical model consumes"
    positions = [pipeline_names.index(n) for n in ANALYTIC_PASS_ORDER]
    assert positions == sorted(positions), \
        "pipeline reordered passes the analytical model consumes"


@pytest.mark.parametrize("design", DESIGNS)
def test_compiled_plan_carries_pinned_counters(design):
    w = get_workload("kmeans")
    plan = compile_for_sim(w.program, design, 16, 16)
    check_pass_stats(plan.pass_stats, design)  # must not raise
    for name in required_passes(design):
        entry = plan.pass_stats[name]
        for key in ANALYTIC_PASS_SCHEMA[name]:
            assert key in entry, (design, name, key)
        assert "time_ms" in entry


def test_schema_drift_error_points_at_analytic_consumers():
    w = get_workload("kmeans")
    plan = compile_for_sim(w.program, "LTRF", 16, 16)
    stats = {k: dict(v) for k, v in plan.pass_stats.items()}
    del stats["prefetch"]["serial_rounds"]
    stats.pop("emit")
    with pytest.raises(AnalyticModelError) as ei:
        check_pass_stats(stats, "LTRF")
    msg = str(ei.value)
    assert "src/repro/sim/analytic.py" in msg
    assert "ANALYTIC_PASS_SCHEMA" in msg
    assert "serial_rounds" in msg and "'emit' missing" in msg


# ------------------------------------------- differential acceptance (fast)

@pytest.fixture(scope="module")
def small_domain(tmp_path_factory):
    """Two workload groups x all designs, both tiers, engine run fresh."""
    cache = tmp_path_factory.mktemp("an_cache")
    jobs = [(n, design_config(d, table2_config=7))
            for n in ("srad", "sgemm") for d in DESIGNS]
    runner = SimRunner(processes=1, cache_dir=cache)
    runner.prefill(jobs, tier="engine")
    eng = {j: runner.sim(*j) for j in jobs}
    est = {j: runner.estimate(*j) for j in jobs}
    return cache, jobs, eng, est


def test_rank_correlation_small_domain(small_domain):
    _, jobs, eng, est = small_domain
    rho = spearman_rho([est[j].cycles for j in jobs],
                       [eng[j].cycles for j in jobs])
    assert rho >= 0.85, f"pooled Spearman rho {rho:.3f} below floor"


def test_frontier_recall_small_domain(small_domain):
    """Per workload, the engine's true Pareto frontier over (cycles, MRF
    accesses) must be contained in the hybrid selection (analytic frontier
    + top-3 estimated-cycle points)."""
    _, jobs, eng, est = small_domain
    for wname in ("srad", "sgemm"):
        members = [j for j in jobs if j[0] == wname]
        eng_front = set(pareto_frontier(
            [(eng[j].cycles, eng[j].mrf_accesses) for j in members]))
        est_pts = [(est[j].cycles, est[j].est_mrf_accesses) for j in members]
        picked = set(pareto_frontier(est_pts))
        picked.update(sorted(range(len(members)),
                             key=lambda i: est_pts[i][0])[:3])
        assert eng_front <= picked, \
            (wname, sorted(eng_front - picked))


def test_hybrid_returns_engine_verdicts_bit_identical(small_domain, tmp_path):
    _, jobs, eng, _ = small_domain
    # fresh cache: only the hybrid confirmation sweep populates it, so the
    # cache itself witnesses exactly which points got engine verdicts
    runner = SimRunner(processes=1, cache_dir=tmp_path / "hyb", tier="hybrid")
    rep = runner.prefill(jobs)
    assert rep.tier == "hybrid" and rep.ok
    assert rep.analytic_points == len(jobs)
    assert rep.frontier_jobs and \
        rep.frontier_confirmed == len(rep.frontier_jobs)
    confirmed = 0
    for job in jobs:
        if runner._lookup(job) is None:
            continue  # screened-out point: estimate only, by design
        confirmed += 1
        res = runner.sim(*job)
        assert res == eng[job]  # replay: engine-verdict result
        assert res == simulate(get_workload(job[0]), job[1])  # fresh engine
    assert confirmed == rep.frontier_confirmed


def test_estimate_ipc_consistency(small_domain):
    _, jobs, _, est = small_domain
    for j, r in est.items():
        assert r.ipc == pytest.approx(r.instructions / max(r.cycles, 1))
        assert r.tier == "analytic"
        total = sum(r.cycle_breakdown.values())
        assert total == pytest.approx(r.cycles, abs=1.0)


def test_screening_grid_is_thousands_of_points():
    jobs = screening_jobs()
    assert len(set(jobs)) == len(jobs) >= 3500
    assert all(analytic_supported(cfg) for _, cfg in jobs)


@pytest.mark.parametrize("workload", sorted(workload_names()))
def test_screening_grid_hybrid_confirms_frontier(workload):
    """The screening grid through the hybrid tier, one workload's points
    per case (the tier selects per workload, so the cases add up to the
    whole grid): every point is priced by the analytic model, and every
    point it selects comes back engine-confirmed."""
    jobs = screening_jobs(workloads=(workload,))
    runner = SimRunner(processes=1, disk_cache=False)
    rep = runner.prefill(jobs, tier="hybrid", top_k=3)
    assert rep.ok and rep.analytic_points == len(jobs)
    assert len(rep.frontier_jobs) >= 3
    assert rep.frontier_confirmed == len(rep.frontier_jobs)


# ---------------------------------------------------------- calibration

def test_calibration_round_trip(tmp_path):
    calib = Calibration(theta_pf=0.5, theta_mem=0.25, theta_dep=0.0,
                        theta_bank=1.5, source="fitted", n_samples=12)
    path = tmp_path / "calib.json"
    save_calibration(calib, path)
    loaded = load_calibration(path)
    assert loaded == calib
    assert load_calibration(tmp_path / "missing.json") is None


@pytest.mark.parametrize("mutate", [
    lambda d: d.update(analytic_rev=ANALYTIC_REV + 1),
    lambda d: d.update(calib_rev=CALIB_REV + 1),
    lambda d: d.pop("coeffs"),
    lambda d: d["coeffs"].update(theta_pf=-0.1),
    lambda d: d["coeffs"].update(theta_mem=float("nan")),
    lambda d: d["coeffs"].pop("theta_bank"),
])
def test_calibration_validation_rejects_bad_payloads(tmp_path, mutate):
    payload = calibration_to_dict(DEFAULT_CALIBRATION)
    mutate(payload)
    with pytest.raises(CalibrationError):
        calibration_from_dict(payload)


def test_corrupt_calibration_file_raises(tmp_path):
    path = tmp_path / "calib.json"
    path.write_text("{definitely not json")
    with pytest.raises(CalibrationError):
        load_calibration(path)


def test_calibration_keys_estimate_cache():
    cfg = SimConfig(design="LTRF")
    k1 = analytic_sim_key("srad", cfg, DEFAULT_CALIBRATION)
    k2 = analytic_sim_key("srad", cfg,
                          replace(DEFAULT_CALIBRATION, theta_pf=0.5))
    assert k1 != k2, "calibration coefficients must key the estimate cache"
    assert k1.startswith("an")
    assert k1 != sim_key("srad", cfg)


def test_run_calibrate_saves_what_load_reads(tmp_path, capsys):
    """``python -m benchmarks.run calibrate`` on a small sample set: it
    prints the fitted calibration, and the file it saves loads back with the
    same coefficients, as the runner's own calibration."""
    from benchmarks.run import calibrate
    from repro.serving.sweep import CALIBRATION_KEY

    runner = SimRunner(processes=1, cache_dir=tmp_path / "cache")
    jobs = sweep_jobs(workloads=("srad", "kmeans"), table2_configs=(7,))
    calib = calibrate(jobs=jobs, runner=runner)
    assert calib.source == "fitted"
    assert calib.n_samples == len([j for j in dict.fromkeys(jobs)
                                   if analytic_supported(j[1])])
    assert json.loads(capsys.readouterr().out) == calibration_to_dict(calib)
    assert load_calibration(runner.store.path(CALIBRATION_KEY)) == calib
    fresh = SimRunner(processes=1, cache_dir=tmp_path / "cache")
    assert fresh.calibration() == calib


def test_fit_calibration_needs_samples():
    w = get_workload("kmeans")
    cfg = SimConfig(design="BL")
    with pytest.raises(AnalyticModelError):
        fit_calibration([(w, cfg, 100)] * 3)


@pytest.mark.slow
def test_fit_calibration_on_engine_ground_truth():
    """The full fit: engine-run training set -> non-negative coefficients
    that do not *hurt* rank accuracy vs the uncalibrated (theta=1) model."""
    jobs = [(n, design_config(d, table2_config=tc))
            for n in ("srad", "kmeans", "bfs", "sgemm")
            for d in DESIGNS for tc in (6, 7)]
    samples, eng = [], {}
    for name, cfg in jobs:
        w = get_workload(name)
        res = simulate(w, cfg)
        eng[(name, cfg)] = res.cycles
        samples.append((w, cfg, res.cycles))
    calib = fit_calibration(samples)
    assert calib.source == "fitted" and calib.n_samples == len(samples)
    for theta in calib.coeffs():
        assert math.isfinite(theta) and theta >= 0.0
    fitted = [estimate(get_workload(n), c, calib=calib).cycles
              for n, c in jobs]
    default = [estimate(get_workload(n), c,
                        calib=Calibration()).cycles for n, c in jobs]
    truth = [eng[j] for j in jobs]
    assert spearman_rho(fitted, truth) >= spearman_rho(default, truth) - 0.02
    assert spearman_rho(fitted, truth) >= 0.9


# ------------------------------------- tracked-domain acceptance (slow)

@pytest.mark.slow
def test_tracked_domain_differential_acceptance(tmp_path):
    """ISSUE 9 acceptance on the tracked sweep domain, in-process: pooled
    Spearman rho >= 0.9, Pareto-frontier recall pinned at 1.0, and analytic
    throughput >= 100x the engine's on the same host."""
    jobs = [j for j in dict.fromkeys(sweep_jobs())
            if analytic_supported(j[1])]
    runner = SimRunner(processes=1, cache_dir=tmp_path / "cache")
    t0 = time.time()
    rep = runner.prefill(jobs, tier="engine")
    engine_wall = time.time() - t0
    assert rep.ok
    eng = {j: runner.sim(*j) for j in jobs}

    t0 = time.time()
    est = {j: runner.estimate(*j) for j in jobs}
    runner._analytic_memo.clear()
    t0 = time.time()
    est = {j: runner.estimate(*j) for j in jobs}
    analytic_wall = time.time() - t0

    rho = spearman_rho([est[j].cycles for j in jobs],
                       [eng[j].cycles for j in jobs])
    assert rho >= 0.9, f"tracked-domain Spearman rho {rho:.4f} < 0.9"

    groups: dict[tuple, list] = {}
    for j in jobs:
        groups.setdefault((j[0], j[1].rf_size_kb), []).append(j)
    missed = []
    for key, members in groups.items():
        eng_front = set(pareto_frontier(
            [(eng[j].cycles, eng[j].mrf_accesses) for j in members]))
        est_pts = [(est[j].cycles, est[j].est_mrf_accesses) for j in members]
        picked = set(pareto_frontier(est_pts))
        picked.update(sorted(range(len(members)),
                             key=lambda i: est_pts[i][0])[:3])
        if not eng_front <= picked:
            missed.append(key)
    assert not missed, f"frontier recall broken in groups {missed}"

    total_instr = sum(r.instructions for r in eng.values())
    engine_per_s = total_instr / max(engine_wall, 1e-9)
    analytic_per_s = total_instr / max(analytic_wall, 1e-9)
    assert analytic_per_s >= 100 * engine_per_s, \
        f"analytic {analytic_per_s:.0f} instr/s < 100x engine {engine_per_s:.0f}"

