"""Unit tests for the observability layer (`repro.obs`) and its wiring:
cycle-attribution helpers, the Chrome-trace sink, the metrics registry,
deterministic sweep run ids, the `benchmarks.profile` CLI, and the
suite-level attribution verdict (the LTRF designs shrink BL's exposed
memory stalls).

The simulation-level invariants (breakdown sums to cycles on random
programs, tracer bit-identity, GPU aggregation) live in
``tests/test_sim_fuzz.py``; the Listing-1 attribution pins live in
``tests/test_sim_golden.py``.  Here: the pieces in isolation.
"""
from __future__ import annotations

import json

import pytest

from repro.obs import (
    CYCLE_CATEGORIES, SCHED_TID, STALL_CATEGORIES, SWEEP_METRICS,
    CycleAttributionError, MetricsRegistry, TraceSink, breakdown_fractions,
    check_breakdown, classify_stall, merge_breakdowns, new_breakdown,
)

# ------------------------------------------------------------- attribution


def test_categories_contract():
    assert CYCLE_CATEGORIES[0] == "issue"
    assert set(STALL_CATEGORIES) == set(CYCLE_CATEGORIES) - {"issue"}
    bd = new_breakdown()
    assert tuple(bd) == CYCLE_CATEGORIES and all(v == 0 for v in bd.values())


def test_classify_stall_precedence():
    # drain wins over everything; then struct, prefetch, mem, dep; the
    # no-signal fallthrough is scheduler_idle
    assert classify_stall(True, True, True, True, True) == "drain"
    assert classify_stall(False, True, True, True, True) == "bank_conflict"
    assert classify_stall(False, False, True, True, True) == "prefetch_stall"
    assert classify_stall(False, False, False, True, True) == "mem_stall"
    assert classify_stall(False, False, False, False, True) == "alu_dep"
    assert classify_stall(False, False, False, False, False) \
        == "scheduler_idle"


def test_check_breakdown_accepts_exact_sum():
    bd = new_breakdown()
    bd["issue"], bd["mem_stall"] = 7, 3
    check_breakdown(bd, 10, "BL", "wl")  # no raise


def test_check_breakdown_raises_on_mismatch_and_bad_categories():
    bd = new_breakdown()
    bd["issue"] = 9
    with pytest.raises(CycleAttributionError, match="unattributed: 1"):
        check_breakdown(bd, 10, "BL", "wl")
    with pytest.raises(CycleAttributionError, match="categories"):
        check_breakdown({"issue": 10}, 10, "BL", "wl")


def test_fractions_and_merge():
    a, b = new_breakdown(), new_breakdown()
    a["issue"], a["drain"] = 6, 2
    b["issue"], b["mem_stall"] = 2, 2
    merged = merge_breakdowns([a, b])
    assert merged["issue"] == 8 and sum(merged.values()) == 12
    frac = breakdown_fractions(merged)
    assert abs(sum(frac.values()) - 1.0) < 1e-12
    assert frac["issue"] == 8 / 12
    assert breakdown_fractions(new_breakdown()) == \
        {c: 0.0 for c in CYCLE_CATEGORIES}


# -------------------------------------------------------------- trace sink


def test_trace_sink_chrome_document():
    sink = TraceSink(sm=3)
    sink.span(0, "add", 10, 4, {"block": "B0"})
    sink.span(SCHED_TID, "mem_stall", 14, 6)
    sink.instant(1, "activate", 2)
    doc = sink.to_chrome()
    evs = doc["traceEvents"]
    # metadata names every track once, plus the process
    names = {(e["tid"], e["args"]["name"]) for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {(0, "warp 0"), (1, "warp 1"),
                     (SCHED_TID, "scheduler")}
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               and e["args"]["name"] == "SM 3" for e in evs)
    # and the document round-trips through JSON
    again = json.loads(json.dumps(doc))
    assert again["displayTimeUnit"] == "ms"
    assert [e for e in again["traceEvents"] if e["ph"] == "X"] == \
        [e for e in evs if e["ph"] == "X"]


def test_trace_sink_zero_duration_spans_stay_visible():
    sink = TraceSink()
    sink.span(0, "bra", 5, 0)
    assert sink.events[0]["dur"] == 1  # Perfetto drops dur=0 spans


def test_trace_sink_write(tmp_path):
    sink = TraceSink()
    sink.instant(2, "swap_out", 9, {"until": 40})
    p = sink.write(tmp_path / "t.json")
    doc = json.loads(p.read_text())
    ev = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert ev == [{"ph": "i", "pid": 0, "tid": 2, "name": "swap_out",
                   "ts": 9, "s": "t", "args": {"until": 40}}]


# ---------------------------------------------------------------- metrics


def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("jobs", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_moves_both_ways():
    g = MetricsRegistry().gauge("pool")
    g.set(4)
    g.dec()
    g.inc(2)
    assert g.value == 5


def test_histogram_nearest_rank_percentiles():
    h = MetricsRegistry().histogram("lat")
    for v in range(1, 101):  # 1..100: pXX == XX under nearest-rank
        h.observe(float(v))
    s = h.summary()
    assert (s["count"], s["min"], s["max"]) == (100, 1.0, 100.0)
    assert (s["p50"], s["p95"], s["p99"]) == (50.0, 95.0, 99.0)
    assert s["sum"] == 5050.0
    one = MetricsRegistry().histogram("one")
    one.observe(7.0)
    assert one.summary()["p99"] == 7.0
    assert MetricsRegistry().histogram("empty").summary() == \
        {"count": 0, "sum": 0.0}


def test_histogram_nearest_rank_matches_naive_reference():
    """Property test over (q, n) grids: the rank must equal the smallest
    1-indexed rank r with r >= q*n — the nearest-rank definition spelled
    out naively.  The old int-scaling trick (-(-int(q*n*100) // 100))
    truncated before ceiling and silently under-ranked whenever q*n had a
    fractional part below 0.01, e.g. (q=0.5000001, n=20)."""
    from repro.obs.metrics import Histogram

    def naive_rank(q, n):
        r = 1
        while r < n and r < q * n:
            r += 1
        return r

    qs = (0.01, 0.05, 0.1, 0.25, 0.5, 0.5000001, 0.75, 0.9, 0.95,
          0.99, 0.999, 1.0)
    for n in (*range(1, 65), 100, 128, 999):
        samples = [float(i) for i in range(1, n + 1)]  # value == rank
        for q in qs:
            got = Histogram._nearest_rank(samples, q)
            assert got == float(naive_rank(q, n)), (q, n, got)
    # an exact case the int-scaling bug got wrong: ceil(10.000002) is 11,
    # but int(1000.0002) // 100 ceiled to 10
    assert Histogram._nearest_rank([float(i) for i in range(1, 21)],
                                   0.5000001) == 11.0


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_snapshot_and_prometheus_and_write(tmp_path):
    reg = MetricsRegistry()
    reg.counter("sweep_jobs_total", "jobs").inc(3)
    reg.gauge("inflight").set(2)
    reg.histogram("sweep_job_latency_s").observe(0.25)
    snap = reg.snapshot(run_id="abc123")
    assert snap["run_id"] == "abc123"
    assert snap["sweep_jobs_total"] == 3
    assert snap["sweep_job_latency_s"]["count"] == 1
    prom = reg.to_prometheus(host="ci")
    assert '# TYPE sweep_jobs_total counter' in prom
    assert 'sweep_jobs_total{host="ci"} 3' in prom
    assert '# TYPE sweep_job_latency_s summary' in prom
    assert 'sweep_job_latency_s{host="ci",quantile="0.5"} 0.25' in prom
    assert 'sweep_job_latency_s_count{host="ci"} 1' in prom
    p = reg.write_snapshot(tmp_path / "m.json", run_id="abc123")
    assert json.loads(p.read_text())["sweep_jobs_total"] == 3


# ----------------------------------------------------- sweep run_id + wiring


def _jobs(n=3):
    from repro.sim import design_config
    return [("srad", design_config(d, num_warps=4))
            for d in ("BL", "LTRF", "LTRF_conf")[:n]]


def test_sweep_run_id_deterministic_and_order_insensitive():
    from repro.serving.sweep import sweep_run_id

    jobs = _jobs()
    rid = sweep_run_id(jobs)
    assert rid and len(rid) == 12
    assert rid == sweep_run_id(list(reversed(jobs)))  # canonicalized
    assert rid != sweep_run_id(_jobs(2))              # job set is identity


def test_runner_metrics_and_run_id(tmp_path):
    from repro.serving.sweep import SimRunner, sweep_run_id

    jobs = _jobs()
    runner = SimRunner(processes=1, disk_cache=False)
    rep = runner.prefill(jobs)
    assert rep.run_id == runner.last_run_id == sweep_run_id(jobs)
    snap = runner.metrics_snapshot()
    assert snap["run_id"] == rep.run_id
    assert snap["sweep_jobs_total"] == len(jobs)
    assert snap["sweep_jobs_computed"] == len(jobs)
    assert snap["sweep_job_latency_s"]["count"] == len(jobs)
    # second prefill: all memo hits, counters accumulate
    runner.prefill(jobs)
    snap2 = runner.metrics_snapshot()
    assert snap2["sweep_jobs_total"] == 2 * len(jobs)
    assert snap2["sweep_cache_hits_total"] >= len(jobs)
    for name in SWEEP_METRICS:
        assert name in snap2, name
    prom = runner.metrics.to_prometheus()
    assert "sweep_jobs_total" in prom and "sweep_job_latency_s_count" in prom


def test_failure_records_carry_run_id(tmp_path):
    """A failed job's FailureRecord is stamped with the sweep's run_id, so
    degraded-sweep artifacts are joinable with metrics snapshots."""
    from repro.serving.sweep import FailureRecord

    fr = FailureRecord(job="srad/BL", workload="srad", design="BL",
                       kind="crash", detail="x", run_id="deadbeef0123")
    assert fr.to_dict()["run_id"] == "deadbeef0123"


# ------------------------------------------------------------- profile CLI


def test_profile_cli_json_and_trace(tmp_path, capsys):
    from benchmarks.profile import main

    out_trace = tmp_path / "trace.json"
    rc = main(["--workload", "srad", "--design", "LTRF", "--num-warps", "4",
               "--trace-out", str(out_trace), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["cycles"] == sum(out["cycle_breakdown"].values())
    assert tuple(out["cycle_breakdown"]) == CYCLE_CATEGORIES
    assert out["trace_events"] > 0
    doc = json.loads(out_trace.read_text())
    assert doc["traceEvents"]
    warp_tracks = {e["tid"] for e in doc["traceEvents"]
                   if e.get("ph") == "M" and e.get("name") == "thread_name"
                   and e["args"]["name"].startswith("warp ")}
    assert len(warp_tracks) >= 2


def test_profile_cli_breakdown_table(capsys):
    from benchmarks.profile import main

    rc = main(["--workload", "kmeans", "--design", "BL", "--num-warps", "4",
               "--breakdown"])
    assert rc == 0
    text = capsys.readouterr().out
    for cat in CYCLE_CATEGORIES:
        assert cat in text
    assert "cycles" in text and "ipc=" in text


# ------------------------------------------------- suite-level attribution

@pytest.fixture(scope="module")
def suite_runner():
    from repro.serving.sweep import SimRunner
    return SimRunner(processes=1, disk_cache=False)


def _suite_runs(runner, design):
    from repro.sim import design_config
    from repro.workloads import workload_names

    jobs = [(n, design_config(design, table2_config=7))
            for n in workload_names()]
    assert runner.prefill(jobs).ok
    return {n: runner.sim(n, c) for n, c in jobs}


@pytest.mark.parametrize("design", ["BL", "LTRF", "LTRF_conf"])
def test_suite_cycle_attribution(suite_runner, design):
    """Over the whole suite at Table-2 config #7 every run's breakdown sums
    to its cycles, and each LTRF design turns BL's exposed memory stalls
    into prefetch the scheduler mostly hides: aggregate ``mem_stall``
    cycles and total cycles both strictly below BL's."""
    runs = _suite_runs(suite_runner, design)
    for name, r in runs.items():
        assert sum(r.cycle_breakdown.values()) == r.cycles, name
    if design == "BL":
        return
    bl = merge_breakdowns(r.cycle_breakdown
                          for r in _suite_runs(suite_runner, "BL").values())
    agg = merge_breakdowns(r.cycle_breakdown for r in runs.values())
    assert agg["mem_stall"] < bl["mem_stall"]
    assert sum(agg.values()) < sum(bl.values())
