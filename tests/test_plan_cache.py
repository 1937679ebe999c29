"""Tests for the compile cache (core.plan_cache) and the sweep runner."""
import pytest

from repro.core.ir import parse_asm
from repro.core.plan_cache import (
    cache_clear, cache_stats, cached_intervals, cached_prefetch_ops,
    cached_renumber, compile_for_sim, program_fingerprint,
)
from repro.serving.sweep import SimRunner
from repro.sim import SimConfig, Simulator, design_config, simulate
from repro.workloads import WORKLOADS

ASM = """
    mov r0, 0
    mov r1, 8
L1: ld r2, [r0]
    add r3, r2, r1
    add r0, r0, 4
    set p0, r0, r1
    @p0 bra L1
    exit
"""


def test_fingerprint_is_structural():
    a = parse_asm(ASM, name="a")
    b = parse_asm(ASM, name="b")  # different object, same structure
    assert a is not b
    assert program_fingerprint(a) == program_fingerprint(b)
    c = parse_asm(ASM.replace("add r3, r2, r1", "add r3, r2, r2"), name="c")
    assert program_fingerprint(a) != program_fingerprint(c)


def test_interval_analysis_shared_across_equal_programs():
    a = parse_asm(ASM, name="a")
    b = parse_asm(ASM, name="b")
    assert cached_intervals(a, 8) is cached_intervals(b, 8)
    assert cached_intervals(a, 8) is not cached_intervals(a, 4)


def test_compile_shared_across_simulators_and_latency_points():
    w = WORKLOADS["srad"]
    s1 = Simulator(design_config("LTRF", mrf_latency_mult=2.0), w)
    s2 = Simulator(design_config("LTRF", mrf_latency_mult=6.3), w)
    # the MRF latency multiplier is not a compile input: one shared plan
    assert s1.prog is s2.prog
    assert s1.pf_ops is s2.pf_ops
    s3 = Simulator(design_config("LTRF_conf", mrf_latency_mult=2.0), w)
    assert s3.prog is not s1.prog  # renumbering produces its own program


def test_compile_cache_hits_counted():
    prog = parse_asm(ASM, name="stats")
    before = cache_stats()
    compile_for_sim(prog, "LTRF", 8, 16)
    compile_for_sim(prog, "LTRF", 8, 16)
    after = cache_stats()
    assert after["hits"] > before["hits"]
    assert after["sim_plans"] >= 1


def test_cached_passes_match_direct_results():
    from repro.core.intervals import form_register_intervals
    from repro.core.prefetch import prefetch_schedule
    prog = parse_asm(ASM, name="direct")
    an_direct = form_register_intervals(prog, 8)
    an_cached = cached_intervals(prog, 8)
    assert [iv.working_set for iv in an_direct.intervals] == \
           [iv.working_set for iv in an_cached.intervals]
    ops_direct = prefetch_schedule(an_direct, num_banks=16)
    ops_cached = cached_prefetch_ops(an_cached, num_banks=16)
    assert {o.interval_id: o.bitvector for o in ops_direct} == \
           {i: o.bitvector for i, o in ops_cached.items()}
    rr = cached_renumber(prog, 8, 16)
    assert rr is cached_renumber(prog, 8, 16)


def test_analysis_caches_key_on_interval_grouping():
    """Two analyses over the SAME split program with the same cap and the
    same interval *count* but different block groupings must not collide in
    the prefetch/ICG caches (reachable via custom interval strategies)."""
    from repro.core.intervals import Interval, IntervalAnalysis

    prog = parse_asm("""
        mov r0, 1
        bra B
    B:  add r1, r0, r0
        bra C
    C:  add r2, r1, r1
        exit
    """, name="grouping")
    a_label = prog.order[0]

    def grouped(pairs):
        intervals = [Interval(iid=i, header=blocks[0], blocks=list(blocks),
                              working_set=set().union(
                                  *(prog.blocks[b].refs() for b in blocks)))
                     for i, blocks in enumerate(pairs)]
        bi = {b: iv.iid for iv in intervals for b in iv.blocks}
        return IntervalAnalysis(prog=prog, intervals=intervals,
                                block_interval=bi, n_cap=8)

    an1 = grouped([(a_label, "B"), ("C",)])
    an2 = grouped([(a_label,), ("B", "C")])
    ops1 = cached_prefetch_ops(an1, 16)
    ops2 = cached_prefetch_ops(an2, 16)
    assert ops1 is not ops2
    assert ops1[0].bitvector != ops2[0].bitvector
    # ...and neither must analyses with identical grouping whose working
    # sets differ (e.g. a liveness-trimming custom strategy)
    an3 = grouped([(a_label, "B"), ("C",)])
    for iv in an3.intervals:
        iv.working_set = {min(iv.working_set)}
    ops3 = cached_prefetch_ops(an3, 16)
    assert ops3 is not ops1
    assert ops3[0].bitvector == frozenset({min(ops1[0].bitvector)})


def test_cache_clear_resets():
    prog = parse_asm(ASM, name="clear-me")
    cached_intervals(prog, 8)
    cache_clear()
    assert cache_stats()["intervals"] == 0
    # and the cache repopulates fine afterwards
    assert cached_intervals(prog, 8).intervals


# ------------------------------------------------------------ sweep runner

def test_runner_memo_and_disk_cache(tmp_path):
    cfg = SimConfig(design="LTRF", num_warps=8)
    runner = SimRunner(processes=1, cache_dir=tmp_path)
    a = runner.sim("kmeans", cfg)
    assert runner.stats["computed"] == 1
    b = runner.sim("kmeans", cfg)
    assert b is a and runner.stats["memo_hits"] == 1
    # a fresh runner sharing the cache dir replays from disk, exactly
    runner2 = SimRunner(processes=1, cache_dir=tmp_path)
    c = runner2.sim("kmeans", cfg)
    assert runner2.stats["disk_hits"] == 1 and runner2.stats["computed"] == 0
    assert c == simulate(WORKLOADS["kmeans"], cfg)


def test_runner_prefill_dedupes(tmp_path):
    cfg = SimConfig(design="BL", num_warps=8)
    runner = SimRunner(processes=1, cache_dir=tmp_path)
    runner.prefill([("bfs", cfg)] * 5 + [("nw", cfg)])
    assert runner.stats["computed"] == 2
    assert runner.sim("bfs", cfg) == simulate(WORKLOADS["bfs"], cfg)


def test_runner_parallel_prefill_matches_serial(tmp_path):
    jobs = [(n, SimConfig(design=d, num_warps=8))
            for n in ("kmeans", "btree") for d in ("BL", "LTRF")]
    par = SimRunner(processes=2, cache_dir=tmp_path / "p")
    par.prefill(jobs)
    for name, cfg in jobs:
        assert par.sim(name, cfg) == simulate(WORKLOADS[name], cfg)
