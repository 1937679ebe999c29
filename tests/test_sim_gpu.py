"""Multi-SM GPU model + warp-scheduler policy tests (`repro.sim.gpu`).

The bit-identity of ``num_sms=1`` + ``two_level`` against the single-SM
engine/golden pair lives in tests/test_sim_golden.py; here: the CTA
dispatcher, the shared memory-partition model, GpuResult aggregation,
scheduler-policy behaviour, and the orchestrator's GPU path.
"""
import pytest

from repro.sim import SCHEDULERS, SimConfig, design_config, simulate, simulate_gpu
from repro.sim.gpu import (
    SM_SEED_STRIDE, dispatch_ctas, gpu_jobs, per_sm_configs,
)
from repro.workloads import WORKLOADS

W = WORKLOADS["srad"]
WMEM = WORKLOADS["bfs"]  # memory-bound, low L1 hit rate


# ------------------------------------------------------------- dispatcher

def test_dispatch_round_robin_balance():
    assert dispatch_ctas(64, 4) == [16, 16, 16, 16]
    assert dispatch_ctas(10, 4) == [4, 4, 2, 0]
    assert dispatch_ctas(3, 2, warps_per_cta=4) == [3, 0]
    assert dispatch_ctas(0, 3) == [0, 0, 0]


def test_dispatch_preserves_total_warps():
    for n, sms, cta in ((64, 4, 4), (13, 3, 2), (7, 8, 4), (100, 6, 8)):
        assert sum(dispatch_ctas(n, sms, cta)) == n


def test_dispatch_rejects_bad_args():
    with pytest.raises(ValueError):
        dispatch_ctas(8, 0)
    with pytest.raises(ValueError):
        dispatch_ctas(8, 2, warps_per_cta=0)


# --------------------------------------------------------- per-SM configs

def test_per_sm_configs_single_sm_is_identity():
    cfg = design_config("LTRF", table2_config=7, num_warps=16)
    assert per_sm_configs(cfg) == [cfg]


def test_per_sm_configs_distinct_seeds_and_shares():
    cfg = design_config("LTRF", num_warps=24, num_sms=3)
    sub = per_sm_configs(cfg)
    assert [c.num_warps for c in sub] == [8, 8, 8]
    assert [c.seed for c in sub] == [cfg.seed + SM_SEED_STRIDE * i
                                     for i in range(3)]
    assert all(c.num_sms == 1 and c.mem_partitions == 0 for c in sub)


def test_per_sm_configs_idle_sms_dropped():
    cfg = design_config("BL", num_warps=4, num_sms=4)  # one CTA of 4 warps
    sub = per_sm_configs(cfg)
    assert len(sub) == 1 and sub[0].num_warps == 4


def test_shared_dram_partitions_scale_interval():
    cfg = design_config("BL", num_warps=32, num_sms=4, mem_partitions=2)
    sub = per_sm_configs(cfg)
    # 4 SMs sharing 2 partitions: each sees half its uncontended bandwidth
    assert all(c.dram_interval == cfg.dram_interval * 2 for c in sub)
    fair = per_sm_configs(design_config("BL", num_warps=32, num_sms=4))
    assert all(c.dram_interval == cfg.dram_interval for c in fair)


def test_dram_contention_hurts_memory_bound_ipc():
    fair = design_config("BL", table2_config=7, num_warps=64, num_sms=4)
    contended = design_config("BL", table2_config=7, num_warps=64, num_sms=4,
                              mem_partitions=1)
    assert simulate_gpu(WMEM, contended).ipc < simulate_gpu(WMEM, fair).ipc


# ------------------------------------------------------------ aggregation

def test_gpu_result_aggregates_counters():
    cfg = design_config("LTRF", table2_config=7, num_warps=32, num_sms=4)
    g = simulate_gpu(W, cfg)
    assert len(g.per_sm) == 4
    assert g.instructions == sum(r.instructions for r in g.per_sm)
    assert g.cycles == max(r.cycles for r in g.per_sm)
    for f in ("mrf_accesses", "rfc_accesses", "rfc_hits", "prefetch_ops",
              "writeback_regs", "activations", "resident_warps"):
        assert getattr(g, f) == sum(getattr(r, f) for r in g.per_sm), f
    assert g.num_sms == 4 and g.scheduler == "two_level"
    assert g.sm_imbalance >= 1.0


def test_gpu_scales_throughput_over_sms():
    one = design_config("LTRF", table2_config=7, num_warps=16, num_sms=1)
    four = design_config("LTRF", table2_config=7, num_warps=64, num_sms=4)
    # 4 SMs x 16 warps retire ~4x the instructions in about the same time
    assert simulate_gpu(W, four).ipc > 2.5 * simulate_gpu(W, one).ipc


def test_gpu_simulation_deterministic():
    cfg = design_config("LTRF_conf", table2_config=6, num_warps=24,
                        num_sms=3, scheduler="gto")
    assert simulate_gpu(W, cfg) == simulate_gpu(W, cfg)


# ------------------------------------------------------------- schedulers

def test_scheduler_policies_same_dynamic_work():
    """Branch outcomes depend only on (wid, visit, seed), so every policy
    retires the identical dynamic instruction stream."""
    counts = set()
    for sched in SCHEDULERS:
        cfg = design_config("LTRF", table2_config=7, num_warps=16,
                            scheduler=sched)
        counts.add(simulate(W, cfg).instructions)
    assert len(counts) == 1


def test_scheduler_sensitivity_on_cached_design():
    """The policies must actually schedule differently: cycle counts differ
    and only two_level pays deactivation write-backs."""
    res = {s: simulate(W, design_config("LTRF", table2_config=7,
                                        num_warps=16, scheduler=s))
           for s in SCHEDULERS}
    assert res["two_level"].writeback_regs > 0
    assert res["gto"].writeback_regs == 0
    assert res["lrr"].writeback_regs == 0
    assert len({r.cycles for r in res.values()}) >= 2


def test_two_level_equals_lrr_on_uncached_designs():
    """Without a register cache there is no active-slot restriction, so the
    paper scheduler degenerates to loose round-robin."""
    for design in ("BL", "RFC", "Ideal"):
        a = simulate(W, design_config(design, table2_config=7, num_warps=16,
                                      scheduler="two_level"))
        b = simulate(W, design_config(design, table2_config=7, num_warps=16,
                                      scheduler="lrr"))
        assert (a.cycles, a.instructions, a.mrf_accesses) == \
               (b.cycles, b.instructions, b.mrf_accesses), design


def test_gto_differs_from_round_robin():
    a = simulate(W, design_config("BL", table2_config=7, num_warps=16,
                                  scheduler="gto"))
    b = simulate(W, design_config("BL", table2_config=7, num_warps=16,
                                  scheduler="lrr"))
    assert a.instructions == b.instructions
    assert a.cycles != b.cycles


def test_engine_rejects_gpu_scale_configs():
    with pytest.raises(ValueError, match="simulate_gpu"):
        simulate(W, design_config("BL", num_sms=2))
    with pytest.raises(ValueError, match="scheduler"):
        simulate(W, SimConfig(design="BL", scheduler="greedy"))


# ----------------------------------------------------------- sweep runner

def test_orchestrator_gpu_path(tmp_path):
    from repro.serving.sweep import SimRunner
    cfg = design_config("LTRF", table2_config=7, num_warps=32, num_sms=4,
                        scheduler="lrr")
    runner = SimRunner(processes=1, cache_dir=tmp_path)
    runner.prefill_gpu([("srad", cfg)])
    g = runner.sim_gpu("srad", cfg)
    assert g == simulate_gpu(W, cfg)
    # every per-SM job was computed exactly once, then replayed from memo
    assert runner.stats["computed"] == len(per_sm_configs(cfg))
    before = dict(runner.stats)
    assert runner.sim_gpu("srad", cfg) == g
    assert runner.stats["computed"] == before["computed"]
    # a fresh runner replays the per-SM results from the disk cache
    replay = SimRunner(processes=1, cache_dir=tmp_path)
    assert replay.sim_gpu("srad", cfg) == g
    assert replay.stats["computed"] == 0 and replay.stats["disk_hits"] > 0


def test_gpu_jobs_expand_per_sm():
    cfg = design_config("BL", num_warps=32, num_sms=4)
    jobs = gpu_jobs("srad", cfg)
    assert len(jobs) == 4
    assert all(name == "srad" and c.num_sms == 1 for name, c in jobs)
