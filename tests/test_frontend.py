"""Frontend: jaxpr lifting, linear-scan regalloc, traced-workload registry.

Covers the acceptance bar for the real-kernel path: every traced workload
lifts end to end, its interval plan validates across caps, both simulator
engines agree bit-for-bit across all 7 designs, the allocator honours
``maxregcount`` (including the spill fallback), and the suite registry keeps
the tracked synthetic job list stable while exposing the traced suite.
"""
import subprocess
import sys

import pytest

from repro.core.intervals import form_register_intervals
from repro.core.ir import back_edges, parse_asm, reachable_blocks
from repro.frontend.regalloc import allocate_registers
from repro.frontend.workloads import TRACED_NAMES, build_traced_workload
from jax_subprocess import jax_subprocess_env
from repro.sim import DESIGNS, design_config, simulate
from repro.sim.golden import golden_simulate
from repro.workloads import (WORKLOADS, Workload, get_workload,
                             register_workload, workload_names)

# The three in-repo kernel references the acceptance criteria name.
KERNEL_NAMES = ("traced_matmul", "traced_attention", "traced_ssd")


# --------------------------------------------------------------------- lift

@pytest.mark.parametrize("name", TRACED_NAMES)
def test_lift_end_to_end(name):
    w = get_workload(name)
    w.program.validate()
    assert w.program.num_instrs() > 15
    assert w.suite == "traced"
    # the whole CFG is reachable and every loop resolves through the trip table
    assert reachable_blocks(w.program) == set(w.program.order)
    for (_u, header) in back_edges(w.program):
        assert header in w.trips, f"loop {header} missing a trip count"
    assert 0 < w.regs_per_thread <= 64


@pytest.mark.parametrize("name", TRACED_NAMES)
@pytest.mark.parametrize("cap", (8, 16, 32))
def test_traced_interval_plan_validates(name, cap):
    w = get_workload(name)
    an = form_register_intervals(w.program, n_cap=cap)
    an.validate()
    assert len(an.intervals) >= 1


def test_lift_is_deterministic():
    a = build_traced_workload("traced_rmsnorm")
    import repro.core.plan_cache as pc
    pc.cache_clear()
    try:
        b = build_traced_workload("traced_rmsnorm")
    finally:
        pc.cache_clear()
    assert a.program.render() == b.program.render()
    assert a.trips == b.trips and a.regs_per_thread == b.regs_per_thread


def test_lift_cond_and_while():
    """Diamonds (`cond`) and default-trip loops (`while`) lift and terminate."""
    import jax

    def f(x):
        y = jax.lax.cond(x[0] > 0, lambda v: v * 2.0, lambda v: v - 1.0, x)

        def body(c):
            i, v = c
            return i + 1, v * 1.1

        return jax.lax.while_loop(lambda c: c[0] < 5, body, (0, y[0]))[1]

    from repro.frontend.jaxpr_lift import lift_fn

    lifted = lift_fn(f, (jax.ShapeDtypeStruct((4,), "float32"),),
                     name="condwhile")
    lifted.prog.validate()
    w = Workload(name="condwhile", program=lifted.prog, trips=lifted.trips,
                 register_sensitive=False, regs_per_thread=16, suite="test")
    cfg = design_config("LTRF", table2_config=7, num_warps=4)
    r = simulate(w, cfg)
    assert r.instructions > 0 and r.cycles > 0
    assert simulate(w, cfg) == golden_simulate(w, cfg)


# ------------------------------------------------------- engine equivalence

@pytest.mark.parametrize("design", DESIGNS)
def test_traced_kernels_match_golden_all_designs(design):
    for name in KERNEL_NAMES:
        w = get_workload(name)
        cfg = design_config(design, table2_config=7, num_warps=8)
        assert simulate(w, cfg) == golden_simulate(w, cfg), (design, name)


def test_traced_layers_match_golden():
    for name in set(TRACED_NAMES) - set(KERNEL_NAMES):
        w = get_workload(name)
        cfg = design_config("LTRF_plus", table2_config=6, num_warps=8)
        assert simulate(w, cfg) == golden_simulate(w, cfg), name


# ----------------------------------------------------------------- regalloc

def test_regalloc_respects_maxregcount():
    for name in ("traced_attention", "traced_mlp"):
        w = build_traced_workload(name, maxregcount=24)
        assert w.regs_per_thread <= 24
        assert max(w.program.registers()) < 24


def test_regalloc_spill_path_still_simulates():
    full = build_traced_workload("traced_attention", maxregcount=64)
    tight = build_traced_workload("traced_attention", maxregcount=16)
    assert tight.regs_per_thread <= 16
    # spilling rewrites uses through memory: strictly more ld/st traffic
    def mem_ops(w):
        return sum(1 for _, _, ins in w.program.instructions() if ins.is_mem)
    assert mem_ops(tight) > mem_ops(full)
    cfg = design_config("LTRF", table2_config=7, num_warps=4)
    assert simulate(tight, cfg) == golden_simulate(tight, cfg)


# Exact allocator output per (traced workload, maxregcount) — pinned when
# frontend/regalloc dropped its private `_live_intervals` in favor of the
# core liveness pass via the pipeline (ISSUE 5): the refactor must not move
# a single spill.  Format: (regs_per_thread, spills, spill_loads, spill_stores)
REGALLOC_GOLDEN = {
    ("traced_matmul", 64): (29, 0, 0, 0),
    ("traced_matmul", 24): (22, 9, 19, 17),
    ("traced_attention", 64): (30, 0, 0, 0),
    ("traced_attention", 24): (22, 17, 38, 32),
    ("traced_ssd", 64): (23, 0, 0, 0),
    ("traced_ssd", 24): (23, 0, 0, 0),
    ("traced_rmsnorm", 64): (8, 0, 0, 0),
    ("traced_rmsnorm", 24): (8, 0, 0, 0),
    ("traced_mlp", 64): (31, 0, 0, 0),
    ("traced_mlp", 24): (22, 18, 46, 31),
    ("traced_attn_layer", 64): (36, 0, 0, 0),
    ("traced_attn_layer", 24): (23, 23, 48, 38),
}


@pytest.mark.parametrize("name", TRACED_NAMES)
def test_regalloc_output_pinned_on_traced_suite(name):
    """Regression pin for the liveness dedup: `allocate_registers` through
    the core pipeline's liveness pass produces exactly the pre-refactor
    spill counts and register demands on the whole traced suite."""
    from repro.frontend.jaxpr_lift import lift_fn
    from repro.frontend.workloads import TRACED_SPECS

    spec = TRACED_SPECS[name]
    fn, args = spec.builder()
    lifted = lift_fn(fn, args, name=name, while_trips=spec.while_trips)
    for mrc in (64, 24):
        a = allocate_registers(lifted.prog, maxregcount=mrc)
        got = (a.regs_per_thread, a.spill_count, a.spill_loads,
               a.spill_stores)
        assert got == REGALLOC_GOLDEN[(name, mrc)], (name, mrc, got)


def test_regalloc_has_no_private_liveness():
    """The frontend must reuse `repro.core.liveness` through the pipeline —
    the duplicated `_live_intervals` implementation is gone for good."""
    from repro.frontend import regalloc

    assert not hasattr(regalloc, "_live_intervals")
    import inspect
    src = inspect.getsource(regalloc)
    assert "frontend_passes" in src and "back_edges" not in src


def test_regalloc_no_spill_for_small_programs():
    prog = parse_asm("""
        mov r0, 1
        mov r1, 2
        L1: add r2, r0, r1
        add r0, r2, r1
        exit
    """, name="tiny")
    res = allocate_registers(prog, maxregcount=8)
    assert not res.spilled
    assert res.regs_per_thread == 3
    assert res.spill_loads == res.spill_stores == 0


# ----------------------------------------------------------------- registry

def test_default_names_exclude_traced_even_after_loading():
    get_workload("traced_matmul")  # force the lazy suite in
    default = workload_names()
    assert len(default) == 14
    assert not any(n.startswith("traced_") for n in default)
    assert set(workload_names("traced")) == set(TRACED_NAMES)
    assert set(TRACED_NAMES) <= set(workload_names("all"))


def test_register_workload_collision_raises():
    with pytest.raises(ValueError):
        register_workload(WORKLOADS["srad"])
    register_workload(WORKLOADS["srad"], replace=True)  # explicit is fine


def test_sweep_jobs_suite_selector():
    from benchmarks.sweep_subset import sweep_jobs

    default_names = {n for n, _ in sweep_jobs()}
    assert default_names == set(workload_names())
    traced_names = {n for n, _ in sweep_jobs(suite="traced")}
    assert traced_names == set(TRACED_NAMES)


def test_orchestrator_runs_traced_jobs():
    from repro.serving.sweep import SimRunner

    runner = SimRunner(processes=1, disk_cache=False)
    cfg = design_config("LTRF", table2_config=7, num_warps=4)
    res = runner.sim("traced_rmsnorm", cfg)
    assert res == simulate(get_workload("traced_rmsnorm"), cfg)
    assert runner.stats["computed"] == 1
    runner.sim("traced_rmsnorm", cfg)
    assert runner.stats["memo_hits"] == 1


# ------------------------------------------------------------- subprocess env

def test_lift_in_subprocess_via_env_helper():
    """Tracing in a child process must pin JAX_PLATFORMS or it can hang on
    TPU-less-libtpu hosts; jax_subprocess_env is the one sanctioned recipe."""
    script = ("from repro.workloads import get_workload; "
              "w = get_workload('traced_rmsnorm'); "
              "print('LIFT_OK', w.regs_per_thread)")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=jax_subprocess_env())
    assert "LIFT_OK" in r.stdout, r.stdout + r.stderr
