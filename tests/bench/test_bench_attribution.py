"""The reductions of a traced window to the program's own names: device
seconds per model scope from leaf operations, and idle seconds per program
span on any host thread; the scopes the program's train step carries; and
one traced run of each cell through the reductions on the CPU at a tiny
size."""
from __future__ import annotations

import dataclasses
import io
from types import SimpleNamespace as NS

import pytest

from bench import attribution as A

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(attn))/mul"}
  %dot.2 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %a), metadata={op_name="jit(step)/adamw/dot_general"}
  %while.3 = (f32[8]{0}, s32[]) while((f32[8]{0}, s32[]) %t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(head_loss)/while"}
  ROOT %add.4 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %a), metadata={op_name="jit(step)/transpose(jvp())/add"}
}
"""


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return NS(name=name, events=events)


def _profile():
    """A window 0..1000 ns opened by a watching thread.  The chip runs a
    loop over 100..600 holding fusion.1 (100..300) and dot.2 (300..400),
    then an operation the HLO lacks (600..700) and add.4 (800..900); the
    main thread is in repro.sim.launch over 50..750, with repro.sim.extract
    inside it over 690..750, then in repro.sim.encode."""
    host = NS(name="/host:CPU", lines=[
        _line("watcher", [_ev("bench.traced_slice", 0, 1000)]),
        _line("python", [_ev("repro.sim.launch", 50, 700),
                         _ev("repro.sim.extract", 690, 60),
                         _ev("PjitFunction(_run_jax)", 60, 10),
                         _ev("repro.sim.encode", 760, 230)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Ops", [_ev("%while.3 = (f32[8]) while(%t)", 100, 500),
                          _ev("%fusion.1 = f32[8]{0} fusion(%a)", 100, 200),
                          _ev("%dot.2", 300, 100),
                          _ev("%copy.9 = f32[8] copy(%a)", 600, 100),
                          _ev("%add.4", 800, 100)]),
    ])
    return NS(planes=[host, dev])


def test_scope_of_names_the_outermost_scope_through_wrappers():
    assert A.scope_of("jit(step)/transpose(jvp(head_loss))/dot_general") \
        == "head_loss"
    assert A.scope_of("jit(step)/jvp()/while/body/attn/closed_call/"
                      "checkpoint/bhqd,bhdk->bhqk/dot_general") == "attn"
    assert A.scope_of("jit(step)/norm/attn/add") == "norm"
    assert A.scope_of("jit(step)/transpose(jvp())/while/body/add") == "other"
    assert A.scope_of("state['layers']['attn']['wq']") == "other"


def test_op_scopes_reads_opcode_and_scope_of_each_instruction():
    ops = A.op_scopes(HLO)
    assert ops["fusion.1"] == ("fusion", "attn")
    assert ops["dot.2"] == ("dot", "adamw")
    assert ops["while.3"] == ("while", "head_loss")
    assert ops["add.4"] == ("add", "other")
    assert ops["m"] == ("multiply", "attn")
    assert ops["a"] == ("parameter", "other")


def test_scopes_sum_leaf_operations_only():
    got = A.scopes(_profile(), "bench.traced_slice", A.op_scopes(HLO))
    sec = got["seconds"]
    assert set(sec) == {*A.SCOPES, "other"}
    # the loop (500 ns, over its children) is not counted again
    assert sec["attn"] == pytest.approx(200e-9)
    assert sec["adamw"] == pytest.approx(100e-9)
    assert sec["head_loss"] == 0
    # an op the HLO lacks and an op with no scope both go to other
    assert sec["other"] == pytest.approx(200e-9)
    assert dict(got["other_ops"]) == pytest.approx({"copy.9": 100e-9,
                                                    "add.4": 100e-9})
    assert got["unknown_s"] == pytest.approx(100e-9)


def test_an_operation_the_hlo_lacks_takes_its_opcode_from_its_text():
    assert A._lookup({}, "%while.7 = (f32[]) while(%t)") \
        == ("while", "other", False)
    assert A._lookup({"fusion.5": ("fusion", "mlp")}, "%fusion.5") \
        == ("fusion", "mlp", True)


def test_idle_by_span_names_gaps_by_spans_on_any_thread():
    got = A.idle_by_span(_profile(), "bench.traced_slice")
    # gaps 0..100 (middle 50: the launch span starts there), 700..800
    # (middle 750: extract's end, inside the launch) and 900..1000
    # (encode); 0..50 of the first gap lies before every span but the
    # gap is named by its middle
    assert got == pytest.approx({"repro.sim.launch": 100e-9,
                                 "repro.sim.extract": 100e-9,
                                 "repro.sim.encode": 100e-9})


def test_idle_by_span_falls_back_to_the_window_name():
    p = _profile()
    p.planes[0].lines[1].events = []
    got = A.idle_by_span(p, "bench.traced_slice")
    assert got == pytest.approx({"bench.traced_slice": 300e-9})


def test_no_chip_reads_no_idle_and_no_scope_time():
    p = _profile()
    p.planes.pop()
    assert A.idle_by_span(p, "bench.traced_slice") == {}
    assert sum(A.scopes(p, "bench.traced_slice", {})["seconds"].values()) \
        == 0


def test_window_span_must_be_there_once():
    with pytest.raises(RuntimeError):
        A.scopes(_profile(), "bench.window", {})


def test_traced_train_run_through_the_reductions(tiny_train, cpu):
    result, extra = A.attribute(tiny_train, 7, 0.5, cpu, log=io.StringIO())
    assert result["correct"], result
    assert extra["steps"] > 0
    assert set(extra["ms_per_step"]) == {*A.SCOPES, "other"}
    assert extra["work"]["train_tokens_per_s"] > 0
    assert extra["idle_by_span"] == {}      # no TPU plane on the CPU
    assert "run_stats" not in extra
    assert extra["hlo_ops"] > extra["hlo_scoped_ops"] > 0
    assert extra["unknown_s"] == 0


def test_traced_sim_run_through_the_reductions(tiny_sim, cpu, monkeypatch):
    from bench.drivers import sim

    monkeypatch.setattr(sim, "TRACE_SLICE", (0.0, 0.05))
    result, extra = A.attribute(tiny_sim, 7, 0.5, cpu, log=io.StringIO())
    assert result["correct"], result
    stats = extra["run_stats"]
    assert stats["ticks"] > 0 and stats["launches"] > 0
    assert 0 < stats["lane_ticks"] <= stats["lane_slots"]
    assert 0 < extra["live_lanes"] <= 100
    host = stats["encode_s"] + stats["build_s"] + stats["extract_s"]
    assert extra["batch_host_share"] == pytest.approx(
        100 * host / extra["window_s"])
    assert extra["work"]["sim_inst_per_s"] > 0
    assert "ms_per_step" not in extra


def _step_hlo(cfg) -> str:
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train_step import build_train_step, make_train_state

    rules = default_rules(make_host_mesh(devices=jax.devices("cpu")[:1]))
    state, _ = make_train_state(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    step = jax.jit(build_train_step(cfg, rules))
    return step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("arch_id,remat", [
    ("qwen3-0.6b", "full"), ("qwen3-0.6b", "none"),
    ("granite-moe-3b-a800m", "none")])
def test_every_matmul_of_the_programs_step_is_scoped(arch_id, remat):
    """Forward, remat recompute and backward alike: every dot of the
    compiled step lies under one of the six scopes, and each scope is
    there."""
    from repro.configs import get_smoke

    cfg = dataclasses.replace(get_smoke(arch_id), remat=remat)
    ops = A.op_scopes(_step_hlo(cfg))
    assert {s for _, s in ops.values()} == {*A.SCOPES, A.OTHER}
    dots = {n: s for n, (op, s) in ops.items() if op == "dot"}
    assert dots
    assert [n for n, s in dots.items() if s == A.OTHER] == []
