"""Faults planted under the timed path, to show that the check catches them.

Each fault wraps a piece of the program the benchmark drives: the train
step that ``runtime.train_step.build_train_step`` returns, or the outcomes
of ``sim.batch.run_batch``.  ``plant(kind, name)`` replaces the program's
function for the rest of the process; the tests plant them at a tiny size,
and ``bench/readings.py --fault`` reads them at the cell's size.  The
benchmark's own runs never plant one.
"""
from __future__ import annotations

import dataclasses


def _unchanged(step):
    def frozen(state, batch):
        return state, step(state, batch)[1]
    return frozen


def _half_batch(step):
    def half(state, batch):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return half


def _head_moved_double(step):
    import jax.numpy as jnp

    def double(state, batch):
        old = state["params"]["lm_head"].astype(jnp.float32)
        new, metrics = step(state, batch)
        p = new["params"]
        moved = old + 2 * (p["lm_head"].astype(jnp.float32) - old)
        p["lm_head"] = moved.astype(p["lm_head"].dtype)
        return new, metrics
    return double


def _answer_altered(jobs, outs):
    from repro.sim import SimResult

    if isinstance(outs[0], SimResult):
        outs[0] = dataclasses.replace(outs[0], cycles=outs[0].cycles + 1)
    return outs


def _half_left_out(jobs, outs):
    return outs[: len(outs) // 2] + [None] * (len(outs) - len(outs) // 2)


def _run_unchanged(jobs, outs):
    from repro.sim import SimResult

    return [dataclasses.replace(o, cycles=0, instructions=0,
                                cycle_breakdown={})
            if isinstance(o, SimResult) else o for o in outs]


TRAIN = {"state_unchanged": _unchanged, "half_batch": _half_batch,
         "leaf_moved_double": _head_moved_double}
SIM = {"answer_altered": _answer_altered, "half_left_out": _half_left_out,
       "state_unchanged": _run_unchanged}


def plant(kind: str, name: str, setattr=setattr) -> None:
    """Break the program underneath the benchmark with one named fault.
    ``setattr`` may be a test's ``monkeypatch.setattr``, which undoes it."""
    if kind == "train":
        from repro.runtime import train_step as mod

        real, wrap = mod.build_train_step, TRAIN[name]
        setattr(mod, "build_train_step",
                lambda *a, **k: wrap(real(*a, **k)))
    elif kind == "sim":
        from repro.sim import batch as mod

        real, wrap = mod.run_batch, SIM[name]
        setattr(mod, "run_batch",
                lambda jobs, **k: wrap(jobs, real(jobs, **k)))
    else:
        raise ValueError(f"no faults for driver {kind!r}")
