#!/usr/bin/env python3
"""Faults planted under the hybrid train cell's timed path, and their
readings at the cell's size.

The train faults of `bench.faults` do not fit a cell of one row and a tied
head: half the batch of one row is no row, and there is no ``lm_head`` to
move.  These are the same three kinds for it, each wrapping the train step
that ``runtime.train_step.build_train_step`` returns: the second half of
the step's positions dropped, the tied embedding's update doubled, and the
state left unchanged.  ``plant(name)`` replaces the program's function for
the rest of the process; the tests plant them at a tiny size, and

    python3 bench/faults_hybrid.py --workload <cell> --seeds 1,2,3 --fault <name>

reads them at the cell's size, one JSON line per seed, as
``bench/readings.py`` reads the program and the control.  The benchmark's
own runs never plant one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.faults import _unchanged  # noqa: E402


def _half_positions(step):
    def half(state, batch):
        return step(state, {k: v[:, : v.shape[1] // 2]
                            for k, v in batch.items()})
    return half


def _embedding_moved_double(step):
    import jax.numpy as jnp

    def double(state, batch):
        old = state["params"]["embed"].astype(jnp.float32)
        new, metrics = step(state, batch)
        p = new["params"]
        moved = old + 2 * (p["embed"].astype(jnp.float32) - old)
        p["embed"] = moved.astype(p["embed"].dtype)
        return new, metrics
    return double


FAULTS = {"state_unchanged": _unchanged, "half_positions": _half_positions,
          "embedding_moved_double": _embedding_moved_double}


def plant(name: str, setattr=setattr) -> None:
    """Break the train step underneath the benchmark with one named fault.
    ``setattr`` may be a test's ``monkeypatch.setattr``, which undoes it."""
    from repro.runtime import train_step as mod

    real, wrap = mod.build_train_step, FAULTS[name]
    setattr(mod, "build_train_step", lambda *a, **k: wrap(real(*a, **k)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    args = ap.parse_args(argv)

    from bench import cells, harness
    from bench.run import prepare

    got, err = prepare(args.workload)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    cell, devices = got
    driver = cells.load_driver(cell.driver)
    plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.Run(cell, seed, 0.0, False, devices, t)
        out = driver.readings(r, control=False)
        print(json.dumps({"seed": seed, "fault": args.fault, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
