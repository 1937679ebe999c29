"""Batches of register-file simulations through the program's batch engine.

Each request is one ``repro.sim.batch.run_batch(jobs, fallback=False)``
call from one closed-loop client.  The traffic file lists the deck of
requests, each one design point of the configuration over a list of its
kernels.  The window runs whole passes over the deck, every pass in an
order drawn from the seed (the order of the requests and of the jobs in
each), until ``--seconds`` have passed: every seed does the same work, in
another order.

Set-up compiles every shape bucket the deck uses by running each request
once with every job under a one-cycle budget (``SimConfig.max_cycles``), so
that nothing compiles in the window; that also encodes each job's plan.

The check compares every job the window completed, every ``SimResult``
counter and all seven categories of its cycle breakdown, with the
configuration's reference simulator (``bench/refs/ltrfsim``), run in
worker processes that never import JAX.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
import random
import time

STAT_KEYS = ("run_s", "ticks", "launches", "compiles")
# The fused loop runs about a thousand small operations a tick, over a
# million a second, and the profiler records each (with or without the
# ``tpu_trace_mode`` ``TRACE_ONLY_XLA``): a whole window's trace overflows
# its buffers and takes minutes to stop and to read.  A traced run records
# a short slice of the steady loop instead, inside the first request
# (every request runs for 15 s or more).
TRACE_SLICE = (5.0, 0.25)
REFERENCE_WORKERS = 8


def deck(traffic: dict) -> list[list[tuple[str, str]]]:
    """The traffic's requests as lists of (kernel, design point) jobs."""
    return [[(k, req["design_point"]) for k in req["kernels"]]
            for req in traffic["requests"]]


def passes(traffic: dict, seed: int):
    """Endless passes over the deck, each in its own seeded order."""
    rng = random.Random(seed)
    requests = deck(traffic)
    while True:
        order = [list(req) for req in requests]
        rng.shuffle(order)
        for req in order:
            rng.shuffle(req)
        yield order


def reference_counters(args) -> dict:
    """Worker: the reference simulator's counters for one job.  With
    ``lower`` set, every float of the job (latency multiplier, L1 hit
    rates) enters as a float32 and the simulation computes in float32: the
    control, one precision below the configuration's float64."""
    import numpy as np

    from bench.refs import ltrfsim

    spec, fields, lower = args
    w = ltrfsim.build_workload(spec)
    if lower:
        fields = {k: np.float32(v) if isinstance(v, float) else v
                  for k, v in fields.items()}
        w = dataclasses.replace(w, l1_hit=np.float32(w.l1_hit))
    res = ltrfsim.golden_simulate(w, ltrfsim.SimConfig(**fields))
    return {f.name: getattr(res, f.name) for f in dataclasses.fields(res)
            if f.name not in ("design", "workload")}


def reference(config: dict, keys, lower: bool = False,
              workers: int = REFERENCE_WORKERS) -> dict:
    """The reference's counters for each (kernel, design point) key."""
    specs = {k["name"]: k for k in config["kernels"]}
    args = [(specs[k], config["design_points"][dp], lower) for k, dp in keys]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(workers, len(args), os.cpu_count() or 1)) as pool:
        got = pool.map(reference_counters, args)
        pool.close()
        pool.join()
    return dict(zip(keys, got))


def mismatched(got: dict, want: dict) -> int:
    """Jobs whose counters differ from the reference's in any field."""
    return sum(got[key] != want[key] for key in want)


def readings(r, control: bool = True) -> dict:
    """The control's reading on the deck: the reference computed in
    float32, in the program's place, against the float64 reference.  Every
    seed runs the same jobs, so every seed reads the same.  The program's
    own reading is its runs' ``mismatched_jobs``."""
    keys = sorted({job for req in deck(r.cell.traffic) for job in req})
    want = reference(r.cell.config, keys)
    ctl = reference(r.cell.config, keys, lower=True)
    return {"control": {"mismatched_jobs": mismatched(ctl, want),
                        "jobs": len(keys)}}


def run(r) -> dict:
    from repro.sim import SimConfig, SimResult
    from repro.sim.batch import RUN_STATS, run_batch
    from repro.workloads import get_workload

    config, traffic = r.cell.config, r.cell.traffic
    points = {name: SimConfig(**f)
              for name, f in config["design_points"].items()}
    kernels = {k["name"]: get_workload(k["name"]) for k in config["kernels"]}

    for req in deck(traffic):
        run_batch([(kernels[k], dataclasses.replace(points[dp], max_cycles=1))
                   for k, dp in req], fallback=False)

    done, request_s, n_passes, n_requests = [], 0.0, 0, 0
    stats0 = {k: RUN_STATS[k] for k in STAT_KEYS}
    with r.window():
        t0 = time.perf_counter()
        for order in passes(traffic, r.seed):
            for req in order:
                with r.span("bench.request"):
                    t = time.perf_counter()
                    outs = run_batch([(kernels[k], points[dp])
                                      for k, dp in req], fallback=False)
                    request_s += time.perf_counter() - t
                n_requests += 1
                done.extend(zip(req, outs))
            n_passes += 1
            if time.perf_counter() - t0 >= r.seconds:
                break
    stats = {k: RUN_STATS[k] - stats0[k] for k in STAT_KEYS}
    ok = [(key, o) for key, o in done if isinstance(o, SimResult)]
    instructions = sum(o.instructions for _, o in ok)

    want = reference(config, sorted({key for key, _ in ok})) if ok else {}
    bad = sum(any(getattr(o, f, None) != v for f, v in want[key].items())
              for key, o in ok)
    return {"metrics": {"sim_inst_per_s": instructions / r.window_s},
            "counters": {"instructions": instructions,
                         "requests": n_requests, "request_s": request_s,
                         "passes": n_passes, **stats},
            "attempted": len(done), "failed": len(done) - len(ok),
            "compared": [("mismatched_jobs", bad,
                          r.cell.limits["mismatched_jobs"])]}
