"""Serving driver: continuous batching with paged KV on the host mesh.

``python -m repro.launch.serve --arch tinyllama-1.1b --requests 16``

Wraps the ServingEngine (two-level request scheduler + the paper's Address
Allocation Unit for KV pages) with a synthetic request generator and reports
throughput/fairness stats.  On a fleet the same engine runs with the
production mesh shardings (see dryrun.py's decode cells for the compiled
evidence).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_arch, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServeConfig, ServingEngine


def serve(arch_id: str, smoke: bool = True, n_requests: int = 16,
          max_new: int = 12, seed: int = 0, active_slots: int = 4,
          total_pages: int = 32, max_len: int = 128) -> dict:
    cfg = get_smoke(arch_id) if smoke else get_arch(arch_id)
    rng = np.random.default_rng(seed)
    engine = ServingEngine(cfg, sc=ServeConfig(
        max_len=max_len, active_slots=active_slots, total_pages=total_pages))
    reqs = []
    for _ in range(n_requests):
        prompt = rng.integers(0, cfg.vocab, rng.integers(1, 8)).tolist()
        reqs.append(engine.submit(prompt, max_new_tokens=int(
            rng.integers(2, max_new + 1))))
    t0 = time.time()
    out = engine.run()
    dt = time.time() - t0
    tokens = sum(len(v) for v in out.values())
    engine.aau.check_invariants()
    return {
        "requests": n_requests,
        "completed": len(engine.sched.finished),
        "tokens": tokens,
        "tok_per_s": tokens / max(dt, 1e-9),
        "preemptions": engine.sched.preemptions,
        "pages_leaked": engine.aau.used_count,
        "wall_s": dt,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    stats = serve(args.arch, smoke=not args.full, n_requests=args.requests)
    print(", ".join(f"{k}={v if not isinstance(v, float) else round(v, 2)}"
                    for k, v in stats.items()))


if __name__ == "__main__":
    main()
