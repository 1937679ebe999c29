"""Paper reproductions and the analytic tier's calibration fit.

    python -m benchmarks.run [paper] [--strict] [--suite synth|traced|all]
    python -m benchmarks.run calibrate [--suite synth|traced|all]

``paper`` (the default) prints one artifact per paper table/figure as
``name,metric,value`` CSV rows (plus per-workload detail rows); heavy
artifacts are cached under experiments/paper/.  ``--strict`` turns a
degraded sweep (failed or quarantined design points — see
`repro.serving.sweep`) from a stderr warning into a non-zero exit.

``calibrate`` re-fits the analytical tier's exposure coefficients against
engine runs of the tracked sweep domain (`benchmarks.sweep_subset`) and
saves them in the sim cache, where `SimRunner` picks them up; it prints
the fitted calibration.

Neither mode measures speed: the benchmark is ``python3 bench/run.py``.
"""
from __future__ import annotations

import json
import sys
import time


def _emit(name: str, rows) -> None:
    if isinstance(rows, dict):
        rows = [rows]
    for row in rows:
        flat = ",".join(f"{k}={_fmt(v)}" for k, v in row.items())
        print(f"{name},{flat}")


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, dict):
        return "|".join(f"{k}:{_fmt(x)}" for k, x in v.items())
    return v


def bench_paper_figures(strict: bool = False) -> None:
    from benchmarks.paper_figs import ALL_FIGS, sweep_health
    for name, fn in ALL_FIGS.items():
        t0 = time.time()
        rows = fn()
        _emit(name, rows)
        print(f"# {name}: {time.time() - t0:.1f}s", file=sys.stderr)
    health = sweep_health()
    if not health["ok"]:
        # degraded sweep: some design points failed/quarantined (see
        # repro.serving.sweep) — the full story (failure records keyed by
        # run_id + the runner's metrics snapshot) goes through the metrics
        # layer rather than an eyeball-only print
        snap = health["metrics"]
        print(f"# WARNING: sweep degraded [run_id {health['run_id']}]: "
              f"{len(health['missing_points'])} missing point(s), "
              f"jobs_failed={snap.get('sweep_jobs_failed', 0)} "
              f"quarantined={snap.get('sweep_quarantined_total', 0)} "
              f"retries={snap.get('sweep_retries_total', 0)}",
              file=sys.stderr)
        for mp in health["missing_points"]:
            print(f"#   missing: {mp['job']} [{mp['kind']}] {mp['detail']}",
                  file=sys.stderr)
        if strict:
            sys.exit(f"# --strict: refusing to pass a degraded sweep "
                     f"(run_id {health['run_id']})")


def calibrate(jobs=None, runner=None, suite: str | None = None):
    """Fit the analytic tier's calibration on engine runs of ``jobs``
    (default: the tracked sweep domain of ``suite``), save it where
    ``runner`` (default: a `SimRunner` on the shared sim cache) reads it,
    print it, and return it."""
    from benchmarks.sweep_subset import sweep_jobs
    from repro.serving.sweep import CALIBRATION_KEY, SimRunner
    from repro.sim.analytic import (analytic_supported, calibration_to_dict,
                                    fit_calibration, save_calibration)
    from repro.workloads import get_workload

    runner = runner or SimRunner()
    if jobs is None:
        jobs = sweep_jobs(suite=suite)
    jobs = [j for j in dict.fromkeys(jobs) if analytic_supported(j[1])]
    runner.prefill(jobs, tier="engine")
    samples = [(get_workload(n), cfg, runner.sim(n, cfg).cycles)
               for n, cfg in jobs]
    calib = fit_calibration(samples)
    path = runner.store.path(CALIBRATION_KEY)
    save_calibration(calib, path)
    print(f"# wrote {path}", file=sys.stderr)
    print(json.dumps(calibration_to_dict(calib), indent=1))
    return calib


def main() -> None:
    args = sys.argv[1:]
    strict = "--strict" in args
    if strict:
        # fail the process (CI job) when any sweep is degraded — failed or
        # quarantined design points — instead of only warning on stderr
        args = [a for a in args if a != "--strict"]
    suite = None
    if "--suite" in args:
        i = args.index("--suite")
        if i + 1 >= len(args):
            sys.exit("--suite requires a value (synth|traced|all)")
        suite = args[i + 1]
        if suite not in ("synth", "traced", "all"):
            sys.exit(f"unknown suite {suite!r} (expected synth|traced|all)")
        del args[i:i + 2]
    mode = args[0] if args else "paper"
    if mode == "paper":
        if suite:
            # run the figure set over another workload suite (e.g. the
            # lifted real kernels: --suite traced); artifacts gain a suffix
            from benchmarks import paper_figs
            paper_figs.set_suite(suite)
        bench_paper_figures(strict=strict)
    elif mode == "calibrate":
        calibrate(suite=suite)
    else:
        sys.exit(f"unknown mode {mode!r} (expected paper|calibrate)")


if __name__ == "__main__":
    main()
