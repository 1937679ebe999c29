#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (weights and inputs from ``--seed``; every shape it will
use compiled, from JAX's persistent cache at ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another), measures for
``--seconds``, checks what the timed path produced against the
configuration's plain reference, and prints one JSON object as the last
line of standard output.  With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and they are its per-layer metrics.

Exits non-zero, printing no result, when the program is not beside the
benchmark, when JAX finds no TPU or fewer chips than the cell asks for, or
when the chip is not in ``bench/peaks.json``.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def prepare(name: str):
    """The cell and the chips it runs on, or an error message: the program
    beside the benchmark, a TPU with enough chips, its peaks in the table,
    and JAX's persistent compilation cache in the checkout."""
    import os
    import tempfile

    from bench import cells

    try:
        cell = cells.resolve(name)
    except cells.UnknownName as e:
        return None, str(e)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return None, f"the program is not beside the benchmark ({src})"
    sys.path.insert(0, str(src))
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise: keep its
    # logs in this run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"no TPU: JAX found {devices[0].platform!r}"
    if len(devices) < cell.chips:
        return None, (f"{cell.name} needs {cell.chips} chips, JAX found "
                      f"{len(devices)}")
    try:
        cells.load_peaks(devices[0].device_kind)
    except cells.UnknownName as e:
        return None, str(e)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return (cell, devices[:cell.chips]), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell's name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    got, err = prepare(args.workload)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    cell, devices = got
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, started=STARTED)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
