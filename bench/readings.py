#!/usr/bin/env python3
"""The readings a cell's limits are set from, one JSON line per seed.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3

For each seed: the numbers the check compares, for the program (its first
steps, no window) and for the control (the reference computed one
precision below the configuration's, in the program's place).  With
``--fault <name>`` the program runs with that fault of `bench.faults`
planted, and the control is not read.  The
benchmark's own runs never run this; ``PERF.md`` records what it read and
the limits set from it.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--fault", help="a fault of bench.faults to plant")
    args = ap.parse_args(argv)

    from bench import cells, faults, harness
    from bench.run import prepare

    got, err = prepare(args.workload)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    cell, devices = got
    driver = cells.load_driver(cell.driver)
    if args.fault:
        faults.plant(cell.driver, args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.Run(cell, seed, 0.0, False, devices, t)
        out = driver.readings(r, control=not args.fault)
        print(json.dumps({"seed": seed, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
