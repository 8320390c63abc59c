#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 4]

For every seed, in one process: the cell's set-up and a short window at
its own load, then the comparison of what the window produced with the
float64 reference (the program's reading).  For the control seeds also the
control: the reference put in the program's place one precision step down
(bench/lib/reference.py), compared in the same way on the same rows or
queries.  Each reading is printed as one JSON line.  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import chip, spec  # noqa: E402
from bench.run import make_driver  # noqa: E402


def readings(cell, seed: int, seconds: float, devices,
             control: bool) -> dict:
    driver = make_driver(cell, seed, devices, seconds, {})
    driver.warm()
    driver.window(seconds)
    got = driver.free()
    gc.collect()
    program = driver.readings(got)
    out = {"seed": seed, "program": program,
           "counts": driver.counts(program)}
    if control:
        out["control"] = driver.control_readings()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload, ROOT)
    chip.enable_compile_cache(ROOT)
    devices = chip.require_chip(cell.chips)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds + sorted(controls - set(seeds)):
        t0 = time.perf_counter()
        r = readings(cell, seed, args.seconds, devices, seed in controls)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps({"workload": cell.name, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
