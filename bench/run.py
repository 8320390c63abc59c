#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name from BENCHMARK.json (bench/lib/spec.py).  The run draws its data on
the chip from --seed, warms every program the window uses (set-up), runs
the window for --seconds, reads peak device memory, frees the program's
state and compares what the window produced with the float64 reference.
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the end-to-end metrics, or with
--trace 1 the per-layer metrics read from the profiler trace of the
window), `device`, with --trace 1 `breakdown`, and last `checks`, each
compared number beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import chip, reference, spec, trace  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402
from bench.lib.record import Record  # noqa: E402


def make_driver(cell: spec.Cell, seed: int, devices, seconds: float,
                program: dict):
    kind = cell.traffic["kind"]
    if kind == "solves":
        from bench.lib.solves import SolvesDriver
        return SolvesDriver(cell, seed, devices, corr=program.get("corr"))
    if kind == "search":
        from bench.lib.search import SearchDriver
        return SearchDriver(cell, seed, devices, seconds,
                            server=program.get("server"))
    raise ValueError(f"traffic {cell.traffic_name!r} has unknown kind "
                     f"{kind!r}")


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices, t_start: float, program: dict = None) -> dict:
    """One run of `cell` on `devices`: set-up, window, check.  `program`
    may replace the program's entry points (tests plant faults there)."""
    import jax
    counter = chip.CompileCounter()
    driver = make_driver(cell, seed, devices, seconds, program or {})
    driver.warm()
    setup_s = time.perf_counter() - t_start
    chip.say(f"set-up {setup_s:.3f} s; window of {seconds} s")

    before = counter.snapshot()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(tdir)
        driver.window(seconds)
        if traced:
            jax.profiler.stop_trace()
        compiles = chip.delta(counter.snapshot(), before)
        device = chip.device_info(devices)
        on_chip = device["platform"] == "tpu"
        rec = Record(kind=cell.traffic["kind"], view=None,
                     compiles=compiles,
                     peaks=peaks(device["kind"]) if on_chip else None,
                     operand_dtype=cell.config["dtype"])
        driver.record(rec)
        if traced:
            rec.view = trace.load(trace.find_xplane(tdir))
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    e2e = {**driver.end_to_end(), "setup_s": setup_s}
    chip.say(f"window: {e2e}; compiles {compiles}")

    got = driver.free()
    gc.collect()
    readings = driver.readings(got)
    limits = cell.traffic["limits"]
    counts = driver.counts(readings)
    correct = (reference.within(readings, limits) and counts["failed"] == 0)

    if traced:
        metrics = spec.read_per_layer(cell, rec)
        device["busy_s"] = trace.busy_s(rec.view)
        device["window_s"] = rec.view.window_s
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": e2e[name], "unit": unit}
                   for name, unit in units.items()}
    result = {"correct": bool(correct), **counts, "metrics": metrics,
              "device": device}
    if traced:
        result["breakdown"] = trace.breakdown(rec.view)
    result["checks"] = reference.checks_line(readings, limits)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload, ROOT)
    chip.enable_compile_cache(ROOT)
    devices = chip.require_chip(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
