#!/usr/bin/env python3
"""Trace one batch cell's window and attribute its device time to the
program's spans.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--genes N --samples L] [--solves K] [--keep DIR]

Set-up and window run as in bench/run.py, the window traced; the trace is
then read by bench/lib/flows.py, which follows its flow ids from every
device program back to the span that dispatched it.  The last line of
standard output is one JSON object, per device and solve where it is a
time: the device time of each span's programs (`attribution_ms`: the
Pallas kernels apart, then `repro.*` and `bench.*` spans, `unlinked`,
`none`); the host wall time inside each span (`host_ms`, nested spans
included); the span and counter readings, and `nonkernel_device_ms.batch`
read from the same trace; the executor's counts over the window; how many
device programs link to a Python thread; and the longest idle gaps
labelled by program span.  --genes/--samples shrink the configuration,
--solves K runs K one-solve windows instead of --seconds, and --keep
copies the trace into DIR (how tests/bench/data/v5e_spans was recorded).
Needs the chip, like bench/run.py.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.lib import chip, flows, spec, trace  # noqa: E402
from bench.lib.record import Record  # noqa: E402

DEVICE_SPANS = {"prepare_device_ms.batch": "repro.prepare",
                "scatter_device_ms.batch": "repro.sink.scatter",
                "symmetrize_device_ms.batch": "repro.sink.symmetrize"}


def _executor_stats() -> dict:
    try:
        from repro.core.api import executor_stats
    except ImportError:         # a program without the counters
        return {}
    return executor_stats()


def reduce(fv: flows.FlowView, solves: int) -> dict:
    """What the trace of `solves` solves says, per device and solve."""
    devs = list(fv.view.devices)

    def per(ns):
        return ns * 1e-6 / len(devs) / solves

    parts: dict = {}
    for d in devs:
        for label, ns in flows.attribute(fv, d).items():
            parts[label] = parts.get(label, 0) + ns
    rec = Record(kind="solves", view=fv.view, peaks=None,
                 operand_dtype="float32", solves=solves)
    metrics = {name: per(flows.span_device_ns(fv, span))
               for name, span in DEVICE_SPANS.items()}
    metrics["launch_host_ms.batch"] = (
        flows.span_host_ns(fv, "repro.launch") * 1e-6 / solves)
    for name in ("mesh_programs_built.batch", "nonkernel_device_ms.batch"):
        metrics[name] = spec.load_reader(name)(rec)
    linked, modules = flows.module_links(fv)
    return {"metrics": metrics,
            "attribution_ms": {k: per(v) for k, v in sorted(parts.items())},
            "host_ms": {name: flows.span_host_ns(fv, name) * 1e-6 / solves
                        for name in sorted({s.name for s in fv.spans})},
            "busy_ms": per(sum(trace.busy_ns(fv.view, d) for d in devs)),
            "modules": modules, "linked": linked,
            "program_idle_gaps": flows.program_idle_gaps(fv),
            "idle_gaps": trace.idle_gaps(fv.view)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--genes", type=int)
    ap.add_argument("--samples", type=int)
    ap.add_argument("--solves", type=int)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)

    import jax
    cell = spec.resolve(args.workload, ROOT)
    sizes = {k: v for k, v in (("n_genes", args.genes),
                               ("n_samples", args.samples)) if v}
    cell = dataclasses.replace(cell, config=dict(cell.config, **sizes))
    chip.enable_compile_cache(ROOT)
    devices = chip.require_chip(cell.chips)
    driver = run.make_driver(cell, args.seed, devices, args.seconds, {})
    driver.warm()
    setup_s = time.perf_counter() - T_START

    tdir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        before = _executor_stats()
        jax.profiler.start_trace(tdir)
        if args.solves:
            for _ in range(args.solves):
                driver.window(0.0)          # one solve a window
        else:
            driver.window(args.seconds)
        jax.profiler.stop_trace()
        after = _executor_stats()
        solves = args.solves or driver.solves
        path = trace.find_xplane(tdir)
        t0 = time.perf_counter()
        fv = flows.load(path)
        if not fv.view.devices:
            raise chip.NoChip(f"bench: no TPU device plane in {path}")
        out = {"workload": cell.name, "seed": args.seed, "solves": solves,
               "setup_s": setup_s, **driver.end_to_end(),
               "window_s": fv.view.window_s,
               "busy_s": trace.busy_s(fv.view),
               "executor": {k: after[k] - before[k] for k in after},
               **reduce(fv, solves),
               "reduce_s": time.perf_counter() - t0}
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(args.keep) / Path(path).name)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
