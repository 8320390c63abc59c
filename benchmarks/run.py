"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and a trailing summary).

  table1  — artificial-data speedup vs sequential baseline   (paper Table I)
  table2  — real-dataset-shaped speedup                      (paper Table II)
  fig2    — scalability vs device count                      (paper Fig. 2)
  kernels — tile/kernel microbenchmarks + grid-savings       (paper SSIII-C)
  serving — plan-cache hit/miss + batched vs serial queries  (serving layer)
  streaming — incremental append vs cold rebuild, watch revalidation (live corpora)
  significance — replica-axis vs legacy batched p-values     (paper SSIV)
  robustness — recovery + CRC-checkpoint overhead            (fault harness)

Run: PYTHONPATH=src python -m benchmarks.run [--only table1,...]
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: table1,table2,fig2,"
                         "kernels,serving,streaming,significance,robustness")
    ap.add_argument("--json", default="",
                    help="append this run as one trajectory point to the "
                         "given BENCH_*.json file (see common.save_trajectory)")
    ap.add_argument("--label", default="",
                    help="label for the --json trajectory point")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import common
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")

    def want(name: str) -> bool:
        return only is None or name in only

    if want("table1"):
        from benchmarks import table1_artificial
        table1_artificial.run()
    if want("table2"):
        from benchmarks import table2_real
        table2_real.run()
    if want("fig2"):
        from benchmarks import fig2_scaling
        fig2_scaling.run()
    if want("kernels"):
        from benchmarks import kernels
        kernels.run()
    if want("serving"):
        from benchmarks import serving
        serving.run()
    if want("streaming"):
        from benchmarks import streaming
        streaming.run()
    if want("significance"):
        from benchmarks import significance
        significance.run()
    if want("robustness"):
        from benchmarks import robustness
        robustness.run()

    if args.json:
        path = common.save_trajectory(args.json, args.label or None)
        print(f"# trajectory point appended to {path}", file=sys.stderr)
    print(f"# {len(common.ROWS)} benchmark rows emitted", file=sys.stderr)


if __name__ == "__main__":
    main()
