"""Benchmark harness — one module per paper table/figure + system benches.

  bench_index      — Thistle's accuracy/runtime-vs-N figures (all engines)
  bench_throughput — the ">99% of time is SBERT" insert-pipeline split
  bench_serve      — production micro-batching latency (p50/p99)
  bench_kernels    — kernel agreement + oracle walltimes

``python -m benchmarks.run [--quick] [--json out.json]`` prints one CSV
stream (and dumps every suite's rows as JSON — the CI smoke artifact); the
roofline tables come from ``repro.launch.dryrun`` + ``repro.launch.roofline``
(they need the 512-device flag and live in their own processes).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: index,throughput,serve,kernels")
    ap.add_argument("--json", default=None,
                    help="dump every suite's returned rows to this path")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bench_index, bench_kernels, bench_serve, bench_throughput
    suites = {"index": bench_index.main, "throughput": bench_throughput.main,
              "serve": bench_serve.main, "kernels": bench_kernels.main}
    chosen = (args.only.split(",") if args.only else list(suites))
    failures = []
    results = {}
    for name in chosen:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            results[name] = suites[name](quick=args.quick)
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {args.json}")
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
