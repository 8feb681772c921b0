#!/usr/bin/env python3
"""Thistle's on-chip benchmark: one run of one cell.

    python benchmarks/chip/run.py --workload marco768.uniform --seed 7 \\
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; everything else is found by those names under this directory
(see ``harness/spec.py``). The run holds one process on the cell's chips.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--rehearse`` runs a tiny corpus on the CPU instead
(never used for a measurement).

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``checks`` comes last, each compared number with its limit.
The same checks are the last lines on stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run, for testing the harness only")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from harness.spec import Cell
    cell = Cell(args.workload)
    import jax
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < cell.chips):
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from harness.bench import enable_cache, run_cell
    cache = enable_cache()
    print(f"{args.workload} seed {args.seed} | {devs[0].platform} "
          f"{devs[0].device_kind} x{len(devs)} | jax {jax.__version__} | "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   rehearse=args.rehearse)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
