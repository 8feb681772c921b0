#!/usr/bin/env python3
"""Read the compared numbers of the program, of the control and of a
planted fault, seed by seed, in one process: the readings that the check
limits are set from.

    python benchmarks/chip/calibrate.py --workload marco768.uniform \\
        --seconds 10 --seeds 11 12 13 [--fault quarter_lists]

Each seed is one run of the cell as run.py makes it (set-up, a window at
the cell's own load, the reference), plus the control: the reference scan
one precision lower (bf16 operands) put in the program's place and judged
by the same comparison and limits. ``--fault`` plants one of
``harness/faults.py``'s faults in the program for every seed. One JSON
line per seed, with the program's verdict and the control's. Needs a TPU,
like run.py (``--rehearse``: tiny CPU run).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax

    from harness import faults
    from harness.bench import enable_cache, run_cell
    from harness.spec import Cell

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    enable_cache()
    cell = Cell(args.workload)
    for seed in args.seeds:
        with faults.plant(args.fault) as proxy:
            out = run_cell(cell, seed, args.seconds, False,
                           time.perf_counter(), rehearse=args.rehearse,
                           proxy=proxy, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, "correct": out["correct"],
                          "metrics": out["metrics"],
                          "checks": {c: v["value"]
                                     for c, v in out["checks"].items()},
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
