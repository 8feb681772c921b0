#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, in one process.

    python benchmarks/chip/sweep.py --workload marco768.uniform --seed 5 \\
        --step-seconds 15

Loads the cell once, warms it as a run does, and times one full batch to
estimate capacity C. It then offers Poisson load at a ladder of rates
around C, each for ``--step-seconds``. A rate is sustained when every
query is answered and the backlog does not grow: the median latency of
the step's last quarter is at most twice that of its first quarter.
It prints one line per rate and, last, a JSON line with the highest rate
sustained and 4/5 of it, the rate a cell offers (its file's
``rate_qps``). Needs a TPU, like run.py (``--rehearse``: tiny CPU run).
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LADDER = (0.5, 0.7, 0.85, 1.0, 1.15, 1.3, 1.5)
SHARE = 0.8  # cells offer 4/5 of the highest sustained rate


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--step-seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from harness import bench, data, drive
    from harness.spec import Cell
    from repro.serve.async_engine import AsyncQueryEngine

    cell = Cell(args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep.py: only open-loop cells have an offered rate",
              file=sys.stderr)
        return 2
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    bench.enable_cache()
    k, f = int(cell.traffic["k"]), cell.front
    shape = bench.cell_shape(cell, args.rehearse)
    x = data.make_corpus(shape, args.seed)
    db = bench.make_db(cell, x)
    del x
    rng = np.random.default_rng(args.seed)
    warm = data.make_queries(shape, args.seed, data.topic_subs(
        shape, cell.traffic["topic"], f["max_batch"], rng), stream=0)
    bench.warm_up(db, cell, warm, k)
    t = time.perf_counter()
    jax.block_until_ready(db.query(warm, k=k))
    cap = f["max_batch"] / (time.perf_counter() - t)
    print(f"one batch of {f['max_batch']}: capacity estimate {cap:.1f} q/s",
          file=sys.stderr, flush=True)

    best, rows_out = 0.0, []
    for i, share in enumerate(LADDER):
        rate = cap * share
        n = data.n_arrivals(rate, args.step_seconds)
        arrivals = np.concatenate([[0.0], np.cumsum(
            data.exp_gaps(n, rate, rng))[:-1]])
        q = data.make_queries(shape, args.seed, data.topic_subs(
            shape, cell.traffic["topic"], n, rng), stream=10 + i)
        with AsyncQueryEngine(db, max_batch=f["max_batch"],
                              max_wait_ms=f["max_wait_ms"],
                              max_queue=f["max_queue"],
                              max_inflight=f["max_inflight"]) as eng:
            win = drive.open_loop(eng, q, arrivals, k)
        lat = (win.done - win.due) * 1e3
        ok = np.isfinite(lat).all()
        quarter = max(1, n // 4)
        first = float(np.median(lat[:quarter]))
        last = float(np.median(lat[-quarter:]))
        held = bool(ok and last <= 2 * first)
        row = {"rate_qps": rate, "queries": n, "p50_ms": float(np.median(lat)),
               "p95_ms": bench.nearest_rank(lat, 0.95),
               "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
               "sustained": held}
        rows_out.append(row)
        print(json.dumps(row), flush=True)
        if held:
            best = rate
        elif best:
            break
    print(json.dumps({"workload": args.workload, "highest_sustained_qps": best,
                      "cell_rate_qps": SHARE * best, "steps": rows_out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
