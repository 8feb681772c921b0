#!/usr/bin/env python3
"""Spread of each end-to-end metric over repeated runs of one cell, and
the bound it suggests.

    python benchmarks/chip/spread.py SET_A.jsonl SET_B.jsonl

Each file holds the result lines (run.py's last stdout line) of one set of
runs, one per seed. A spread is the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) as a share of the
median; per metric it prints each set's median and spread, the wider
spread, and five times it clamped to [1%, 25%] -- the bound the
benchmark's rules ask for -- and how far the second set's median lies from
the first's.
"""
import json
import statistics
import sys


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values)), statistics.median(values)


def main(paths) -> int:
    sets = []
    for p in paths:
        with open(p) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        rows = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            rows.append((spread(vals), vals) if len(vals) >= 2 else None)
        got = [r for r in rows if r]
        if not got:
            continue
        widest = max(sp for (sp, _), _ in got)
        meds = [med for (_, med), _ in got]
        drift = (meds[-1] - meds[0]) / abs(meds[0]) if len(meds) > 1 else 0.0
        print(json.dumps({
            "metric": name,
            "sets": [{"median": med, "spread": sp, "values": v}
                     for (sp, med), v in got],
            "widest_spread": widest,
            "bound_5x": min(0.25, max(0.01, 5 * widest)),
            "median_drift": drift}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
