"""A new cell (its own traffic mix), an end-to-end metric only it reports
and a per-layer metric are added as new files and entries only, and run
without an edit to any file the benchmark has."""
import hashlib
import json
import os
import shutil
import time

from conftest import BENCH, ROOT
from harness.bench import run_cell
from harness.spec import Cell


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_new_cell_and_metric_from_files(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = digest(bench)

    (bench / "traffic" / "tiny-open.json").write_text(json.dumps(
        {"loop": "open", "arrivals": "poisson", "arrival_seed": 3,
         "topic": {"kind": "uniform"}, "k": 5}))
    (bench / "cells" / "marco768.tiny-open.json").write_text(json.dumps(
        {"max_batch": 8, "max_wait_ms": 1.0, "max_queue": 64,
         "max_inflight": 1, "rate_qps": 25.0, "warm_buckets": [1, 2, 4, 8],
         "limits": {"score_gap": 1e-4, "malformed": 0, "unanswered": 0,
                    "recall_miss": 0.5}}))
    (bench / "metrics" / "p50_ms.py").write_text(
        "import numpy as np\n"
        "def read(run):\n"
        "    return float(np.median(run.latencies_ms()))\n")
    (bench / "metrics" / "calls_per_query.py").write_text(
        "def read(run):\n"
        "    return len(run.calls) / max(run.window.sent, 1)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append(
        {"name": "marco768.tiny-open", "config": "msmarco-passage-768",
         "traffic": "tiny-open", "chips": 1, "why": "a throwaway cell"})
    spec["end_to_end"].append(
        {"name": "p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["marco768.tiny-open"]})
    spec["per_layer"].append(
        {"name": "calls_per_query.open", "unit": "calls", "better": "lower",
         "source": "program_counter", "layer": "serving front",
         "moves": "p50_ms", "workloads": ["marco768.tiny-open"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = Cell("marco768.tiny-open", bench_dir=str(bench),
                root=str(tmp_path))
    out = run_cell(cell, 8, 1.0, False, time.perf_counter(), rehearse=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"p50_ms", "recall_at_10", "setup_s"}
    out = run_cell(cell, 8, 1.0, True, time.perf_counter(), rehearse=True)
    assert out["metrics"]["calls_per_query.open"]["value"] > 0
    after = digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
