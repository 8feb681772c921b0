"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, and
run.py's refusal to measure without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT
from harness.bench import run_cell
from harness.spec import Cell, read_json

CELLS = [w["name"] for w in read_json(os.path.join(ROOT, "BENCHMARK.json"))
         ["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses(name):
    cell = Cell(name)
    out = run_cell(cell, 3_000_000_019, 2.0, False, time.perf_counter(),
                   rehearse=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_traced(name):
    """Traced on the CPU: the host-read per-layer metrics are there; the
    device-trace ones find nothing to read and are left out."""
    cell = Cell(name)
    out = run_cell(cell, 5, 2.0, True, time.perf_counter(), rehearse=True)
    assert out["correct"], out["checks"]
    host = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert set(out["metrics"]) == host
    assert "busy_s" not in out["device"]


def test_interpret_mode_kernels():
    """The same run with the Pallas kernels in interpret mode."""
    cell = Cell(CELLS[0])
    cell.config["index"]["use_kernel"] = True
    out = run_cell(cell, 11, 1.0, False, time.perf_counter(), rehearse=True)
    assert out["correct"], out["checks"]


def test_same_seed_same_inputs():
    cell = Cell(CELLS[0])
    a = run_cell(cell, 2**31 + 7, 1.0, False, time.perf_counter(),
                 rehearse=True)
    b = run_cell(cell, 2**31 + 7, 1.0, False, time.perf_counter(),
                 rehearse=True)
    assert a["attempted"] == b["attempted"]
    assert a["metrics"]["recall_at_10"] == b["metrics"]["recall_at_10"]


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_prints_result_line():
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "4", "--seconds",
                        "1", "--trace", "0", "--rehearse"],
                       capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
