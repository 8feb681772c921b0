"""The comparison that decides ``correct`` fails the control and each
fault the served path can have, at a tiny size on the CPU.

Each fault breaks the timed path underneath an otherwise whole run
(``harness/faults.py``): the front's batches, through a broken proxy in
place of the database the window drives, or the ADC stage below the exact
re-rank, through a broken candidate dispatch."""
import time

import jax
import numpy as np
import pytest

from harness import faults
from harness.bench import run_cell
from harness.spec import Cell

CELL = "marco768.uniform"


@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_fails(fault):
    cell = Cell(CELL)
    jax.clear_caches()  # no program traced before the fault was planted
    with faults.plant(fault) as proxy:
        out = run_cell(cell, 21, 2.0, False, time.perf_counter(),
                       rehearse=True, proxy=proxy)
    jax.clear_caches()
    assert out["correct"] is False, out["checks"]


def test_adc_fault_is_lifted():
    """A planted ADC fault is gone once its context closes."""
    from repro.kernels import ops
    sound = ops.ivf_adc_topk
    with faults.plant("rolled_lut"):
        assert ops.ivf_adc_topk is not sound
    assert ops.ivf_adc_topk is sound


def test_control_fails_and_program_passes():
    """The reference one precision lower, in the program's place, fails
    the score check the program passes, by the same limits."""
    cell = Cell(CELL)
    out = run_cell(cell, 22, 2.0, False, time.perf_counter(), rehearse=True,
                   control=True)
    limit = cell.front["limits"]["score_gap"]
    assert out["correct"] and out["checks"]["score_gap"]["value"] <= limit
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["score_gap"] > limit


def test_compare_reads_each_fault():
    """compare() on hand-made answers: exact, altered, malformed, missing."""
    from harness import reference
    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.normal(size=(300, 16)).astype(np.float32))
    q = rng.normal(size=(4, 16)).astype(np.float32)
    s, i = reference.scan_topk(x, q, 5)
    good = [(s[j], i[j]) for j in range(4)]
    checks = reference.compare(good, x, q, 5, i)
    assert checks == {"score_gap": checks["score_gap"], "malformed": 0,
                      "unanswered": 0, "recall_miss": 0.0}
    assert checks["score_gap"] < 1e-5
    bad = list(good)
    bad[1] = (s[1], np.r_[i[1][:4], i[2][0]])   # an id swapped
    bad[2] = (s[2][::-1], i[2][::-1])           # ranked worst first
    bad[3] = None                               # never answered
    checks = reference.compare(bad, x, q, 5, i)
    assert checks["score_gap"] > 1e-3
    assert checks["malformed"] == 1 and checks["unanswered"] == 1
    assert checks["recall_miss"] == pytest.approx(0.1)  # 9 of 10 recalled
