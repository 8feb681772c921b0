"""The trace reduction, on a small trace recorded on one TPU v5e (a 5 s
window of marco768.uniform, seed 102) and on hand-made intervals."""
import os

import pytest

from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP_TRACE = os.path.join(DATA, "marco768.uniform.xplane.pb")
STAGE = ("jit_ivf_adc", "jit_ivf_adc_blocked", "jit_ivf_adc_run_resident")
# the reduction of that trace as first read, kept as a guard
BUSY_S = 7.303503476
ADC_S = 7.302592461


def test_union_of_intervals():
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr.union_ns([(0, 10), (2, 3), (10, 12)]) == 12


def test_merge_of_events():
    assert tr.merge([]) == []
    assert tr.merge([("a", 5, 15), ("b", 0, 10), ("c", 20, 30)]) == [
        [0, 15], [20, 30]]


def test_module_names_lose_their_ids():
    assert tr.module_name("jit_ivf_adc(1234)") == "jit_ivf_adc"
    assert tr.module_name("jit__exact_rerank") == "jit__exact_rerank"


@pytest.fixture(scope="module")
def chip():
    return tr.Trace(CHIP_TRACE)


def test_chip_trace_window_and_busy(chip):
    assert chip.devices == 1
    assert 4.5 < chip.window_s < 8.0
    busy = chip.busy_s()
    assert 0.0 < busy <= chip.window_s
    assert busy == pytest.approx(BUSY_S, rel=1e-9)


def test_chip_trace_adc_stage(chip):
    adc = chip.module_s(STAGE)
    assert 0.0 < adc <= chip.busy_s() * chip.devices
    assert adc == pytest.approx(ADC_S, rel=1e-9)
    assert chip.top_modules(3)[0][0] == "jit_ivf_adc"


def test_chip_trace_idle_gaps(chip):
    gaps = chip.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    idle = 1.0 - chip.busy_s() / chip.window_s
    assert sum(g[1] for g in gaps) <= idle * chip.window_s + 1e-9


def test_idle_within_hand_made():
    t = tr.Trace.__new__(tr.Trace)
    t.t0, t.t1 = 0, 100
    t.ops = {"/device:TPU:0": [("a", 10, 20), ("b", 15, 30), ("c", 60, 70)]}
    assert t.idle_within([]) == 0
    assert t.idle_within([(0, 100)]) == 100 - 30
    # overlapping waits count once; outside the window counts not at all
    assert t.idle_within([(5, 25), (20, 40), (90, 200)]) == 35 - 20 + 10
    assert t.idle_within([(12, 18)]) == 0


def test_chip_trace_idle_within_window(chip):
    """Waiting through the whole window: idle is the window less busy."""
    idle = chip.idle_within([(chip.t0 - 5, chip.t1 + 5)]) * 1e-9
    assert idle == pytest.approx(chip.window_s - chip.busy_s(), rel=1e-9)


def test_pending_intervals_on_the_trace_clock():
    """Run.pending_ns maps each query's due-to-answer wait from the host
    clock onto the trace's; a query never answered waits to the end."""
    import numpy as np

    from harness.bench import Run
    from harness.drive import Window

    win = Window(3)
    win.due[:] = [100.0, 100.5, 101.0]
    win.done[:] = [100.2, np.nan, 101.1]
    win.sent, win.t1 = 3, 101.0
    run = Run.__new__(Run)
    run.window, run.t_window = win, 99.0
    run.trace = tr.Trace.__new__(tr.Trace)
    run.trace.t0 = 5e9
    got = run.pending_ns()
    want = [(6.0e9, 6.2e9), (6.5e9, 7.1e9), (7.0e9, 7.1e9)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e3)
