"""The readers of the program's spans (``harness/spans.py`` and the five
metrics built on it), on hand-made records and a stub trace, and the
anchor that maps a span onto the profiler trace's clock, on one CPU
profiler session."""
import glob
import os
import sys
import time

import jax
import numpy as np
import pytest

import repro.obs as obs
from harness import trace as tr
from harness.bench import Run
from harness.drive import Window
from harness.spec import HERE, load_module

NEW = ("queue_wait_ms", "adc_pad_steps", "idle_front_ms", "idle_planning_ms",
       "idle_unattributed_ms")
T_WINDOW = 10.0  # host clock (s) at the trace window's start, trace t0 = 0
HOST = T_WINDOW * 1e9  # host ns of trace ns 0
MS = 1e6


def reader(name):
    return load_module(os.path.join(HERE, "metrics", name + ".py")).read


def span(i, name, t0_ms, t1_ms, thread="serve-batcher", parent=None,
         **attrs):
    """A record at trace-clock milliseconds."""
    return obs.Span(i, name, thread, int(HOST + t0_ms * MS),
                    int(HOST + t1_ms * MS), parent, attrs)


def records():
    """Two queries due at 10 and 11 ms, answered at 60 ms; the device runs
    30-50 ms, so it idles with them waiting over 10-30 and 50-60 ms."""
    old = span(1, "db.query", -9000, -8000)  # warm-up, before the window
    recs = [
        old,
        span(2, "serve.slot_wait", 12, 13),
        span(3, "serve.complete", 14, 14.5, thread="serve-completer",
             batch=0),
        span(4, "serve.batch_wait", 13, 15, batch=1),
        span(5, "serve.complete", 18, 19, thread="serve-completer",
             batch=0),
        span(7, "ivf.visit_sync", 20, 25, parent=6),
        span(8, "ivf.adc", 25.5, 27, parent=6, steps=4096, real_steps=512),
        span(6, "db.query", 16, 28, parent=9, bucket=1),
        span(9, "serve.dispatch", 15, 29, batch=1, rows=2, real=2),
        span(10, "host.gc", 29.5, 30, generation=0),
        span(12, "serve.request", 10.5, 57, thread="serve-completer",
             parent=11, batch=1, rid=0),
        span(13, "serve.request", 11, 57, thread="serve-completer",
             parent=11, batch=1, rid=1),
        span(11, "serve.complete", 52, 58, thread="serve-completer",
             batch=1),
    ]
    # warm-up's grid: outside the window, so not counted
    recs.insert(1, span(14, "ivf.adc", -8500, -8400, parent=1, steps=64,
                        real_steps=64))
    return sorted(recs, key=lambda r: r.t1)


def stub_run(recs, monkeypatch):
    monkeypatch.setattr(obs, "spans", lambda: list(recs))
    win = Window(2)
    win.due[:] = T_WINDOW + np.array([10e-3, 11e-3])
    win.done[:] = T_WINDOW + 60e-3
    win.sent, win.t0, win.t1 = 2, T_WINDOW, T_WINDOW + 0.1
    trace = tr.Trace.__new__(tr.Trace)
    trace.t0, trace.t1 = 0, 100 * MS
    trace.ops = {"/device:TPU:0": [("fusion", 30 * MS, 50 * MS)]}
    trace.modules, trace.spans = {}, []
    run = Run.__new__(Run)
    run.window, run.t_window, run.trace = win, T_WINDOW, trace
    return run


def test_idle_split_sums_to_pending_idle(monkeypatch):
    run = stub_run(records(), monkeypatch)
    got = {n: reader(n)(run) for n in NEW[2:]}
    pending = reader("pending_idle_ms")(run)
    assert pending == pytest.approx(30 / 2)
    assert sum(got.values()) == pytest.approx(pending, rel=1e-12)


def test_idle_priority_rule(monkeypatch):
    """The completer first (14-14.5 and 18-19 ms, though the batcher is
    then filling or planning), then the batcher's innermost span: its
    waits and dispatch are the front's, anything in ``db.query`` is
    planning; a collection at the top (29.5-30) and time under no span
    are unattributed."""
    run = stub_run(records(), monkeypatch)
    # front: completer 0.5 + 1 + 6; batcher 12-14, 14.5-16, 28-29
    assert reader("idle_front_ms")(run) == pytest.approx((7.5 + 4.5) / 2)
    # planning: 16-18 and 19-28
    assert reader("idle_planning_ms")(run) == pytest.approx(11 / 2)
    # unattributed: 10-12, 29-30, 50-52, 58-60
    assert reader("idle_unattributed_ms")(run) == pytest.approx(7 / 2)


def test_queue_wait_and_pad_steps(monkeypatch):
    run = stub_run(records(), monkeypatch)
    # waits 4.5 and 4 ms to the batch's dispatch at 15 ms
    assert reader("queue_wait_ms")(run) == pytest.approx(4.5)
    # only the window's grid: 512 of 4096 steps real
    assert reader("adc_pad_steps")(run) == pytest.approx(87.5)


@pytest.mark.parametrize("name", NEW)
def test_ring_that_lost_the_window_raises(monkeypatch, name):
    recs = [r for r in records() if r.t1 > HOST]  # the oldest are gone
    run = stub_run(recs, monkeypatch)
    with pytest.raises(ValueError, match="no longer covers"):
        reader(name)(run)


@pytest.mark.parametrize("name", NEW)
def test_program_without_spans_reads_nothing(monkeypatch, name):
    """Over a program that has no ``repro.obs`` every reader is silent."""
    run = stub_run(records(), monkeypatch)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name)(run) is None


def test_device_readers_silent_without_trace(monkeypatch):
    run = stub_run(records(), monkeypatch)
    run.trace = None
    assert all(reader(n)(run) is None for n in NEW[2:])


def test_innermost_pieces():
    from harness.spans import innermost
    got = innermost([(0, 10, "a"), (2, 4, "b"), (4, 6, "c"), (5, 6, "d"),
                     (12, 13, "e")])
    assert got == [(0, 2, "a"), (2, 4, "b"), (4, 5, "c"), (5, 6, "d"),
                   (6, 10, "a"), (12, 13, "e")]


def test_span_lands_on_its_trace_event(tmp_path):
    """One CPU profiler session wrapped as ``bench.window`` wraps the
    window: each ``serve.dispatch`` record, mapped through the anchor,
    starts and ends within 1 ms of its event in the ``.xplane.pb``."""
    from jax.profiler import ProfileData

    from repro.core import VectorDB
    from repro.serve import AsyncQueryEngine

    x = np.random.default_rng(0).normal(size=(256, 16)).astype(np.float32)
    eng = AsyncQueryEngine(VectorDB("flat").load(x), max_batch=4,
                           max_wait_ms=0.5)
    for f in [eng.submit(x[i], k=3) for i in range(4)]:  # compile first
        f.result(timeout=60)
    tr.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        t_window = time.perf_counter()
        for i in range(6):
            eng.submit(x[i], k=3).result(timeout=60)
            time.sleep(0.005)
    jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    (window,) = events["bench.window"]
    off = window[0] - t_window * 1e9
    recs = [r for r in obs.spans() if r.name == "serve.dispatch"
            and r.t0 >= t_window * 1e9]
    assert len(recs) == 6 == len(events["serve.dispatch"])
    for r in recs:
        s, e = min(events["serve.dispatch"],
                   key=lambda ev: abs(ev[0] - (r.t0 + off)))
        assert abs(s - (r.t0 + off)) < 1e6 and abs(e - (r.t1 + off)) < 1e6
