"""The span record (repro.obs) and the spans of the served IVF-PQ path.

  * ``span`` nests per thread: each record names the enclosing span of its
    own thread, never another thread's.
  * The ring is bounded and keeps the newest records.
  * ``record`` stores a span timed across threads; collections show up as
    ``host.gc``.
  * Through ``AsyncQueryEngine`` every read leaves one ``serve.request``
    whose batch id joins a ``serve.dispatch`` and a ``serve.complete``;
    every ``db.query`` holds the IVF-PQ stages, and ``ivf.adc`` counts the
    grid steps that ``visit_sharing`` found real.
  * ``latency_stats`` keeps its keys and reads only its own front's
    requests.
"""
import gc
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import VectorDB
from repro.serve import AsyncQueryEngine, QueryEngine


def _clustered(rng, n, d, n_clusters, scale=2.0):
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * scale
    return (centers[rng.integers(0, n_clusters, n)]
            + rng.normal(size=(n, d)).astype(np.float32))


def _ivf_pq(rng, **kw):
    corpus = _clustered(rng, 600, 32, 8)
    kw = dict(dict(metric="cosine", m=8, nprobe=4, refine=16,
                   adc_mode="auto"), **kw)
    return VectorDB("ivf_pq", **kw).load(corpus), corpus


def _since(first_id):
    return [s for s in obs.spans() if s.id >= first_id]


def test_spans_nest_per_thread():
    """Two threads interleave their nested spans; each child's parent is
    its own thread's outer span."""
    start = obs.new_id()
    both_open = threading.Barrier(2, timeout=10)

    def work(tag):
        with obs.span("t.outer", tag=tag):
            both_open.wait()
            with obs.span("t.inner", tag=tag):
                both_open.wait()

    threads = [threading.Thread(target=work, args=(t,), name=f"t-{t}")
               for t in ("a", "b")]
    for th in threads:
        th.start()
    for th in threads:
        th.join(10)
        assert not th.is_alive()
    recs = [s for s in _since(start) if s.name.startswith("t.")]
    assert len(recs) == 4
    outer = {s.attrs["tag"]: s for s in recs if s.name == "t.outer"}
    for s in recs:
        if s.name == "t.inner":
            o = outer[s.attrs["tag"]]
            assert s.parent == o.id and s.thread == o.thread
            assert o.t0 <= s.t0 <= s.t1 <= o.t1
        else:
            assert s.parent is None
    assert {s.thread for s in recs} == {"t-a", "t-b"}


def test_span_attrs_may_grow_inside_the_block():
    start = obs.new_id()
    with obs.span("t.attrs", a=1) as sp:
        sp.attrs["b"] = 2
    (rec,) = [s for s in _since(start) if s.name == "t.attrs"]
    assert rec.attrs == {"a": 1, "b": 2}


def test_ring_is_bounded_and_keeps_the_newest():
    n = obs.CAPACITY + 5
    for i in range(n):
        obs.record("t.bound", i, i + 1, i=i)
    recs = obs.spans()
    assert len(recs) == obs.CAPACITY
    mine = [s.attrs["i"] for s in recs if s.name == "t.bound"]
    # a collection may land among them; the tail is unbroken and the
    # first five are gone
    assert mine == list(range(mine[0], n)) and mine[0] >= 5


def test_record_crosses_threads():
    """A span begun on one thread and recorded on another: its stamps are
    the caller's, its thread and parent the recording thread's."""
    start = obs.new_id()
    t0 = obs.time.perf_counter_ns()

    def finish():
        with obs.span("t.completer"):
            obs.record("t.request", t0, obs.time.perf_counter_ns(), rid=7)

    th = threading.Thread(target=finish, name="t-completer")
    th.start()
    th.join(10)
    assert not th.is_alive()
    recs = _since(start)
    (req,) = [s for s in recs if s.name == "t.request"]
    (done,) = [s for s in recs if s.name == "t.completer"]
    assert req.t0 == t0 and req.t1 >= t0 and req.attrs == {"rid": 7}
    assert req.thread == "t-completer" and req.parent == done.id


def test_collections_are_recorded():
    start = obs.new_id()
    gc.collect()
    got = [s for s in _since(start) if s.name == "host.gc"]
    assert got and got[-1].attrs["generation"] == 2
    assert got[-1].t1 >= got[-1].t0


def test_async_requests_join_dispatch_and_complete(rng):
    db, corpus = _ivf_pq(rng)
    start = obs.new_id()
    eng = AsyncQueryEngine(db, max_batch=8, max_wait_ms=1.0)
    futs = [eng.submit(corpus[i], k=5) for i in range(40)]
    assert eng.drain(timeout=120)
    eng.close()
    for f in futs:
        f.result(timeout=5)
    recs = _since(start)
    reqs = [s for s in recs if s.name == "serve.request"
            and s.attrs["front"] == eng.front]
    assert sorted(s.attrs["rid"] for s in reqs) == list(range(40))
    dispatch = {s.attrs["batch"]: s for s in recs
                if s.name == "serve.dispatch"}
    complete = {s.attrs["batch"]: s for s in recs
                if s.name == "serve.complete"}
    for r in reqs:
        d, c = dispatch[r.attrs["batch"]], complete[r.attrs["batch"]]
        assert r.t0 <= d.t0 <= d.t1 <= c.t1 and c.t0 <= r.t1 <= c.t1
        assert r.parent == c.id  # recorded inside its batch's completion
    batches = {r.attrs["batch"] for r in reqs}
    assert sum(dispatch[b].attrs["real"] for b in batches) == 40
    st = eng.latency_stats()
    assert st["rows_real"] == 40
    assert st["rows_dispatched"] == sum(dispatch[b].attrs["rows"]
                                        for b in batches)
    for b in batches:  # the batcher's waits carry the same batch id
        (w,) = [s for s in recs if s.name == "serve.batch_wait"
                and s.attrs["batch"] == b]
        assert w.t1 <= dispatch[b].t0 and w.thread == dispatch[b].thread


def test_db_query_holds_the_ivf_stages(rng, monkeypatch):
    import repro.core.ivf as ivf

    seen = []
    real_sharing = ivf.visit_sharing

    def spy(visit, **kw):
        out = real_sharing(visit, **kw)
        seen.append((np.asarray(visit).size, out["pairs"]))
        return out

    monkeypatch.setattr(ivf, "visit_sharing", spy)
    db, corpus = _ivf_pq(rng)
    steps0 = db.adc_stats["steps"]
    real0 = db.adc_stats["real_steps"]
    start = obs.new_id()
    db.query(corpus[:3], k=5)
    recs = _since(start)
    (q,) = [s for s in recs if s.name == "db.query"]
    assert q.attrs["bucket"] == 4 and q.attrs["plan_miss"] in (True, False)
    kids = {s.name: s for s in recs if s.parent == q.id}
    assert {"ivf.probe", "ivf.visit_sync", "ivf.sharing", "ivf.adc",
            "ivf.rerank"} <= set(kids)
    order = ["ivf.probe", "ivf.visit_sync", "ivf.sharing", "ivf.adc",
             "ivf.rerank"]
    assert [kids[n].t0 for n in order] == sorted(kids[n].t0 for n in order)
    adc = kids["ivf.adc"]
    ((steps, pairs),) = seen
    assert adc.attrs["real_steps"] == pairs
    assert adc.attrs["steps"] == steps  # Q_bucket x T
    assert 0 < pairs <= steps
    assert adc.attrs["grid"] in ("per_query", "blocked", "run_resident")
    assert db.adc_stats["steps"] - steps0 == steps
    assert db.adc_stats["real_steps"] - real0 == pairs


def test_forced_per_query_grid_counts_no_steps(rng):
    """Without the host round trip the real steps are unknown: the batch
    adds to neither sum, so the ratio stays honest."""
    db, corpus = _ivf_pq(rng, adc_mode="per_query")
    start = obs.new_id()
    db.query(corpus[:3], k=5)
    names = {s.name for s in _since(start)}
    assert "ivf.adc" in names and "ivf.visit_sync" not in names
    assert db.adc_stats["steps"] == db.adc_stats["real_steps"] == 0


BASE_KEYS = {"engine", "p50_ms", "p99_ms", "mean_ms", "n", "plan_hits",
             "plan_misses", "adc_blocked", "adc_per_query",
             "adc_run_resident", "adc_probes", "adc_sched_cache_hits",
             "adc_sched_cache_misses", "adc_sharing_factor",
             "adc_effective_nprobe", "adc_steps", "adc_real_steps"}
ASYNC_KEYS = {"queue_depth", "queue_depth_max", "rejected", "inflight",
              "durable_pending", "rows_dispatched", "rows_real"}


@pytest.mark.parametrize("front", ["sync", "async"])
def test_latency_stats_keys_and_own_requests(rng, front):
    """Every key ``latency_stats`` had stays; two fronts in one process
    each count only their own requests."""
    db, corpus = _ivf_pq(rng)

    def serve(n):
        if front == "sync":
            eng = QueryEngine(db, max_batch=8)
            for i in range(n):
                eng.submit(corpus[i], k=5)
            eng.drain()
        else:
            eng = AsyncQueryEngine(db, max_batch=8, max_wait_ms=0.5)
            futs = [eng.submit(corpus[i], k=5) for i in range(n)]
            assert eng.drain(timeout=120)
            eng.close()
            for f in futs:
                f.result(timeout=5)
        return eng.latency_stats()

    first, second = serve(12), serve(5)
    assert first["n"] == 12 and second["n"] == 5
    want = BASE_KEYS | (ASYNC_KEYS if front == "async" else set())
    assert want <= set(second)
    assert {k for k in second if k.startswith("write_")}  # mutation counters
    assert 0 < second["adc_real_steps"] <= second["adc_steps"]
    assert second["p50_ms"] <= second["p99_ms"]
