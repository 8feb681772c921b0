"""The async continuous-batching front (repro.serve.async_engine).

Contract under test, in order of load-bearing-ness:

  * ORACLE PARITY — results through the threaded front are exactly the
    results the synchronous pump / a direct ``db.query`` produces, for any
    interleaving of concurrent submitters (reads are row-independent, so
    batch composition cannot matter — this asserts it doesn't).
  * READ-YOUR-WRITES — queue arrival order is execution order: a read
    submitted after a write observes it, a read submitted before does not,
    including across threads once arrival order is fixed.
  * BACKPRESSURE — the bounded queue rejects/blocks deterministically at
    the bound (probed with the batcher paused, so the queue cannot drain
    mid-assert).
  * SHUTDOWN — close(drain=True) resolves every accepted future;
    close(drain=False) cancels the queued ones. No orphans either way.
"""
import threading
import time
from concurrent.futures import CancelledError

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import VectorDB
from repro.serve import AsyncQueryEngine, BackpressureError, QueryEngine


def _corpus(rng, n=400, d=32):
    return rng.normal(size=(n, d)).astype(np.float32)


# ------------------------------------------------------------ oracle parity

def test_concurrent_submitters_match_oracle(rng):
    """4 submitter threads x 32 reads race for queue position; every result
    must still equal the single-query oracle bit-for-bit on ids."""
    corpus = _corpus(rng)
    db = VectorDB("flat", metric="cosine").load(corpus)
    queries = corpus[:128] + 0.01 * rng.normal(size=(128, 32)).astype(np.float32)
    oracle_s, oracle_i = db.query(queries, k=5, bucketize=False)
    oracle_s, oracle_i = np.asarray(oracle_s), np.asarray(oracle_i)

    eng = AsyncQueryEngine(db, max_batch=16, max_wait_ms=1.0, max_queue=64)
    futs = [None] * 128

    def client(t):
        for j in range(32):
            i = t * 32 + j
            futs[i] = eng.submit(queries[i], k=5)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert eng.drain(timeout=60)
    eng.close()
    for i, f in enumerate(futs):
        scores, ids = f.result(timeout=5)
        assert ids.shape == (5,)
        np.testing.assert_array_equal(ids, oracle_i[i])
        np.testing.assert_allclose(scores, oracle_s[i], atol=1e-5)


def test_async_matches_sync_pump_exactly(rng):
    """The same submission sequence through the async front and the
    synchronous pump yields identical ids (and matching scores), on the
    mutable ivf_pq engine with interleaved writes — the two fronts share
    one batching/write body, and this pins it."""
    corpus = _corpus(rng, n=256, d=16)
    kw = dict(n_clusters=8, nprobe=4, m=4, ksub=16, refine=0, block_size=8,
              seed=0)
    db_a = VectorDB("ivf_pq", metric="cosine", **kw).load(corpus)
    db_s = VectorDB("ivf_pq", metric="cosine", **kw).load(corpus)
    new = rng.normal(size=(24, 16)).astype(np.float32)
    qs = rng.normal(size=(40, 16)).astype(np.float32)

    def script(submit, submit_write):
        outs = []
        for i in range(40):
            if i % 10 == 3:
                submit_write("insert", new[(i // 10) * 6:(i // 10) * 6 + 6])
            if i % 10 == 7:
                submit_write("delete", ids=np.arange(i, i + 3))
            outs.append(submit(qs[i], 8))
        return outs

    eng_a = AsyncQueryEngine(db_a, max_batch=8, max_wait_ms=0.5)
    futs = script(lambda q, k: eng_a.submit(q, k),
                  lambda kind, *a, **kw2: eng_a.submit_write(kind, *a, **kw2))
    assert eng_a.drain(timeout=60)
    eng_a.close()

    eng_s = QueryEngine(db_s, max_batch=8, max_wait_ms=0.0)
    rids = script(lambda q, k: eng_s.submit(q, k),
                  lambda kind, *a, **kw2: eng_s.submit_write(kind, *a, **kw2))
    eng_s.drain()

    for f, rid in zip(futs, rids):
        s_a, i_a = f.result(timeout=5)
        s_s, i_s = eng_s.result(rid)
        np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_s))
        np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_s),
                                   atol=1e-5)


# --------------------------------------------------------- read-your-writes

def test_read_your_writes_is_queue_order(rng):
    """Paused engine fixes arrival order exactly: read, write, read. On
    start, the first read must not observe the insert, the second must —
    the write closes the first read's batch."""
    corpus = rng.normal(size=(16, 8)).astype(np.float32)
    target = np.full((8,), 2.0, np.float32)
    db = VectorDB("flat", metric="l2").load(corpus)
    eng = AsyncQueryEngine(db, max_batch=64, max_wait_ms=0.5, start=False)
    f_before = eng.submit(target, k=1)
    f_write = eng.submit_write("insert", target[None])
    f_after = eng.submit(target, k=1)
    eng.start()
    kind, new_ids = f_write.result(timeout=10)
    assert kind == "insert" and new_ids.tolist() == [16]
    assert int(f_before.result(timeout=10)[1][0]) != 16
    assert int(f_after.result(timeout=10)[1][0]) == 16
    eng.close()
    st = eng.latency_stats()
    assert st["write_inserts"] == 1


def test_read_your_writes_across_threads(rng):
    """A reader thread that waits for the writer's future must observe the
    write, from a different thread than the one that submitted it."""
    corpus = rng.normal(size=(16, 8)).astype(np.float32)
    target = np.full((8,), 3.0, np.float32)
    db = VectorDB("flat", metric="l2").load(corpus)
    eng = AsyncQueryEngine(db, max_batch=8, max_wait_ms=0.5)
    got = {}

    def writer():
        got["write"] = eng.submit_write("insert", target[None]).result(10)

    def reader():
        wt = threading.Thread(target=writer)
        wt.start()
        wt.join()  # write future resolved -> applied in queue order
        got["read"] = eng.submit(target, k=1).result(10)

    rt = threading.Thread(target=reader)
    rt.start()
    rt.join()
    eng.close()
    assert got["write"][1].tolist() == [16]
    assert int(got["read"][1][0]) == 16


# ------------------------------------------------------------- backpressure

def test_backpressure_rejects_at_bound(rng):
    corpus = _corpus(rng, n=64)
    db = VectorDB("flat").load(corpus)
    eng = AsyncQueryEngine(db, max_queue=4, overflow="reject", start=False)
    futs = [eng.submit(corpus[i], k=2) for i in range(4)]  # exactly the bound
    with pytest.raises(BackpressureError):
        eng.submit(corpus[4], k=2)
    with pytest.raises(BackpressureError):
        eng.submit_write("insert", corpus[:1])
    assert eng.rejected == 2
    eng.start()
    for f in futs:
        assert f.result(timeout=10)[1].shape == (2,)
    eng.close()
    st = eng.latency_stats()
    assert st["rejected"] == 2
    assert st["queue_depth_max"] == 4
    assert st["queue_depth"] == 0


def test_backpressure_block_times_out_then_frees(rng):
    corpus = _corpus(rng, n=64)
    db = VectorDB("flat").load(corpus)
    eng = AsyncQueryEngine(db, max_queue=2, overflow="block", start=False)
    futs = [eng.submit(corpus[i], k=2) for i in range(2)]
    with pytest.raises(BackpressureError):
        eng.submit(corpus[2], k=2, timeout=0.05)  # full + paused: must expire

    blocked = {}

    def late_submitter():
        blocked["fut"] = eng.submit(corpus[3], k=2)  # no timeout: waits

    th = threading.Thread(target=late_submitter)
    th.start()
    time.sleep(0.05)
    assert th.is_alive()  # still blocked on the full queue
    eng.start()           # batcher drains -> space frees -> submit returns
    th.join(timeout=10)
    assert not th.is_alive()
    for f in futs + [blocked["fut"]]:
        assert f.result(timeout=10)[1].shape == (2,)
    eng.close()
    assert eng.latency_stats()["rejected"] == 1


# ----------------------------------------------------------------- shutdown

def test_close_drains_cleanly_no_orphans(rng):
    corpus = _corpus(rng)
    db = VectorDB("flat").load(corpus)
    eng = AsyncQueryEngine(db, max_batch=8, max_wait_ms=0.5, max_queue=256)
    futs = [eng.submit(corpus[i % 400], k=3) for i in range(100)]
    futs.append(eng.submit_write("insert", corpus[:2]))
    eng.close(drain=True)  # immediately: everything queued must still serve
    assert all(f.done() for f in futs)
    for f in futs[:100]:
        assert f.result()[1].shape == (3,)
    kind, ids = futs[100].result()
    assert kind == "insert" and len(ids) == 2
    with pytest.raises(RuntimeError):
        eng.submit(corpus[0], k=3)  # closed: no new intake


def test_close_without_drain_cancels_queued(rng):
    corpus = _corpus(rng, n=64)
    db = VectorDB("flat").load(corpus)
    eng = AsyncQueryEngine(db, max_queue=16, start=False)
    futs = [eng.submit(corpus[i], k=2) for i in range(5)]
    eng.close(drain=False)
    assert all(f.cancelled() for f in futs)
    assert eng.drain(timeout=5)  # outstanding count reached zero


def test_close_without_drain_on_running_engine_leaves_no_pending(rng):
    corpus = _corpus(rng)
    db = VectorDB("flat").load(corpus)
    eng = AsyncQueryEngine(db, max_batch=4, max_wait_ms=0.0, max_queue=256)
    futs = [eng.submit(corpus[i % 400], k=2) for i in range(64)]
    eng.close(drain=False)
    assert eng.drain(timeout=30)
    for f in futs:  # every future resolved one way: result or cancelled
        assert f.done()
        if not f.cancelled():
            assert f.result()[1].shape == (2,)


def test_context_manager_and_restart(rng):
    corpus = _corpus(rng, n=64)
    db = VectorDB("flat").load(corpus)
    with AsyncQueryEngine(db, max_batch=4, max_wait_ms=0.0) as eng:
        f = eng.submit(corpus[1], k=1)
        assert int(f.result(timeout=10)[1][0]) == 1
    # closed by the context exit; start() reopens intake on the same engine
    eng.start()
    f = eng.submit(corpus[2], k=1)
    assert int(f.result(timeout=10)[1][0]) == 2
    eng.close()


# -------------------------------------------------------------------- stats

def test_latency_stats_surface_gauges_and_counters(rng):
    corpus = _corpus(rng)
    db = VectorDB("flat", metric="cosine").load(corpus)
    eng = AsyncQueryEngine(db, max_batch=8, max_wait_ms=0.5)
    assert eng.latency_stats() == {}  # nothing served yet
    futs = [eng.submit(corpus[i], k=3) for i in range(32)]
    eng.submit_write("insert", corpus[:1])
    assert eng.drain(timeout=60)
    eng.close()
    st = eng.latency_stats()
    assert st["n"] == 32
    assert np.isfinite(st["p50_ms"]) and np.isfinite(st["p99_ms"])
    assert st["p50_ms"] <= st["p99_ms"]
    assert st["plan_hits"] + st["plan_misses"] >= 1  # the shared ledger
    assert st["write_inserts"] == 1
    assert st["queue_depth"] == 0 and st["inflight"] == 0
    assert st["rejected"] == 0
    for f in futs:
        assert f.done()


def test_submit_many_matches_per_submit_path(rng):
    """The amortized block path is submit() in a loop, exactly: same FIFO
    positions (so a write submitted after the block orders after ALL of
    it), same results, same backpressure accounting."""
    corpus = _corpus(rng, n=128, d=16)
    db = VectorDB("flat", metric="l2").load(corpus)
    queries = corpus[:48] + 0.01 * rng.normal(size=(48, 16)).astype(np.float32)
    oracle_i = np.asarray(db.query(queries, k=3, bucketize=False)[1])

    eng = AsyncQueryEngine(db, max_batch=16, max_queue=33, start=False)
    futs = eng.submit_many(queries[:32], k=3)  # the block is admitted whole
    assert len(futs) == 32 and eng.queue_depth_max == 32
    f_write = eng.submit_write("insert", corpus[:1])  # queue pos 33: after it
    eng.start()
    assert f_write.result(timeout=10)[0] == "insert"  # ordered after block
    futs += eng.submit_many(queries[32:], k=3)
    assert eng.drain(timeout=60)
    eng.close()
    got = np.stack([np.asarray(f.result(timeout=5)[1]) for f in futs])
    np.testing.assert_array_equal(got, oracle_i)


def test_submit_many_backpressure_cancels_stranded_requests(rng):
    """On a paused engine a block larger than the free space must time out
    (policy block) — the stranded tail is cancelled and counted, the
    admitted head still completes after start()."""
    corpus = _corpus(rng, n=64, d=16)
    db = VectorDB("flat", metric="l2").load(corpus)
    eng = AsyncQueryEngine(db, max_queue=8, overflow="block", start=False)
    head = eng.submit_many(corpus[:8], k=2)  # fills the queue exactly
    with pytest.raises(BackpressureError):
        eng.submit_many(corpus[8:24], k=2, timeout=0.05)
    assert eng.rejected == 16  # the whole stranded chunk
    eng.start()
    for f in head:
        assert f.result(timeout=10)[1].shape == (2,)
    assert eng.drain(timeout=30)
    eng.close()
    assert eng.latency_stats()["n"] == 8


# ------------------------------------------------------ deployment widths

@pytest.mark.parametrize("d,m", [(768, 96), (1536, 192)])
def test_ivf_pq_deployment_widths_match_exact_scan(d, m):
    """The MS MARCO deployments' index (cosine IVF-PQ at 8 dims a subspace,
    ksub 256, nprobe 16, exact re-rank of 32) on a small clustered corpus,
    served through the async front: every score is the exact cosine of its
    id, every answer is k distinct ids best first, and recall@10 against
    an exact f32 HIGHEST scan stays above 0.7. Rows sit 4 to a sub-centre
    around 4 topics, so a query's top-10 spreads over many sub-centres
    and lists (recall reads 0.79 at d=768 and 0.85 at d=1536; nprobe 4
    reads about 0.61 at both)."""
    rng = np.random.default_rng(d)
    n, n_q, k = 3072, 48, 10
    top = rng.normal(size=(4, d)).astype(np.float32)
    sub = top[np.arange(n // 4) % 4] + 0.5 * rng.normal(
        size=(n // 4, d)).astype(np.float32)
    x = sub[np.arange(n) % len(sub)] + rng.normal(
        size=(n, d)).astype(np.float32)
    q = sub[rng.integers(0, len(sub), n_q)] + rng.normal(
        size=(n_q, d)).astype(np.float32)
    db = VectorDB("ivf_pq", metric="cosine", m=m, ksub=256, nprobe=16,
                  refine=32).load(x)
    with AsyncQueryEngine(db, max_batch=16, max_wait_ms=1.0) as eng:
        futs = [eng.submit(row, k=k) for row in q]
        got = [f.result(timeout=120) for f in futs]

    xn = x / np.linalg.norm(x, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    exact = np.asarray(jnp.dot(jnp.asarray(qn), jnp.asarray(xn).T,
                               precision=jax.lax.Precision.HIGHEST))
    truth = np.argsort(-exact, axis=1)[:, :k]
    hits = 0
    for j, (s, i) in enumerate(got):
        s, i = np.asarray(s), np.asarray(i)
        assert i.shape == (k,) and len(set(i.tolist())) == k
        assert ((i >= 0) & (i < n)).all() and (np.diff(s) <= 0).all()
        np.testing.assert_allclose(s, np.sum(qn[j] * xn[i], axis=1),
                                   rtol=0, atol=2e-5)
        hits += len(set(i.tolist()) & set(truth[j].tolist()))
    assert hits / (n_q * k) > 0.7
