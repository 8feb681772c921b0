"""Serve a vector DB with batched requests — the production query path.

Loads a corpus, then drives BOTH serving fronts with a synthetic request
stream, printing p50/p99 and accuracy per engine:

  * ``QueryEngine`` — the synchronous pump (caller's thread drives it);
  * ``AsyncQueryEngine`` — the continuous-batching front: concurrent
    submitter threads, futures, a write folded mid-stream (read-your-
    writes), bounded queue + backpressure gauges.

Ends with the durability round trip: durable writes acked under group
commit, a simulated crash mid-ingest (``repro.ft.faults``), and recovery
from snapshot + WAL tail replay serving bit-for-bit what an uncrashed
process would have.

Also demos the sharded multi-device path when more than one jax device is
visible (XLA_FLAGS=--xla_force_host_platform_device_count=8).

    PYTHONPATH=src python examples/serve_vectordb.py
"""
import tempfile
import threading
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import DistributedVectorDB, VectorDB
from repro.serve import AsyncQueryEngine, QueryEngine


def drive(engine_name: str, db, corpus, n_requests: int = 300):
    rng = np.random.default_rng(1)
    eng = QueryEngine(db, max_batch=32, max_wait_ms=1.0)
    rids = []
    for i in range(n_requests):
        q = corpus[i % len(corpus)] + 0.02 * rng.normal(size=corpus.shape[1])
        rids.append(eng.submit(q.astype(np.float32), k=5))
        if rng.random() < 0.5:
            eng.pump()
    eng.drain()
    correct = sum(int(np.asarray(eng.result(r)[1])[0] == i % len(corpus))
                  for i, r in enumerate(rids))
    st = eng.latency_stats()
    print(f"  {engine_name:18s} acc={correct/n_requests:.3f} "
          f"p50={st['p50_ms']:.2f}ms p99={st['p99_ms']:.2f}ms")


def drive_async(engine_name: str, db, corpus, n_requests: int = 300,
                n_clients: int = 4):
    """The continuous-batching front: n_clients threads submit futures
    concurrently; one insert rides along mid-stream and every later read
    observes it (queue arrival order is execution order)."""
    rng = np.random.default_rng(1)
    queries = (corpus[np.arange(n_requests) % len(corpus)]
               + 0.02 * rng.normal(size=(n_requests, corpus.shape[1]))
               ).astype(np.float32)
    futs = [None] * n_requests
    with AsyncQueryEngine(db, max_batch=32, max_wait_ms=1.0,
                          max_queue=256, overflow="block") as eng:
        def client(c):
            for i in range(c, n_requests, n_clients):
                futs[i] = eng.submit(queries[i], k=5)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wfut = eng.submit_write("insert", queries[:1])  # folds into the queue
        eng.drain(timeout=120)
        st = eng.latency_stats()
        correct = sum(int(np.asarray(futs[i].result()[1])[0] == i % len(corpus))
                      for i in range(n_requests))
        print(f"  {engine_name:18s} acc={correct/n_requests:.3f} "
              f"p50={st['p50_ms']:.2f}ms p99={st['p99_ms']:.2f}ms "
              f"qdepth_max={st['queue_depth_max']} "
              f"writes={st['write_inserts']} (id {wfut.result()[1][0]})")


def drive_durable(tmpdir: str, corpus, n_writes: int = 24):
    """Durable writes, a crash, a recovery: the WAL lifecycle end to end.

    Writes are acked only after their WAL record is fsync'd (group
    commit, 5ms window); then a crash is injected at ``wal.append.post``
    — the record hit the disk but the process died before anything else.
    A fresh process restores the snapshot, replays the WAL tail, and must
    serve exactly what an uncrashed twin would."""
    from repro.ft.faults import SimulatedCrash, inject_crashes

    kw = dict(metric="cosine", m=8, nprobe=8, refine=64)
    db = VectorDB("ivf_pq", **kw).load(corpus)
    db.save_index(tmpdir, step=0, durable=True)
    rng = np.random.default_rng(7)
    rows = (corpus[:n_writes]
            + 0.02 * rng.normal(size=(n_writes, corpus.shape[1]))
            ).astype(np.float32)
    with AsyncQueryEngine(db, max_batch=16, max_wait_ms=1.0,
                          fsync_interval_ms=5.0) as eng:
        futs = [eng.submit_write("insert", rows[i:i + 1])
                for i in range(n_writes)]
        acked = [f.result(timeout=60) for f in futs]  # ack == fsync'd
        st = eng.latency_stats()
    print(f"  durable writes    acked={len(acked)} "
          f"wal_records={st['wal_records']} wal_fsyncs={st['wal_fsyncs']} "
          f"(group commit) durable_pending={st['durable_pending']}")

    # the process "dies" mid-ingest: the 5th record reaches the log, then
    # crash — everything in memory is gone, the disk image is all that
    # survives
    crash_at = 5
    with inject_crashes("wal.append.post", hits=crash_at):
        try:
            for i in range(10):
                db.insert(rows[i:i + 1] * 0.5)
        except SimulatedCrash:
            print(f"  simulated crash   at wal.append.post, "
                  f"record {db.wal.last_lsn}")

    recovered = VectorDB("ivf_pq", **kw).restore_index(tmpdir, durable=True)
    twin = VectorDB("ivf_pq", **kw).restore_index(tmpdir, step=0)
    for i in range(n_writes):
        twin.insert(rows[i:i + 1])
    for i in range(crash_at):  # append.post: the crashing record survived
        twin.insert(rows[i:i + 1] * 0.5)
    q = rows[:32]
    parity = float(np.mean(np.asarray(recovered.query(q, k=5)[1])
                           == np.asarray(twin.query(q, k=5)[1])))
    print(f"  recovery          replayed={recovered.wal.recovered_records} "
          f"records, n={recovered.n}, parity vs uncrashed twin={parity:.3f}")


def main():
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(20_000, 128)).astype(np.float32)
    print(f"corpus: {corpus.shape}, devices: {len(jax.devices())}")
    for engine in ("flat", "int8", "ivf"):
        db = VectorDB(engine, metric="cosine").load(corpus)
        drive(engine, db, corpus)
    print("async continuous batching (4 concurrent clients + 1 write):")
    for engine in ("flat", "ivf_pq"):
        db = VectorDB(engine, metric="cosine").load(corpus)
        drive_async(f"async {engine}", db, corpus)
    print("durability (WAL + crash-point recovery):")
    with tempfile.TemporaryDirectory(prefix="serve_wal") as tmpdir:
        drive_durable(tmpdir, corpus[:4096, :64].copy())
    if len(jax.devices()) > 1:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        db = DistributedVectorDB(mesh, metric="cosine")
        db.load(corpus)
        drive(f"sharded x{len(jax.devices())}", db, corpus)


if __name__ == "__main__":
    enable_compile_cache()
    main()
