"""Batched query serving for the vector DB — the synchronous pump front.

The paper benchmarks one query at a time; production serving amortizes the
encoder forward + MXU scoring over micro-batches. Two fronts share this
module's batching machinery:

  * ``QueryEngine`` (here) — the SYNCHRONOUS pump: the caller's thread
    drives ``pump()``; submit returns a request id, results are polled via
    ``result(rid)``. Deterministic and single-threaded, it is the oracle
    the async front is tested against.
  * ``AsyncQueryEngine`` (``repro.serve.async_engine``) — the CONTINUOUS-
    BATCHING front: thread-safe ``submit``/``submit_write`` returning
    futures, a background batcher thread draining a bounded queue, and a
    completer thread overlapping host work with device scoring. Same
    batch assembly, same write ordering, same bucket ladder — via the
    shared helpers below (``bucket_of`` / ``assemble_queries`` /
    ``apply_db_write``), so the two fronts cannot drift.

Query execution
---------------
A pumped micro-batch takes one trip through the compiled query plan:

  1. *bucketize* — the batch pads up to the shared ``BUCKETS`` ladder
     (= ``repro.core.db.PLAN_BUCKETS``) BEFORE the encoder so both the
     encoder forward and the DB search hit an already-compiled executable;
  2. *plan lookup* — ``VectorDB.query`` re-buckets (a no-op here, the sizes
     align), records a plan-cache hit/miss for the (engine, bucket, k,
     dtype) key, and dispatches the engine's jitted search — on PQ engines
     that is the fused ADC path picked by ``repro.kernels.ops.adc_topk``
     (Pallas kernel on TPU, fused jnp twin elsewhere);
  3. *one host sync* — scores and ids come back in a single device_get at
     scatter time; nothing else blocks on the device.

Write execution
---------------
``submit_write`` enqueues insert/delete/upsert/compact batches into the
SAME queue as reads. ``pump`` preserves arrival order: writes at the queue
head apply immediately (they are not latency-batched), and a read
micro-batch never reaches past the next queued write — so every read
observes exactly the writes submitted before it and never a later one
(READ-YOUR-WRITES within the pump loop), while reads between two writes
still batch together. A write that overflows a capacity bucket surfaces as
a plan miss on the next query via the shared ledger's ``plan_generation``.
Both fronts route writes through ``VectorDB.apply_write`` — the single
write entry point in ``repro.core.db`` — so write dispatch has one body.

``latency_stats`` reports enqueue->result p50/p99 over the front's
``serve.request`` spans still held by the bounded record of
``repro.obs`` (one per resolved read), plus the DB's plan-cache
counters AND its mutation counters
(inserts/deletes/upserts/compactions, from the engine's
``mutation_stats``), so a serving run can prove it stopped retracing
(misses stay flat while hits grow) and show the write mix it absorbed. The
counters come from the shared ``repro.core.db._PlanLedger`` /
``repro.core.mutable.MutationMixin``, which every front implements — the
engine serves ``VectorDB`` and the mesh fronts (``DistributedVectorDB``,
``DistributedPQ``, ``DistributedIVFPQ``) interchangeably.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro import obs
from repro.core.db import PLAN_BUCKETS

WRITE_KINDS = ("insert", "delete", "upsert", "compact")


@dataclasses.dataclass
class Request:
    rid: int
    query: np.ndarray  # (d,) embedding or token ids, per engine mode
    k: int = 10
    where: Optional[object] = None   # repro.search.meta.Predicate
    hybrid: Optional[float] = None   # BM25 fusion alpha (None = dense)
    text: Optional[str] = None       # raw query text for the lexical side
    t_enqueue: float = 0.0
    result: Optional[tuple] = None
    t_done: float = 0.0
    future: Optional[object] = None  # set by the async front only


@dataclasses.dataclass
class WriteRequest:
    rid: int
    kind: str  # one of WRITE_KINDS
    vectors: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    t_enqueue: float = 0.0
    result: Optional[tuple] = None  # (kind, returned ids / count / stats)
    t_done: float = 0.0
    future: Optional[object] = None  # set by the async front only


# --------------------------------------------------------------- shared
# batch machinery used by BOTH serving fronts (sync pump + async batcher)

def read_group(r: Request) -> tuple:
    """Batch-compatibility key for a read: requests only co-batch when
    they share the same predicate (structural key) and the same hybrid
    alpha — ``VectorDB.query`` takes ONE bitmap / one fusion weight per
    batch. Both fronts close a read run at a group change, exactly like
    they close it at a write."""
    return (None if r.where is None else r.where.key(),
            None if r.hybrid is None else float(r.hybrid))


def bucket_of(n: int, buckets=PLAN_BUCKETS) -> int:
    """Smallest ladder bucket holding n requests (caps at the top rung —
    the fronts never assemble batches past max_batch anyway)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def assemble_queries(take: List[Request], bucket: int) -> np.ndarray:
    """Stack a read micro-batch and pad it up to its bucket by repeating
    the last query — padded rows are independent of the real rows in every
    engine, so they cannot change the first len(take) results."""
    q = np.stack([r.query for r in take])
    if bucket > len(take):
        q = np.concatenate([q, np.repeat(q[-1:], bucket - len(take), axis=0)])
    return q


def query_kwargs(take: List[Request], n_rows: int) -> dict:
    """Per-batch ``VectorDB.query`` kwargs from a group-homogeneous read
    run (see ``read_group``): the shared predicate, and for hybrid the
    shared alpha plus the batch's texts padded to ``n_rows`` by repeating
    the last one (mirroring ``assemble_queries``)."""
    head = take[0]
    kw = {}
    if head.where is not None:
        kw["where"] = head.where
    if head.hybrid is not None:
        texts = [r.text for r in take]
        texts += [texts[-1]] * (n_rows - len(texts))
        kw["hybrid"] = head.hybrid
        kw["hybrid_texts"] = texts
    return kw


def apply_db_write(db, kind: str, vectors=None, ids=None):
    """Route one write batch to the DB front. Prefers the front's
    ``apply_write`` entry point (``repro.core.db``); falls back to
    attribute dispatch for duck-typed fronts that only expose the four
    mutation methods."""
    fn = getattr(db, "apply_write", None)
    if fn is not None:
        return fn(kind, vectors=vectors, ids=ids)
    if kind == "insert":
        return db.insert(vectors, ids)
    if kind == "delete":
        return db.delete(ids)
    if kind == "upsert":
        return db.upsert(vectors, ids)
    if kind == "compact":
        return db.compact()
    raise ValueError(f"unknown write kind {kind!r}; have {WRITE_KINDS}")


def request_latencies_ms(front: int) -> List[float]:
    """Enqueue->result ms of one front's reads, from its ``serve.request``
    spans that the bounded record still holds."""
    return [(s.t1 - s.t0) * 1e-6 for s in obs.spans()
            if s.name == "serve.request" and s.attrs.get("front") == front]


def summarize_latencies(latencies_ms, writes_applied: int, db,
                        extra: Optional[dict] = None) -> Dict[str, float]:
    """The one ``latency_stats`` body: enqueue->result percentiles (of
    ``request_latencies_ms``) + the DB's plan-cache and mutation counters
    (when the front keeps them). ``extra`` lets the async front append its
    queue-depth/backpressure gauges without duplicating this."""
    if not latencies_ms and not writes_applied and not extra:
        return {}
    stats = {"engine": getattr(db, "engine_name", "?")}
    if latencies_ms:
        a = np.asarray(latencies_ms)
        stats.update({"p50_ms": float(np.percentile(a, 50)),
                      "p99_ms": float(np.percentile(a, 99)),
                      "mean_ms": float(a.mean()), "n": int(a.size)})
    plans = getattr(db, "plan_stats", None)
    if plans is not None:  # compiled-plan reuse (misses = first compiles)
        stats["plan_hits"] = int(plans["hits"])
        stats["plan_misses"] = int(plans["misses"])
    muts = getattr(db, "mutation_stats", None)
    if muts is not None:  # write/compaction counters (rows applied)
        stats.update({f"write_{k}": int(v) for k, v in muts.items()})
    wal = getattr(db, "wal_stats", None)
    if wal is not None:  # durability counters (records vs fsyncs = the
        # group-commit amortization; synced_lsn lags last_lsn by held acks)
        stats.update({f"wal_{k}": int(v) for k, v in wal.items()})
    adc = getattr(db, "adc_stats", None)
    if adc is not None and adc.get("batches"):
        # ADC grid dispatch: which grid served each batch, how many
        # batches went to the autotuner's measured probe, the fitted
        # sharing crossover it dispatches on, schedule-cache reuse, and
        # the mean block-sharing factor / effective nprobe observed
        b = adc["batches"]
        stats["adc_blocked"] = int(adc["blocked"])
        stats["adc_per_query"] = int(adc["per_query"])
        stats["adc_run_resident"] = int(adc.get("run_resident", 0))
        stats["adc_probes"] = int(adc.get("probes", 0))
        if adc.get("crossover") is not None:
            stats["adc_crossover_sharing"] = float(adc["crossover"])
        if "sched_cache_hits" in adc:
            stats["adc_sched_cache_hits"] = int(adc["sched_cache_hits"])
            stats["adc_sched_cache_misses"] = int(adc["sched_cache_misses"])
        stats["adc_sharing_factor"] = float(adc["sharing_sum"] / b)
        stats["adc_effective_nprobe"] = float(adc["eff_nprobe_sum"] / b)
        # grid steps of the batches whose visit table came to the host,
        # and those that visit a real (not the all-pad) block
        stats["adc_steps"] = int(adc["steps"])
        stats["adc_real_steps"] = int(adc["real_steps"])
    flt = getattr(db, "filter_stats", None)
    if flt is not None:
        # filtered/hybrid telemetry: batches that carried a predicate,
        # cumulative bitmap compile time, where the selectivities landed,
        # hybrid fusion count, and IVF nprobe boosts taken
        stats["filtered_batches"] = int(flt["filtered_batches"])
        stats["filter_bitmap_ms"] = float(flt["bitmap_build_ms"])
        stats["hybrid_merges"] = int(flt["hybrid_merges"])
        stats["filter_nprobe_boosts"] = int(flt["nprobe_boosts"])
        for kk, v in flt["selectivity_hist"].items():
            stats[f"filter_sel_{kk}"] = int(v)
    if extra:
        stats.update(extra)
    return stats


class QueryEngine:
    """The synchronous pump front (see module docstring).

    NOT thread-safe: one thread owns the engine and drives ``pump()`` —
    which is exactly what makes it the deterministic oracle for
    ``AsyncQueryEngine`` parity tests. For concurrent submitters, bounded
    queues, and backpressure, use the async front.
    """

    BUCKETS = PLAN_BUCKETS  # one ladder for encoder pads and DB query plans

    def __init__(self, db, *, encoder: Optional[Callable] = None,
                 max_batch: int = 64, max_wait_ms: float = 2.0):
        self.db = db
        self.encoder = encoder  # tokens -> embeddings; None = raw vectors
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.queue: List = []  # Requests and WriteRequests, arrival order
        self.done: Dict[int, object] = {}
        self._next_id = 0
        self.front = obs.new_id()  # tags this front's serve.request spans
        self.writes_applied = 0

    def submit(self, query: np.ndarray, k: int = 10, *,
               where=None, hybrid: Optional[float] = None,
               text: Optional[str] = None) -> int:
        """Enqueue one read; returns the request id to poll via
        ``result``. The query is captured as-is ((d,) embedding, or token
        ids when the engine has an encoder); nothing runs until the next
        ``pump``. ``where``/``hybrid``/``text`` thread through to
        ``VectorDB.query(where=..., hybrid=...)``; reads only co-batch
        with reads sharing the same (predicate, alpha) group."""
        if hybrid is not None and text is None:
            raise ValueError("hybrid submit needs the query text")
        rid = self._next_id
        self._next_id += 1
        self.queue.append(Request(rid, np.asarray(query), k, where, hybrid,
                                  text, time.perf_counter()))
        return rid

    def submit_write(self, kind: str, vectors=None, ids=None) -> int:
        """Enqueue a write batch (insert/delete/upsert/compact). Writes keep
        arrival order relative to reads: a read submitted after this write
        is guaranteed to observe it, and a read submitted before it is
        guaranteed NOT to (read-your-writes, both directions)."""
        assert kind in WRITE_KINDS, kind
        rid = self._next_id
        self._next_id += 1
        self.queue.append(WriteRequest(
            rid, kind,
            None if vectors is None else np.asarray(vectors),
            None if ids is None else np.asarray(ids), time.perf_counter()))
        return rid

    def _apply_write(self, w: WriteRequest) -> None:
        out = apply_db_write(self.db, w.kind, w.vectors, w.ids)
        w.result = (w.kind, out)
        w.t_done = time.perf_counter()
        self.done[w.rid] = w
        self.writes_applied += 1

    def pump(self, *, force: bool = False) -> int:
        """Apply due writes, then run one read micro-batch if due. Returns
        the number of READ requests served; writes at the queue head always
        apply (they are not latency-batched), and the read batch stops at
        the next queued write so it cannot observe the future."""
        while self.queue and isinstance(self.queue[0], WriteRequest):
            self._apply_write(self.queue.pop(0))
        if not self.queue:
            return 0
        oldest_wait = (time.perf_counter() - self.queue[0].t_enqueue) * 1e3
        group = read_group(self.queue[0])
        n_reads = 0  # contiguous same-group run of reads at the head
        while (n_reads < len(self.queue) and n_reads < self.max_batch
               and isinstance(self.queue[n_reads], Request)
               and read_group(self.queue[n_reads]) == group):
            n_reads += 1
        # a write (or a different filter/hybrid group) right behind the
        # run CLOSES the batch: the run can never grow past it, so waiting
        # out max_wait_ms would only stall these reads and what's behind
        closed = n_reads < len(self.queue) and n_reads < self.max_batch
        if (not force and not closed and n_reads < self.max_batch
                and oldest_wait < self.max_wait_ms):
            return 0
        take = self.queue[:n_reads]
        self.queue = self.queue[n_reads:]
        n = len(take)
        k = max(r.k for r in take)
        q = assemble_queries(take, bucket_of(n, self.BUCKETS))
        qv = self.encoder(q) if self.encoder is not None else q
        scores, ids = self.db.query(qv, k=k, **query_kwargs(take, len(q)))
        scores, ids = jax.device_get((scores, ids))  # the batch's one host sync
        t_ns, batch = time.perf_counter_ns(), obs.new_id()
        for i, r in enumerate(take):
            r.result = (scores[i, : r.k], ids[i, : r.k])
            r.t_done = t_ns * 1e-9
            self.done[r.rid] = r
            obs.record("serve.request", round(r.t_enqueue * 1e9), t_ns,
                       front=self.front, rid=r.rid, batch=batch)
        return n

    def drain(self) -> int:
        served = 0
        while self.queue:
            served += self.pump(force=True)
        return served

    def result(self, rid: int):
        """Completed result for a request id, or None while pending. Reads
        resolve to (scores (k,), ids (k,)); writes to (kind, engine
        return — assigned ids for insert/upsert, live-row count for
        delete, stats dict for compact)."""
        r = self.done.get(rid)
        return None if r is None else r.result

    def latency_stats(self) -> Dict[str, float]:
        """Enqueue->result p50/p99/mean per served read + the DB front's
        plan-cache (``plan_hits``/``plan_misses``) and mutation
        (``write_*``) counters. Empty dict before any request resolves."""
        return summarize_latencies(request_latencies_ms(self.front),
                                   self.writes_applied, self.db)
