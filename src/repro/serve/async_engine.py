"""Async continuous-batching serving front — the "millions of users" shape.

``QueryEngine`` (``repro.serve.engine``) is a pump loop driven by the
caller's thread: correct, deterministic, and bounded by one thread doing
everything in sequence — assemble, encode, score, device_get, scatter.
``AsyncQueryEngine`` rebuilds that pipeline as the worker-threads-feeding-
device pattern from offline LLM inference engines (MaxText's offline
engine): host-side batch assembly overlaps device scoring, so the device
never waits for the host between micro-batches and the host never waits
for the device to start the next batch.

Threads and queues
------------------
::

    submitters (any threads)          batcher thread              completer thread
    ------------------------          --------------              ----------------
    submit()/submit_write()  --> [bounded request queue] -->  assemble + encode
         returns Future                (backpressure)           + db.query()
                                                                 (async dispatch)
                                                          --> [inflight queue] -->
                                                               device_get + scatter
                                                               + future.set_result

  * **Submitters** enqueue ``Request``/``WriteRequest`` jobs carrying a
    ``concurrent.futures.Future`` into ONE bounded FIFO queue
    (``max_queue``). The queue bound is the backpressure surface: policy
    ``"block"`` makes ``submit`` wait (optionally with a timeout),
    ``"reject"`` makes it raise ``BackpressureError`` immediately —
    either way the server's memory is bounded and overload is explicit,
    never an unbounded latency tail.
  * **The batcher thread** is the ONLY thread that touches the DB front.
    It drains the queue in arrival order: writes apply immediately via
    ``VectorDB.apply_write``; reads accumulate into a micro-batch until
    ``max_batch``, ``max_wait_ms``, or the next write (a write CLOSES the
    batch — same read-your-writes rule as the pump: a read never observes
    a write submitted after it, and always observes every write submitted
    before it). The batch pads up to the shared ``PLAN_BUCKETS`` ladder
    and dispatches ``db.query`` — jax dispatch is asynchronous, so this
    returns device futures, not results, and the batcher immediately
    assembles the next batch while the device scores this one.
  * **The completer thread** drains the inflight queue, performs the
    batch's one host sync (``jax.device_get``), scatters per-request
    results into their futures, and records each request's
    enqueue->result span (``serve.request``, in ``repro.obs``).
    ``max_inflight`` is an exact device-pipeline bound enforced by a slot
    semaphore: the batcher takes a slot before each dispatch and the
    completer returns it after the host sync, so at most ``max_inflight``
    batches are ever queued on the device (bounded device memory), and
    while the batcher waits for a slot, arrivals accumulate into the NEXT
    batch — batch size adapts to load. Depth 1 reproduces the sync pump's
    serve-then-collect cadence (lowest latency when host and device share
    a core); deeper pipelines pay latency for overlap on real
    accelerators.

Because the batcher serializes ALL DB access, the engine needs no locks
around the index: mutation edits host mirrors between dispatches, and jax
arrays already in flight are immutable, so a write never corrupts a
dispatched batch. Steady-state traffic hits the ``_PlanLedger`` plan cache
(one compile per (engine, bucket, k, dtype, generation) key) and never
retraces — the continuous batcher reuses exactly the compiled-plan
machinery the pump front proved out.

``latency_stats`` adds the serving gauges to the shared summary:
``queue_depth`` / ``queue_depth_max`` (bounded-queue occupancy),
``rejected`` (backpressure refusals), ``inflight`` (batches dispatched
but not yet synced), and ``rows_dispatched`` / ``rows_real`` (plan-bucket
rows sent to ``db.query`` and the real queries among them).

Spans (``repro.obs``; one batch id joins them): the batcher's
``serve.slot_wait`` (waiting for an inflight slot), ``serve.batch_wait``
(filling the batch), ``serve.dispatch`` (assemble, encode, ``db.query``);
the completer's ``serve.complete`` (host sync, scatter, resolve) and one
``serve.request`` per read, from ``submit`` to its resolve.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional

import jax
import numpy as np

from repro import obs
from repro.core.db import PLAN_BUCKETS
from repro.serve.engine import (WRITE_KINDS, Request, WriteRequest,
                                apply_db_write, assemble_queries, bucket_of,
                                query_kwargs, read_group,
                                request_latencies_ms, summarize_latencies)


class BackpressureError(RuntimeError):
    """The bounded request queue is full (policy "reject", or "block" with
    an expired timeout). The caller sheds load or retries later — the
    server never queues unboundedly."""


_SENTINEL = object()  # queue terminator: close() enqueues it LAST


class _BoundedFIFO:
    """Bounded FIFO tuned for continuous batching: ``pop_ready`` hands the
    batcher every queued job in ONE lock acquisition (``queue.Queue`` costs
    one per item — at serving rates that mutex traffic is the hot path),
    and ``put`` returns the post-insert depth so the submitter's
    queue-depth gauge needs no second acquisition."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d = collections.deque()
        mu = threading.Lock()
        self._not_empty = threading.Condition(mu)
        self._not_full = threading.Condition(mu)

    def put(self, item, timeout: Optional[float] = None) -> int:
        """Append; blocks while full (timeout=0 -> immediate). Raises
        ``queue.Full`` on timeout/full; returns the new depth."""
        with self._not_full:
            if len(self._d) >= self.maxsize:
                if timeout == 0 or not self._not_full.wait_for(
                        lambda: len(self._d) < self.maxsize, timeout):
                    raise queue.Full
            self._d.append(item)
            self._not_empty.notify()
            return len(self._d)

    def get(self, timeout: Optional[float] = None):
        """Pop one job, blocking up to timeout; raises ``queue.Empty``."""
        with self._not_empty:
            if not self._not_empty.wait_for(lambda: self._d, timeout):
                raise queue.Empty
            item = self._d.popleft()
            self._not_full.notify_all()
            return item

    def put_block(self, items: list, timeout: Optional[float] = None) -> int:
        """Append a whole block contiguously in one acquisition, blocking
        until the bound admits ALL of it (items count individually toward
        maxsize — the memory bound holds exactly). Raises ``queue.Full``
        on timeout; returns the new depth."""
        with self._not_full:
            if not self._not_full.wait_for(
                    lambda: len(self._d) + len(items) <= self.maxsize,
                    timeout):
                raise queue.Full
            self._d.extend(items)
            self._not_empty.notify()
            return len(self._d)

    def pop_ready(self, max_n: int) -> list:
        """Everything queued right now, up to max_n, in one acquisition."""
        with self._not_empty:
            n = min(max_n, len(self._d))
            items = [self._d.popleft() for _ in range(n)]
            if n:
                self._not_full.notify_all()
            return items

    def qsize(self) -> int:
        return len(self._d)  # len() is atomic under the GIL; gauge-grade


class AsyncQueryEngine:
    """Thread-safe continuous-batching front (see module docstring).

    Thread-safety guarantees:
      * ``submit`` / ``submit_write`` may be called from any number of
        threads concurrently; each returns a ``concurrent.futures.Future``
        resolving to the same result shape as ``QueryEngine.result``.
      * Ordering is QUEUE ARRIVAL order: within one submitter thread,
        program order is preserved (the queue is FIFO), so a read
        submitted after a write on the same thread observes that write
        (read-your-writes), and a read submitted before it does not.
        Across threads, concurrent submissions race for queue position —
        there is no cross-thread ordering unless the submitters
        synchronize externally (e.g. wait on the write's future).
      * The DB front itself is NOT thread-safe and is only ever touched by
        the batcher thread; callers must not call ``db.query``/mutations
        directly while the engine is running.

    Backpressure: the request queue holds at most ``max_queue`` jobs.
    ``overflow="block"`` blocks ``submit`` until space frees (or
    ``timeout`` expires -> ``BackpressureError``); ``overflow="reject"``
    raises ``BackpressureError`` immediately. Both count into the
    ``rejected`` gauge.

    Shutdown: ``close(drain=True)`` (also the context-manager exit) stops
    intake, lets the batcher finish every queued job, then joins both
    threads — no future is left pending. ``close(drain=False)`` cancels
    queued jobs instead (their futures report cancelled); jobs already
    dispatched still complete.
    """

    BUCKETS = PLAN_BUCKETS  # the shared plan-bucket ladder

    def __init__(self, db, *, encoder: Optional[Callable] = None,
                 max_batch: int = 64, max_wait_ms: float = 2.0,
                 max_queue: int = 1024, overflow: str = "block",
                 max_inflight: int = 2, start: bool = True,
                 fsync_interval_ms: Optional[float] = None):
        assert overflow in ("block", "reject"), overflow
        self.db = db
        self.encoder = encoder  # tokens -> embeddings; None = raw vectors
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.max_queue = max_queue
        self.overflow = overflow
        # group-commit knob: when the DB has a WAL attached, a write's
        # future resolves only after the fsync covering its record. 0 =
        # fsync per record; > 0 batches appends into one fsync per window
        # (the batcher flushes at the deadline, so the ack latency bound
        # is ~fsync_interval_ms); None = leave the WAL's own policy
        if fsync_interval_ms is not None:
            wal = getattr(db, "wal", None)
            assert wal is not None, "fsync_interval_ms needs a durable DB " \
                "(save_index/restore_index with durable=True first)"
            wal.fsync_interval_ms = float(fsync_interval_ms)
        self._wal_pending: List = []  # applied writes awaiting their fsync
        self._wal_deadline = 0.0     # batcher-local, armed on first pending
        self._durable_pending = 0    # len(_wal_pending) mirror, under _lock
        self._requests = _BoundedFIFO(max_queue)
        self._pending: "collections.deque" = collections.deque()  # batcher-local
        self._inflight: "queue.Queue" = queue.Queue()
        # exact device-pipeline bound: acquired before dispatch, released
        # by the completer AFTER the host sync — so at most max_inflight
        # batches are ever queued on the device. Depth 1 = the sync pump's
        # cadence (next batch accumulates while this one scores: lowest
        # latency on a single shared device); deeper pipelines help when
        # dispatch genuinely overlaps device compute.
        self.max_inflight = max_inflight
        self._slots = threading.Semaphore(max_inflight)
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._outstanding = 0  # accepted jobs whose future hasn't resolved
        self._rid = itertools.count()  # lock-free: count() is atomic enough
        self.front = obs.new_id()  # tags this front's serve.request spans
        self.writes_applied = 0
        self.rows_dispatched = 0  # plan-bucket rows sent to db.query
        self.rows_real = 0        # ... of which real queries
        self.rejected = 0
        self.queue_depth_max = 0
        self._closed = False
        self._discard = threading.Event()  # close(drain=False): cancel jobs
        self._batcher = self._completer = None
        if start:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "AsyncQueryEngine":
        """Start (or restart after close) the batcher/completer threads.
        Jobs submitted while stopped wait in the queue until started —
        which is also how tests freeze the queue to probe backpressure
        deterministically."""
        if self._batcher is not None:
            return self
        with self._lock:
            self._closed = False
        self._discard.clear()
        self._slots = threading.Semaphore(self.max_inflight)  # fresh permits
        self._completer = threading.Thread(
            target=self._complete_loop, name="serve-completer", daemon=True)
        self._batcher = threading.Thread(
            target=self._batch_loop, name="serve-batcher", daemon=True)
        self._completer.start()
        self._batcher.start()
        return self

    def close(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Stop intake and shut the pipeline down. ``drain=True`` serves
        everything already queued (no orphaned futures); ``drain=False``
        cancels still-queued jobs (dispatched batches still complete)."""
        with self._lock:
            if self._closed and self._batcher is None:
                return
            self._closed = True
        if not drain:
            self._discard.set()
        if self._batcher is None:  # never started: nothing will drain it
            self._cancel_queued()
            return
        self._requests.put(_SENTINEL)  # after every accepted job (FIFO)
        self._batcher.join(timeout)
        self._completer.join(timeout)
        self._batcher = self._completer = None
        self._cancel_queued()  # stragglers that raced the closed check

    def __enter__(self) -> "AsyncQueryEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=True)

    def _cancel_queued(self) -> None:
        while True:
            jobs = self._requests.pop_ready(self.max_queue + 1)
            if not jobs:
                return
            for job in jobs:
                if job is not _SENTINEL:
                    job.future.cancel()
                    self._resolve_one()

    # ----------------------------------------------------------- submission
    def _enqueue(self, job, timeout: Optional[float]) -> Future:
        if self._closed:
            raise RuntimeError("submit after close")
        job.rid = next(self._rid)
        with self._idle:  # count BEFORE put: a job must never resolve to -1
            self._outstanding += 1
        try:
            depth = self._requests.put(
                job, timeout=0 if self.overflow == "reject" else timeout)
        except queue.Full:
            self._resolve_one()  # roll the optimistic accept back
            with self._lock:
                self.rejected += 1
            msg = (f"request queue full ({self.max_queue}); shed load or "
                   "use overflow='block'" if self.overflow == "reject" else
                   f"request queue full ({self.max_queue}) after {timeout}s")
            raise BackpressureError(msg) from None
        if depth > self.queue_depth_max:  # benign race: high-water gauge
            self.queue_depth_max = depth
        return job.future

    def submit(self, query: np.ndarray, k: int = 10,
               timeout: Optional[float] = None, *, where=None,
               hybrid: Optional[float] = None,
               text: Optional[str] = None) -> Future:
        """Thread-safe read submission; returns a Future resolving to
        (scores (k,), ids (k,)) — bitwise the result the synchronous pump
        would produce for the same submission order. Blocks (or raises
        ``BackpressureError``, per ``overflow``) when the queue is full.
        ``where``/``hybrid``/``text`` thread through to
        ``VectorDB.query``; reads co-batch only within one
        (predicate, alpha) group (see ``read_group``)."""
        if hybrid is not None and text is None:
            raise ValueError("hybrid submit needs the query text")
        job = Request(-1, np.asarray(query), k, where, hybrid, text,
                      time.perf_counter())
        job.future = Future()
        return self._enqueue(job, timeout)

    def submit_many(self, queries, k: int = 10,
                    timeout: Optional[float] = None) -> List[Future]:
        """Amortized thread-safe submission: equivalent to
        ``[submit(q, k) for q in queries]`` — same FIFO ordering (the block
        occupies consecutive queue positions), same read-your-writes, same
        backpressure accounting (each request counts toward ``max_queue``)
        — but one queue operation per ``max_queue``-sized chunk instead of
        one per request. At high offered load the per-request queue mutex
        IS the submit-side cost; clients holding a block of requests
        should send it as a block. On timeout, futures of the requests
        that never made it in are cancelled and ``BackpressureError``
        raises; already-enqueued ones still complete."""
        if self._closed:
            raise RuntimeError("submit after close")
        t = time.perf_counter()
        jobs = []
        for q in queries:
            job = Request(next(self._rid), np.asarray(q), k, t_enqueue=t)
            job.future = Future()
            jobs.append(job)
        with self._idle:
            self._outstanding += len(jobs)
        step = max(1, self.max_queue)  # a chunk must FIT, or it deadlocks
        for i in range(0, len(jobs), step):
            chunk = jobs[i:i + step]
            try:
                depth = self._requests.put_block(
                    chunk, timeout=0 if self.overflow == "reject" else timeout)
            except queue.Full:
                stranded = jobs[i:]
                for job in stranded:
                    job.future.cancel()
                self._resolve_one(len(stranded))
                with self._lock:
                    self.rejected += len(stranded)
                raise BackpressureError(
                    f"request queue full ({self.max_queue}): block stalled "
                    f"at {i}/{len(jobs)}") from None
            if depth > self.queue_depth_max:  # benign race: high-water gauge
                self.queue_depth_max = depth
        return [job.future for job in jobs]

    def submit_write(self, kind: str, vectors=None, ids=None,
                     timeout: Optional[float] = None) -> Future:
        """Thread-safe write submission (insert/delete/upsert/compact);
        returns a Future resolving to (kind, engine return). Read-your-
        writes: any read THIS thread submits afterwards observes the
        write; other threads observe it once this future resolves (or by
        queue-arrival order before that)."""
        assert kind in WRITE_KINDS, kind
        job = WriteRequest(
            -1, kind,
            None if vectors is None else np.asarray(vectors),
            None if ids is None else np.asarray(ids), time.perf_counter())
        job.future = Future()
        return self._enqueue(job, timeout)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every accepted job has resolved (results set,
        exception set, or cancelled). True if idle was reached."""
        with self._idle:
            return self._idle.wait_for(lambda: self._outstanding == 0,
                                       timeout)

    def _resolve_one(self, n: int = 1) -> None:
        with self._idle:
            self._outstanding -= n
            if self._outstanding == 0:
                self._idle.notify_all()

    # -------------------------------------------------------------- batcher
    def _apply_write(self, w: WriteRequest) -> None:
        try:
            out = apply_db_write(self.db, w.kind, w.vectors, w.ids)
        except Exception as e:  # surface engine errors on the caller's future
            w.future.set_exception(e)
            self._resolve_one()
            return
        w.result = (w.kind, out)
        w.t_done = time.perf_counter()
        with self._lock:
            self.writes_applied += 1
        wal = getattr(self.db, "wal", None)
        if wal is not None and wal.synced_lsn < wal.last_lsn:
            # group commit: the record is written but not yet fsync'd —
            # hold the ack until the flush that makes it durable
            if not self._wal_pending:
                self._wal_deadline = (time.perf_counter()
                                      + max(wal.fsync_interval_ms, 0.0) * 1e-3)
            self._wal_pending.append(w)
            with self._lock:  # lock-protected mirror for latency_stats
                self._durable_pending += 1
            return
        w.future.set_result(w.result)
        self._resolve_one()

    def _flush_wal(self) -> None:
        """fsync the WAL and release every ack held for it (batcher thread
        only, like all DB access)."""
        if not self._wal_pending:
            return
        self.db.wal.sync()
        held, self._wal_pending = self._wal_pending, []
        with self._lock:
            self._durable_pending -= len(held)
        for w in held:
            w.future.set_result(w.result)
        self._resolve_one(len(held))

    def _get_job(self, timeout: Optional[float]):
        """Pop the next queued job, flushing the group-commit window if
        its deadline expires while we wait (held write acks must not
        stall behind an idle queue). Raises queue.Empty only once the
        CALLER's timeout is spent."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        while True:
            t = (None if deadline is None
                 else max(deadline - time.perf_counter(), 0.0))
            if self._wal_pending:
                rem = max(self._wal_deadline - time.perf_counter(), 0.0)
                t = rem if t is None else min(t, rem)
            try:
                return self._requests.get(t)
            except queue.Empty:
                if (self._wal_pending
                        and time.perf_counter() >= self._wal_deadline):
                    self._flush_wal()
                if (deadline is not None
                        and time.perf_counter() >= deadline):
                    raise

    def _dispatch(self, batch: List[Request], bid: int) -> None:
        """Assemble + encode + dispatch one read micro-batch (batch id
        ``bid``). The caller must hold an inflight slot; it travels with
        the batch and the completer releases it after the host sync (or
        the except path here, if dispatch never reaches the device).
        db.query's async dispatch returns device arrays immediately, so
        the batcher is back to accepting while the device scores."""
        rows = bucket_of(len(batch), self.BUCKETS)
        with obs.span("serve.dispatch", batch=bid, rows=rows,
                      real=len(batch)):
            k = max(r.k for r in batch)
            q = assemble_queries(batch, rows)
            try:
                qv = self.encoder(q) if self.encoder is not None else q
                scores, ids = self.db.query(qv, k=k,
                                            **query_kwargs(batch, len(q)))
            except Exception as e:
                self._slots.release()
                for r in batch:
                    r.future.set_exception(e)
                self._resolve_one(len(batch))
                return
        with self._lock:
            self.rows_dispatched += len(q)
            self.rows_real += len(batch)
        self._inflight.put((batch, bid, scores, ids))

    def _batch_loop(self) -> None:
        wait_s = self.max_wait_ms * 1e-3
        pending = self._pending  # batcher-local backlog, bulk-refilled
        done = False
        while not done:
            if pending:
                job = pending.popleft()
            else:
                job = self._get_job(None)  # block for the first job
            if job is _SENTINEL:
                break
            if self._discard.is_set():
                job.future.cancel()
                self._resolve_one()
                continue
            if isinstance(job, WriteRequest):
                self._apply_write(job)
                continue
            # take the inflight slot BEFORE filling the batch: while we
            # wait for the device pipeline to free, arrivals keep landing
            # in the queue and ride along in THIS batch — the adaptive
            # batch-size behavior that keeps latency flat under load
            with obs.span("serve.slot_wait"):
                self._slots.acquire()
            bid = obs.new_id()
            batch = [job]
            group = read_group(job)  # filter/hybrid batch-compat key
            deadline = None  # lazily armed: saturated queues never sleep
            closer = None  # the write (or sentinel) that closed the batch
            with obs.span("serve.batch_wait", batch=bid):
                while (len(batch) < self.max_batch
                       and not self._discard.is_set()):
                    if not pending:  # bulk-pop: one lock per refill
                        pending.extend(self._requests.pop_ready(
                            self.max_batch - len(batch)))
                    if pending:
                        nxt = pending.popleft()
                    else:
                        if deadline is None:
                            deadline = time.perf_counter() + wait_s
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        try:
                            nxt = self._get_job(remaining)
                        except queue.Empty:
                            break
                    if nxt is _SENTINEL:
                        done = True
                        break
                    if isinstance(nxt, WriteRequest):
                        closer = nxt  # a write CLOSES the batch: reads ahead
                        break  # of it must not observe it (read-your-writes)
                    if read_group(nxt) != group:
                        # a different (predicate, alpha) group also closes
                        # the batch; the read stays at the head for the next
                        pending.appendleft(nxt)
                        break
                    batch.append(nxt)
            self._dispatch(batch, bid)
            if closer is not None:
                if self._discard.is_set():
                    closer.future.cancel()
                    self._resolve_one()
                else:
                    self._apply_write(closer)
        self._sweep_after_sentinel()
        self._flush_wal()  # no ack survives shutdown un-fsync'd
        self._inflight.put(_SENTINEL)

    def _sweep_after_sentinel(self) -> None:
        """Serve (or, under discard, cancel) jobs found BEHIND the shutdown
        sentinel: a submitter that passed the closed check just before
        ``close()`` ran may enqueue after the sentinel — still accepted
        work, so no future may be orphaned."""
        jobs = list(self._pending)
        self._pending.clear()
        jobs.extend(self._requests.pop_ready(self.max_queue + 1))

        def flush(batch):
            self._slots.acquire()
            self._dispatch(batch, obs.new_id())

        batch: List[Request] = []
        for job in jobs:
            if job is _SENTINEL:
                continue
            if self._discard.is_set():
                job.future.cancel()
                self._resolve_one()
            elif isinstance(job, WriteRequest):
                if batch:
                    flush(batch)
                    batch = []
                self._apply_write(job)
            else:
                if batch and read_group(job) != read_group(batch[0]):
                    flush(batch)  # group change closes here too
                    batch = []
                batch.append(job)
                if len(batch) >= self.max_batch:
                    flush(batch)
                    batch = []
        if batch:
            flush(batch)

    # ------------------------------------------------------------ completer
    def _complete_loop(self) -> None:
        while True:
            item = self._inflight.get()
            if item is _SENTINEL:
                return
            batch, bid, scores, ids = item
            with obs.span("serve.complete", batch=bid):
                try:
                    scores, ids = jax.device_get((scores, ids))
                except Exception as e:
                    self._slots.release()  # device done (badly): slot frees
                    for r in batch:
                        r.future.set_exception(e)
                    self._resolve_one(len(batch))
                    continue
                self._slots.release()  # host sync done: batcher may dispatch
                t_ns = time.perf_counter_ns()
                for i, r in enumerate(batch):
                    r.result = (scores[i, : r.k], ids[i, : r.k])
                    r.t_done = t_ns * 1e-9
                    obs.record("serve.request", round(r.t_enqueue * 1e9),
                               t_ns, front=self.front, rid=r.rid, batch=bid)
                for r in batch:  # resolve AFTER recording: stats can't lag
                    r.future.set_result(r.result)
                self._resolve_one(len(batch))

    # ---------------------------------------------------------------- stats
    def latency_stats(self) -> dict:
        """The shared summary (p50/p99/mean, plan + mutation counters, and
        the ADC grid-dispatch telemetry — per-grid batch counts, autotuner
        probes + fitted crossover, schedule-cache reuse; see
        ``QueryEngine.latency_stats``) plus the continuous-batching gauges:
        ``queue_depth`` (now), ``queue_depth_max`` (high-water mark),
        ``rejected`` (backpressure refusals), ``inflight`` (batches
        dispatched, not yet synced), ``rows_dispatched`` / ``rows_real``
        (bucket rows sent to ``db.query`` / real queries among them).
        Thread-safe; callable while serving."""
        lats = request_latencies_ms(self.front)
        with self._lock:
            extra = {"queue_depth": self._requests.qsize()
                     + len(self._pending),
                     "queue_depth_max": self.queue_depth_max,
                     "rejected": self.rejected,
                     "inflight": self._inflight.qsize(),
                     "durable_pending": self._durable_pending,
                     "rows_dispatched": self.rows_dispatched,
                     "rows_real": self.rows_real}
            writes = self.writes_applied
        if not lats and not writes and not self.rejected:
            return {}
        return summarize_latencies(lats, writes, self.db, extra)
