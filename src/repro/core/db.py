"""VectorDB — Thistle's load/query trait as the framework's deployment API.

    db = VectorDB(engine="flat|int8|ivf|lsh|graph", metric="cosine|l2|dot")
    db.load(vectors)                      # or db.load_texts(texts, encoder)
    scores, ids = db.query(q, k=10)       # or db.query_texts(["..."], k=10)
    ids = db.insert(new_vectors)          # online mutation (mutable engines)
    db.delete(ids); db.upsert(vs, ids); db.compact()

Mirrors the paper's Rust Trait interface (load + query per engine) with a
registry so new engines compose in, plus the MUTATION LIFECYCLE
(repro.core.mutable): insert/delete/upsert/compact forward to the engine,
and the front tracks the engine's ``shape_key`` so a capacity-bucket
overflow bumps ``plan_generation`` — the plan ledger then counts the
retrace as a miss while steady-state inserts (contents change, shapes
don't) keep hitting the same compiled plans. Under a mesh,
``DistributedVectorDB`` shards corpus rows across every device and runs the
SPMD merge program in ``repro.core.distributed``; ``DistributedPQ`` is its
compressed twin — uint8 PQ codes sharded, LUTs replicated, 8-32x less HBM
per device — and ``DistributedIVFPQ`` range-shards the block-aligned
inverted lists so per-device QUERY WORK (not just bytes) scales with the
probed candidate count instead of N/S; its inserts route each row's spilled
blocks onto the shard owning the target cluster's slab.

Query plans: every engine's search is a jitted program whose executable is
keyed on (batch shape, k, dtype), so a naive front end retraces for every
distinct caller batch size. Every query front (``VectorDB`` AND the mesh
fronts, via the shared ``_PlanLedger``) therefore canonicalizes the batch
to a fixed ladder of bucket sizes (``PLAN_BUCKETS``, shared with
serve.QueryEngine) before dispatching, and keeps a plan ledger: a miss is
the first use of a (engine, bucket, k, dtype, generation) plan by THIS
front (the process-wide jit cache may already hold the executable if
another instance compiled the same shapes), every later call at the same
key is a hit that reuses the cached executable. ``plan_stats`` feeds
QueryEngine.latency_stats.
"""
from __future__ import annotations

import functools
import os
import time
import warnings
from typing import Callable, Dict, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt
from repro import obs
from repro.core import distances as D
from repro.core import distributed as dist
from repro.core.flat import FlatIndex
from repro.core.graph import GraphIndex
from repro.core.ivf import BlockListLayout, IVFIndex, ScheduleCache
from repro.core.lsh import LSHIndex
from repro.core.mutable import MutationMixin
from repro.core.pq import (IVFPQIndex, PQIndex, adc_tables, encode_ivf_pq,
                           expand_visit, pq_encode, probe_luts, train_ivf_pq,
                           train_pq)
from repro.core.quant import Int8FlatIndex
from repro.core.wal import WriteAheadLog
from repro.ft.faults import crashpoint
from repro.kernels import ops as kops
from repro.search.lexical import BM25Index, hybrid_merge
from repro.search.meta import MetadataStore, Predicate, filter_hash

ENGINES: Dict[str, Type] = {
    "flat": FlatIndex,      # paper: Iterative (exact), cosine + l2
    "ivf": IVFIndex,        # paper: HNSW adaptation (a) — coarse quantizer
    "graph": GraphIndex,    # paper: HNSW adaptation (b) — graph beam search
    "lsh": LSHIndex,        # paper: LSH
    "int8": Int8FlatIndex,  # beyond-paper: quantized exact
    "pq": PQIndex,          # beyond-paper: product-quantized ADC (m B/row)
    "ivf_pq": IVFPQIndex,   # beyond-paper: IVF buckets of PQ residuals
}


def register_engine(name: str, cls: Type) -> None:
    ENGINES[name] = cls


# jit-plan bucket ladder: batches pad up to the next bucket so one compiled
# executable serves every batch size below it (serve.QueryEngine aliases this)
PLAN_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)


class _WriteFront:
    """The serving layer's single write entry point: one call dispatches any
    of the four mutation kinds, so the synchronous pump and the async
    batcher (repro.serve) share one write body instead of each hand-rolling
    the kind->method mapping. Like the mutation methods themselves this is
    NOT thread-safe — the serving fronts serialize all writes (and writes
    against queries) on one thread."""

    WRITE_KINDS = ("insert", "delete", "upsert", "compact")

    def apply_write(self, kind: str, vectors=None, ids=None, meta=None):
        """Apply one write batch by kind. Returns the mutation's native
        result: assigned ids (insert/upsert), live-row count (delete), or
        the stats dict (compact). ``meta`` (optional columnar metadata
        dict, the WAL form) is forwarded only when present, so fronts
        whose mutation methods predate metadata stay compatible."""
        if kind == "insert":
            if meta is not None:
                return self.insert(vectors, ids, meta=meta)
            return self.insert(vectors, ids)
        if kind == "delete":
            return self.delete(ids)
        if kind == "upsert":
            if meta is not None:
                return self.upsert(vectors, ids, meta=meta)
            return self.upsert(vectors, ids)
        if kind == "compact":
            return self.compact()
        raise ValueError(
            f"unknown write kind {kind!r}; have {self.WRITE_KINDS}")


class _PlanLedger:
    """Jit-plan bookkeeping shared by every query front (single-host AND
    mesh): canonicalize the batch to the PLAN_BUCKETS ladder, count
    hit/miss per (engine, bucket, k, dtype, generation) plan key, pad the
    batch up to its bucket. A miss is the first use of a plan key by THIS
    front (the process-wide jit cache may already hold the executable);
    serve's ``latency_stats`` surfaces the counters via ``plan_stats``.
    ``plan_generation`` bumps only when a mutation overflows a capacity
    bucket (device shapes actually changed) — steady-state inserts keep the
    same keys, so their queries stay hits."""

    def _plan_init(self):
        self.plan_buckets = PLAN_BUCKETS
        self.plan_generation = 0
        self._plans = set()
        self.plan_stats = {"hits": 0, "misses": 0}
        # host-side twin of the jit-plan cache: built block schedules for
        # the grouped ADC grids, keyed (bucket, generation, nprobe) by the
        # engine (repro.core.ivf.ScheduleCache — content-verified, so a
        # changed batch or mutated index just misses)
        self.sched_cache = ScheduleCache()

    def _bucket(self, n: int) -> int:
        for b in self.plan_buckets:
            if n <= b:
                return b
        top = self.plan_buckets[-1]  # bulk path: next multiple of the cap
        return -(-n // top) * top

    def _plan_salt(self) -> tuple:
        """Engine-config components of the plan key beyond shape/dtype —
        anything that changes WHICH executable a query compiles (e.g. the
        ADC grid mode or adaptive-nprobe masking) without changing array
        shapes. Fronts override; default is no extra salt."""
        return ()

    def _plan_batch(self, q, kk: int):
        """Record the plan key and pad q up to its bucket. Returns
        (padded q, original Q): padded rows repeat the last query, so the
        first Q result rows are unchanged and get sliced back out."""
        Q = q.shape[0]
        bucket = self._bucket(Q)
        key = (self.engine_name, bucket, kk, str(q.dtype),
               self.plan_generation) + self._plan_salt()
        if key in self._plans:
            self.plan_stats["hits"] += 1
        else:
            self.plan_stats["misses"] += 1
            self._plans.add(key)
        if bucket > Q:
            pad = jnp.broadcast_to(q[-1:], (bucket - Q,) + q.shape[1:])
            q = jnp.concatenate([q, pad])
        return q, Q


def _empty_result(Q: int, k: int):
    """Well-formed result for an empty (or fully-deleted) index: zero-wide
    score/id rows, one per query — downstream slicing (serve scatters
    ``result[:k]``) degrades gracefully instead of a reshape error."""
    return (jnp.zeros((Q, 0), jnp.float32), jnp.full((Q, 0), -1, jnp.int32))


class VectorDB(_PlanLedger, _WriteFront):
    """Single-host front end over the engine registry.

    Thread-safety: a VectorDB is single-writer/single-reader — queries and
    mutations share host mirrors and the lazy device-sync flag, so callers
    must serialize access. The serving fronts do exactly that: the
    synchronous ``QueryEngine`` runs on the caller's thread, and the async
    front's batcher thread is the ONLY thread that ever touches the DB
    (see ``repro.serve.async_engine``)."""

    def __init__(self, engine: str = "flat", metric: str = "cosine", **engine_kwargs):
        if engine not in ENGINES:
            raise KeyError(f"unknown engine {engine!r}; have {sorted(ENGINES)}")
        assert metric in D.METRICS, metric
        self.engine_name = engine
        self.metric = metric
        self._engine_kwargs = dict(engine_kwargs)  # fresh-engine rebuilds
        self.index = ENGINES[engine](metric=metric, **engine_kwargs)
        self.n = 0
        self._loaded = False
        self._texts = None
        self.wal = None  # attached by save_index/restore_index(durable=True)
        self._wal_replaying = False
        # snapshot-cadence policy (attach_wal): auto-truncate the log by
        # size/age instead of only at explicit save_index calls
        self._snap_every_bytes = None
        self._snap_every_s = None
        self._snap_dir = None
        self._snap_bytes_mark = 0
        self._snap_t_mark = time.monotonic()
        self._snap_step = 0
        self._auto_snapshots = 0
        # filtered + hybrid search state (repro.search): metadata columns
        # keyed by slot id, an optional frozen BM25 index, and the current
        # batch's filter context (filter crc32, nprobe boost) for the plan
        # ledger — None outside a filtered query
        self.metastore = MetadataStore()
        self.lexical = None
        self._filter_ctx = None
        self._filter_stats = {
            "filtered_batches": 0, "bitmap_build_ms": 0.0,
            "selectivity_hist": {"<=1%": 0, "<=10%": 0, "<=50%": 0,
                                 ">50%": 0},
            "hybrid_merges": 0, "nprobe_boosts": 0}
        self._plan_init()

    def _plan_salt(self) -> tuple:
        # the ADC grid mode and adaptive-nprobe masking each change the
        # compiled search program on the same shapes — distinct plan keys;
        # the filter context separates filtered batches in the ledger (the
        # nprobe boost really is a different compiled program; the bitmap
        # itself is data, but per-filter counters are what serve reports)
        return (getattr(self.index, "adc_mode", None),
                getattr(self.index, "adaptive_nprobe", None),
                self._filter_ctx)

    # ----------------------------------------------------------- load
    def load(self, vectors, meta=None) -> "VectorDB":
        """Index a corpus. ``meta`` (optional) attaches metadata to rows
        0..N-1: either a columnar dict ({column: [v0..vN-1]}) or a list of
        per-row dicts — see ``repro.search.meta``. Load is not WAL-logged
        (it precedes durability), so its metadata rides snapshots only."""
        if not hasattr(vectors, "shape"):
            vectors = np.asarray(vectors)
        assert vectors.ndim == 2, vectors.shape
        self.index.load(vectors)  # host rows stay on the host: the engine
                                  # decides what goes to the device
        self.n = vectors.shape[0]
        self._loaded = True
        self.metastore = MetadataStore()  # fresh corpus, fresh id space
        if meta is not None:
            self.metastore.put(np.arange(self.n), meta)
        return self

    def load_texts(self, texts, encoder: Callable, batch_size: int = 128) -> "VectorDB":
        """Embed texts with `encoder(list[str]) -> (B, d)` then index them."""
        embs = []
        for i in range(0, len(texts), batch_size):
            embs.append(jnp.asarray(encoder(texts[i:i + batch_size])))
        self._texts = list(texts)
        return self.load(jnp.concatenate(embs, axis=0))

    # ----------------------------------------------------------- mutation
    def _mutate(self, op: str, *args, meta=None):
        if not self._loaded:
            raise RuntimeError(f"{op} before load")
        fn = getattr(self.index, op, None)
        if fn is None:
            raise NotImplementedError(
                f"engine {self.engine_name!r} does not support {op}")
        before = getattr(self.index, "shape_key", None)
        out = fn(*args)
        if getattr(self.index, "shape_key", None) != before:
            # capacity bucket overflowed: the next query at any batch size
            # compiles fresh executables — make the ledger say so
            self.plan_generation += 1
        self.n = getattr(self.index, "size", self.n)
        # metadata syncs with the id outcome of the mutation: insert/upsert
        # attach rows at the engine-assigned ids (upsert replaces, so stale
        # fields don't linger; upsert WITHOUT meta keeps the old metadata —
        # replay re-applies the same choice), delete clears presence,
        # compact is a no-op (ids are stable addresses)
        norm_meta = None
        if meta is not None and op in ("insert", "upsert"):
            norm_meta = self.metastore.put(np.asarray(out), meta,
                                           replace=(op == "upsert"))
        elif op == "delete":
            self.metastore.delete(np.asarray(args[0]))
        if (self.wal is not None and not self._wal_replaying
                and op in WriteAheadLog.KINDS):
            self._wal_log(op, args, out, norm_meta)
            self._maybe_auto_snapshot()
        return out

    def _maybe_auto_snapshot(self) -> None:
        """Enforce the snapshot-cadence policy after a logged mutation:
        when the log has grown past ``snapshot_every_bytes`` (or aged past
        ``snapshot_every_s``) since the last snapshot, take a durable
        snapshot — which truncates the log — without waiting for an
        explicit ``save_index``. Bounds both recovery replay time and log
        disk footprint under a pure write workload."""
        if self._snap_every_bytes is None and self._snap_every_s is None:
            return
        grown = self.wal.bytes_written - self._snap_bytes_mark
        aged = time.monotonic() - self._snap_t_mark
        if ((self._snap_every_bytes is not None
             and grown >= self._snap_every_bytes)
                or (self._snap_every_s is not None
                    and aged >= self._snap_every_s)):
            self.save_index(self._snap_dir, self._snap_step + 1,
                            durable=True)
            self._auto_snapshots += 1

    def _wal_log(self, op: str, args, out, meta=None) -> None:
        """Append the applied mutation to the WAL. Insert logs the ids the
        engine ASSIGNED (not the caller's None), so replay re-applies with
        explicit ids and the recovered id space is bit-identical. ``meta``
        is the NORMALIZED columnar metadata dict (MetadataStore.put's
        return), so replay re-attaches exactly what was stored. reserve
        is not logged: capacity pre-sizing changes no query result, and
        replayed mutations re-grow capacity deterministically."""
        if op == "insert":
            self.wal.append("insert", vectors=np.asarray(args[0]),
                            ids=np.asarray(out), meta=meta)
        elif op == "delete":
            self.wal.append("delete", ids=np.asarray(args[0]))
        elif op == "upsert":
            self.wal.append("upsert", vectors=np.asarray(args[0]),
                            ids=np.asarray(args[1]), meta=meta)
        elif op == "compact":
            self.wal.append("compact")

    def insert(self, vectors, ids=None, meta=None) -> np.ndarray:
        """Append rows online; returns the assigned (stable) ids — ids are
        never reused or renumbered, so results stay meaningful across
        mutations. ``meta`` (optional; columnar dict or per-row dicts)
        attaches filterable metadata at the assigned ids. Applies to host
        mirrors immediately; the next query uploads the dirty arrays once
        (lazy device sync). Not thread-safe: serialize against queries
        (the serve fronts do)."""
        return self._mutate("insert", vectors, ids, meta=meta)

    def delete(self, ids) -> int:
        """Tombstone rows by id; returns how many were live. Deleted slots
        ride through the fused kernels as the -1 pad sentinel (query work
        does not shrink until ``compact``), and the ids stay retired
        forever. Same thread-safety rule as ``insert``."""
        return self._mutate("delete", ids)

    def upsert(self, vectors, ids, meta=None) -> np.ndarray:
        """Re-encode existing ids in place (update-or-resurrect). With
        ``meta``, the rows' metadata is REPLACED wholesale (no field
        merge); without it the existing metadata is kept. Same
        thread-safety rule as ``insert``."""
        return self._mutate("upsert", vectors, ids, meta=meta)

    def compact(self) -> dict:
        """Reclaim tombstoned query work (engine-specific; see engines).
        Repacks layout structures without changing capacity buckets, so
        compiled query plans survive. Same thread-safety rule as
        ``insert``."""
        return self._mutate("compact")

    def reserve(self, *args):
        """Pre-size the engine's capacity buckets for a planned ingest
        volume, so the insert stream stays inside one shape bucket (any
        immediate shape change is counted against the plan ledger here,
        not blamed on the first post-grow query)."""
        return self._mutate("reserve", *args)

    @property
    def mutation_stats(self) -> Optional[dict]:
        return getattr(self.index, "mutation_stats", None)

    @property
    def generation(self) -> int:
        return getattr(self.index, "generation", 0)

    # ----------------------------------------------------------- query
    # engines whose query() composes the predicate bitmap into validity
    # (invariant 6). graph/lsh candidate generation is structural (beam /
    # hash probes), so post-hoc masking would silently return < k under
    # selective filters — they refuse rather than degrade.
    FILTERABLE = ("flat", "int8", "ivf", "pq", "ivf_pq")

    def enable_lexical(self, texts=None, tokens=None, *,
                       vocab_size: int = 30_000, seq_len: int = 64,
                       k1: float = 1.5, b: float = 0.75) -> BM25Index:
        """Build the BM25 half of hybrid search over the indexed corpus
        (row i of ``texts``/``tokens`` = slot id i, the load order).
        Defaults to the texts remembered by ``load_texts``. The lexical
        index is FROZEN at build time (see repro.search.lexical); rebuild
        after mutations if lexical coverage of new rows matters."""
        if texts is None and tokens is None:
            if self._texts is None:
                raise ValueError(
                    "enable_lexical needs texts/tokens (or a prior "
                    "load_texts)")
            texts = self._texts
        if tokens is not None:
            self.lexical = BM25Index.from_tokens(tokens, k1=k1, b=b)
        else:
            self.lexical = BM25Index.from_texts(
                list(texts), vocab_size=vocab_size, seq_len=seq_len,
                k1=k1, b=b)
        return self.lexical

    def _filter_bitmap(self, where: Predicate):
        """Predicate -> (bitmap over the id space, engine kwargs). Also
        decides the selectivity-aware nprobe boost for the IVF engines:
        probing C/nprobe more lists roughly holds the CANDIDATE count
        (survivors of the bitmap) steady as selectivity drops, clamped to
        4x so a 0.1% filter can't recompile a 1000x-wider program."""
        if self.engine_name not in self.FILTERABLE:
            raise NotImplementedError(
                f"engine {self.engine_name!r} does not support filtered "
                f"queries (have {self.FILTERABLE})")
        t0 = time.perf_counter()
        n_ids = int(getattr(self.index, "next_id", self.n) or self.n)
        allowed = self.metastore.mask(where, n_ids)
        fs = self._filter_stats
        fs["bitmap_build_ms"] += (time.perf_counter() - t0) * 1e3
        fs["filtered_batches"] += 1
        sel = float(allowed.sum()) / max(self.n, 1)
        hist = fs["selectivity_hist"]
        hist["<=1%" if sel <= 0.01 else "<=10%" if sel <= 0.10
             else "<=50%" if sel <= 0.50 else ">50%"] += 1
        extra = {"allowed": jnp.asarray(allowed)}
        boost = 1
        if self.engine_name in ("ivf", "ivf_pq"):
            boost = int(np.clip(np.round(1.0 / max(sel, 1e-9)), 1, 4))
            extra["nprobe_boost"] = boost
            if boost > 1:
                fs["nprobe_boosts"] += 1
        self._filter_ctx = (filter_hash(where), boost)
        return allowed, extra

    def _hybrid_fuse(self, scores, ids, alpha, texts, tokens, allowed,
                     kk: int):
        """Fuse the dense result with BM25 over the same queries (and the
        same predicate bitmap) via repro.search.lexical.hybrid_merge."""
        if self.lexical is None:
            raise RuntimeError(
                "hybrid query before enable_lexical(...)")
        Q = scores.shape[0]
        if tokens is None:
            if texts is None:
                raise ValueError(
                    "hybrid query needs hybrid_texts or hybrid_tokens")
            tokens = self.lexical.tokenize(list(texts))
        tokens = np.asarray(tokens)
        assert tokens.shape[0] >= Q, (tokens.shape, Q)
        lex_s, lex_i = self.lexical.score(tokens[:Q], k=kk,
                                          allowed=allowed)
        self._filter_stats["hybrid_merges"] += 1
        return hybrid_merge(np.asarray(scores), np.asarray(ids),
                            lex_s, lex_i, alpha=float(alpha), k=kk)

    def query(self, q, k: int = 10, *, bucketize: bool = True,
              where: Optional[Predicate] = None,
              hybrid: Optional[float] = None, hybrid_texts=None,
              hybrid_tokens=None):
        """q: (d,) or (Q, d) -> (scores (Q, k) f32, ids (Q, k) int32).

        ``bucketize`` pads Q up to the plan-bucket ladder so the engine's
        jitted search compiles once per (bucket, k, dtype) plan instead of
        once per caller batch size; rows are independent in every engine, so
        the padded rows (repeats of the last query) cannot change the first
        Q results, which are sliced back out lazily (no host sync).

        ``where`` (a ``repro.search.meta`` Predicate) restricts results to
        matching rows: the predicate compiles to one bitmap over the id
        space, and filtered-out slots ride through the engines as the -1
        pad sentinel the fused kernels already knock out (invariant 6) —
        same compiled executables, bit-identical when the bitmap is
        all-true. ``hybrid=alpha`` fuses the dense scores with BM25 over
        ``hybrid_texts``/``hybrid_tokens`` (needs ``enable_lexical``):
        alpha=1 is dense-only, 0 lexical-only.

        An empty index — never inserted into, or fully deleted — returns
        (Q, 0)-shaped results rather than erroring: emptiness is a normal
        state for a database, unlike querying before ``load``.

        The call is one ``db.query`` span (``repro.obs``) with the rows
        dispatched (``bucket``) and whether the plan was new (``plan_miss``).
        """
        if not self._loaded:
            raise RuntimeError("query before load")
        with obs.span("db.query") as sp:
            return self._query(sp, q, k, bucketize, where, hybrid,
                               hybrid_texts, hybrid_tokens)

    def _query(self, sp, q, k, bucketize, where, hybrid, hybrid_texts,
               hybrid_tokens):
        q = jnp.atleast_2d(jnp.asarray(q))
        kk = min(k, self.n)
        if kk <= 0:
            return _empty_result(q.shape[0], k)
        allowed, extra = (None, {})
        self._filter_ctx = None
        if where is not None:
            allowed, extra = self._filter_bitmap(where)
        try:
            if bucketize:
                misses = self.plan_stats["misses"]
                q, Q = self._plan_batch(q, kk)
                sp.attrs["plan_miss"] = self.plan_stats["misses"] > misses
                if hasattr(self.index, "sched_cache"):
                    # hand the engine the ledger's schedule cache + this
                    # batch's plan context; the engine appends nprobe to
                    # complete the key
                    self.index.sched_cache = self.sched_cache
                    self.index._sched_ctx = (self._bucket(Q),
                                             self.plan_generation)
            else:
                Q = q.shape[0]
            sp.attrs["bucket"] = q.shape[0]
            scores, ids = self.index.query(q, k=kk, **extra)
            scores, ids = scores[:Q], ids[:Q]
        finally:
            self._filter_ctx = None
        if hybrid is not None:
            scores, ids = self._hybrid_fuse(scores, ids, hybrid,
                                            hybrid_texts, hybrid_tokens,
                                            allowed, kk)
        return scores, ids

    def query_texts(self, texts, encoder: Callable, k: int = 10):
        q = jnp.asarray(encoder(list(texts)))
        scores, ids = self.query(q, k)
        if self._texts is not None:
            hits = [[self._texts[j] for j in row] for row in ids.tolist()]
            return scores, ids, hits
        return scores, ids, None

    # ----------------------------------------------------------- persistence
    def attach_wal(self, directory: str, fsync_interval_ms: float = 0.0,
                   *, after_lsn: int = 0, replay: bool = False,
                   snapshot_every_bytes: Optional[int] = None,
                   snapshot_every_s: Optional[float] = None) -> int:
        """Open (or create) ``<directory>/wal.log`` and start logging every
        mutation through it. With ``replay=True`` the intact records with
        lsn > after_lsn are re-applied through ``apply_write`` first (the
        recovery path); re-logging is suppressed during replay — the
        records are already in the log. Returns the replayed count.

        ``snapshot_every_bytes`` / ``snapshot_every_s`` set the snapshot
        cadence: after any logged mutation that pushes the log past the
        size (or age) bound since the last snapshot, the front takes a
        durable snapshot into ``directory`` on its own — truncating the
        log — so replay length stays bounded without explicit
        ``save_index`` calls (``wal_stats['auto_snapshots']`` counts
        them). Requires a persistence-capable engine."""
        if ((snapshot_every_bytes is not None or snapshot_every_s is not None)
                and getattr(self.index, "state_dict", None) is None):
            raise NotImplementedError(
                f"snapshot cadence needs persistence, which engine "
                f"{self.engine_name!r} does not support")
        path = os.path.join(directory, "wal.log")
        self.wal, records = WriteAheadLog.open(
            path, fsync_interval_ms=fsync_interval_ms, after_lsn=after_lsn)
        self._snap_every_bytes = snapshot_every_bytes
        self._snap_every_s = snapshot_every_s
        self._snap_dir = directory
        self._snap_bytes_mark = self.wal.bytes_written
        self._snap_t_mark = time.monotonic()
        steps = ckpt.valid_steps(directory)
        self._snap_step = max(steps) if steps else 0
        n = 0
        if replay:
            self._wal_replaying = True
            try:
                for rec in records:
                    self.apply_write(rec.kind, vectors=rec.vectors,
                                     ids=rec.ids, meta=rec.meta)
                    n += 1
            finally:
                self._wal_replaying = False
        return n

    def save_index(self, directory: str, step: int = 0, *,
                   durable: bool = False,
                   fsync_interval_ms: float = 0.0) -> str:
        """Snapshot the engine's trained state (codebooks/codes/centroids —
        plus tombstone state and the generation stamp on mutable engines)
        through the sharding-aware checkpoint store. Engines opt in by
        implementing ``state_dict()``.

        ``durable=True`` attaches (or keeps) the directory's write-ahead
        log: the manifest stamps the WAL high-water mark ``wal_lsn``, and
        after the snapshot commits the log is truncated to the records
        past it. A crash between snapshot rename and truncation is safe —
        restore skips records at or below the stamped lsn."""
        state_dict = getattr(self.index, "state_dict", None)
        if state_dict is None:
            raise NotImplementedError(
                f"engine {self.engine_name!r} does not support persistence")
        if durable and self.wal is None:
            os.makedirs(directory, exist_ok=True)
            self.attach_wal(directory, fsync_interval_ms)
        if self.wal is not None:
            # the manifest's wal_lsn stamp only means something for the log
            # sitting NEXT TO the snapshot — stamping (and truncating) a log
            # in another directory would strand the post-snapshot records
            # where no restore of this directory can find them
            expected = os.path.join(directory, "wal.log")
            if os.path.abspath(self.wal.path) != os.path.abspath(expected):
                raise ValueError(
                    f"save_index: WAL is attached at {self.wal.path!r} but "
                    f"the snapshot targets {directory!r}; write durable "
                    "snapshots to the WAL's own directory")
        meta = {"engine": self.engine_name, "metric": self.metric,
                "generation": int(self.generation),
                "live_rows": int(getattr(self.index, "size", self.n))}
        if self.wal is not None:
            self.wal.sync()  # the snapshot must not outrun the log
            meta["wal_lsn"] = int(self.wal.last_lsn)
        tree = dict(state_dict())
        # metadata columns ride the same snapshot as extra leaves, so a
        # restore serves identical filtered results (invariant 6 durably)
        tree.update(self.metastore.state_leaves())
        out = ckpt.save(tree, directory, step, meta=meta)
        if self.wal is not None:
            crashpoint("wal.truncate.pre")
            self.wal.truncate_through(meta["wal_lsn"])
            # restart the snapshot cadence: explicit saves count too
            self._snap_bytes_mark = self.wal.bytes_written
            self._snap_t_mark = time.monotonic()
            self._snap_step = max(self._snap_step, step)
        return out

    def restore_index(self, directory: str, step: Optional[int] = None, *,
                      durable: bool = False,
                      fsync_interval_ms: float = 0.0) -> "VectorDB":
        """Load a saved index snapshot into this (fresh) VectorDB — no
        retraining; shapes come from the checkpoint manifest. A snapshot of
        a mutated index round-trips exactly: tombstoned ids stay retired
        and the restored layout serves bit-identical results.

        Robust to partial/corrupt snapshots: leftover ``step_<n>.tmp/``
        dirs never qualify, and a step whose manifest or leaf files are
        missing (or that fails mid-load) is skipped with a warning,
        falling back to the next-latest valid step. When no step loads, a
        RuntimeError lists what was tried.

        ``durable=True`` then attaches the directory's WAL and replays the
        record tail past the snapshot's ``wal_lsn`` stamp through the
        mutation API — recovery = latest valid snapshot + WAL replay."""
        if getattr(self.index, "load_state", None) is None:
            raise NotImplementedError(
                f"engine {self.engine_name!r} does not support persistence")
        steps = [step] if step is not None else ckpt.valid_steps(directory)[::-1]
        if not steps:
            raise RuntimeError(
                f"no valid index snapshot to restore in {directory!r}")
        errors, chosen = [], None
        for s in steps:
            def _skip(e):
                errors.append(f"step {s}: {type(e).__name__}: {e}")
                warnings.warn(f"restore_index: skipping snapshot step {s} "
                              f"({type(e).__name__}: {e})")
            try:
                # any failure reading leaves (torn/truncated npy, missing
                # file, mangled manifest) falls back to an older step
                arrays = ckpt.load_arrays(directory, s)
            except (OSError, EOFError, KeyError, ValueError) as e:
                _skip(e)
                continue
            try:
                # the metastore leaves are popped out FIRST so engines
                # only ever see their own keys; a skipped step discards
                # the half-built store along with the rebuilt engine
                store = MetadataStore.from_leaves(arrays)
                self.index.load_state(arrays)
                self.metastore = store
                chosen = s
                break
            # ENGINE validation errors (metric/engine mismatch ValueError)
            # propagate — every step would refuse identically, and masking
            # them hides a real bug; structural gaps (missing keys) skip
            except KeyError as e:
                _skip(e)
                # a partial load may have half-populated the engine:
                # rebuild it fresh before trying the next step
                self.index = ENGINES[self.engine_name](
                    metric=self.metric, **self._engine_kwargs)
        if chosen is None:
            raise RuntimeError(
                f"no loadable index snapshot in {directory!r} "
                f"(tried {list(steps)}): {'; '.join(errors)}")
        self.n = getattr(self.index, "size", 0)
        self._loaded = True
        if durable:
            snap_lsn = int(ckpt.load_meta(directory, chosen).get("wal_lsn", 0))
            self.attach_wal(directory, fsync_interval_ms,
                            after_lsn=snap_lsn, replay=True)
        return self

    @property
    def wal_stats(self) -> Optional[dict]:
        """Durability counters (records/fsyncs/lsn marks, plus the cadence
        policy's auto_snapshots) when a WAL is attached; None otherwise.
        Surfaces in serve ``latency_stats``."""
        if self.wal is None:
            return None
        return dict(self.wal.stats, auto_snapshots=self._auto_snapshots)

    @property
    def filter_stats(self) -> Optional[dict]:
        """Filtered/hybrid query telemetry — batch count, cumulative
        bitmap-build time, a selectivity histogram, hybrid merge count,
        and how often the IVF engines took an nprobe boost — when any
        filtered or hybrid query ran; None otherwise. Surfaces in serve
        ``latency_stats`` exactly like ``adc_stats``."""
        fs = self._filter_stats
        if not (fs["filtered_batches"] or fs["hybrid_merges"]):
            return None
        return dict(fs, selectivity_hist=dict(fs["selectivity_hist"]))

    @property
    def adc_stats(self) -> Optional[dict]:
        """ADC grid-dispatch telemetry (batch counts per grid — blocked /
        per_query / run_resident — plus autotuner probe count + fitted
        crossover, schedule-cache hit/miss, and running sharing-factor /
        effective-nprobe sums) when the engine keeps it (IVF-PQ); None
        otherwise."""
        st = getattr(self.index, "adc_stats", None)
        if st is None:
            return None
        return dict(st, sched_cache_hits=self.sched_cache.stats["hits"],
                    sched_cache_misses=self.sched_cache.stats["misses"])


class DistributedVectorDB(_PlanLedger):
    """Corpus row-sharded over a mesh; exact SPMD search with local top-k +
    hierarchical all-gather merge (repro.core.distributed). Queries go
    through the same plan-bucket ladder as the single-host front — the
    shard_map program retraces per batch shape exactly like a jitted scan,
    so mesh serving needs the plan cache MORE, not less."""

    engine_name = "dist_flat"

    def __init__(self, mesh: Mesh, metric: str = "cosine", axes=None,
                 dtype=jnp.float32, tile: int = 65536):
        assert metric in D.METRICS
        self.mesh = mesh
        self.metric = metric
        self.axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        self.dtype = jnp.dtype(dtype)
        self.tile = tile
        self.corpus = None
        self.valid = None
        self.n = 0
        self.n_shards = 1
        for a in self.axes:
            self.n_shards *= mesh.shape[a]
        self._plan_init()

    def load(self, vectors) -> "DistributedVectorDB":
        x = jnp.asarray(vectors, jnp.float32)
        corpus, _sq = D.preprocess_corpus(x, self.metric)
        corpus, valid = dist.pad_to_shards(corpus.astype(self.dtype), self.n_shards)
        sharding = dist.corpus_sharding(self.mesh, self.axes)
        self.corpus = jax.device_put(corpus, sharding)
        self.valid = jax.device_put(valid, NamedSharding(self.mesh, P(self.axes)))
        self.n = x.shape[0]
        return self

    def query(self, q, k: int = 10, *, bucketize: bool = True):
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32)).astype(self.dtype)
        metric = "dot" if self.metric == "cosine" else self.metric
        qq = D.l2_normalize(q) if self.metric == "cosine" else q
        kk = min(k, self.n)
        if not bucketize:
            return dist.sharded_flat_search(
                self.corpus, qq, mesh=self.mesh, k=kk, metric=metric,
                axes=self.axes, valid=self.valid, tile=self.tile)
        qq, Q = self._plan_batch(qq, kk)
        s, i = dist.sharded_flat_search(
            self.corpus, qq, mesh=self.mesh, k=kk, metric=metric,
            axes=self.axes, valid=self.valid, tile=self.tile)
        return s[:Q], i[:Q]


class DistributedPQ(_PlanLedger):
    """PQ serving under the mesh: uint8 codes row-sharded, LUTs replicated.

    ``DistributedVectorDB`` keeps an f32 corpus shard per device (N*d*4/S
    bytes); at MS MARCO scale that — not compute — caps corpus size. This
    engine shards the PQ *codes* instead (N*m/S bytes, 8-32x less at the
    default geometries) and replicates only the codebooks and the per-query
    (Q, m, ksub) score tables, reusing the exact local-top-k + all-gather
    merge from the flat path. Each shard's local scan goes through the
    fused ADC dispatch, so on TPU the Pallas kernel serves every shard.
    Queries bucketize through the shared plan ladder (see _PlanLedger).
    """

    engine_name = "dist_pq"

    def __init__(self, mesh: Mesh, metric: str = "cosine", m: int = 8,
                 ksub: int = 256, kmeans_iters: int = 10, seed: int = 0,
                 axes=None, use_kernel=None, lut_dtype: str = "float32"):
        assert metric in D.METRICS
        assert lut_dtype in kops.ADC_LUT_DTYPES, lut_dtype
        self.mesh = mesh
        self.metric = metric
        self.m = m
        self.ksub = ksub
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self.axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        self.use_kernel = use_kernel
        self.lut_dtype = lut_dtype
        self.codebooks = self.codes = self.valid = None
        self.n = 0
        self.d = 0
        self.n_shards = 1
        for a in self.axes:
            self.n_shards *= mesh.shape[a]
        self._plan_init()

    def load(self, vectors) -> "DistributedPQ":
        x = jnp.asarray(vectors, jnp.float32)
        self.n, self.d = x.shape
        corpus, _sq = D.preprocess_corpus(x, self.metric)
        self.codebooks = train_pq(jax.random.PRNGKey(self.seed), corpus,
                                  m=self.m, ksub=self.ksub,
                                  iters=self.kmeans_iters)
        codes = pq_encode(self.codebooks, corpus)
        codes, valid = dist.pad_to_shards(codes, self.n_shards)
        self.codes = jax.device_put(codes,
                                    dist.corpus_sharding(self.mesh, self.axes))
        self.valid = jax.device_put(valid,
                                    NamedSharding(self.mesh, P(self.axes)))
        return self

    def query(self, q, k: int = 10, *, bucketize: bool = True):
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
        metric = self.metric
        if metric == "cosine":
            q = D.l2_normalize(q)
            metric = "dot"
        kk = min(k, self.n)
        Q = q.shape[0]
        if bucketize:
            q, Q = self._plan_batch(q, kk)
        luts = adc_tables(self.codebooks, q, metric=metric)
        s, i = dist.sharded_pq_search(
            self.codes, luts, mesh=self.mesh, k=kk,
            axes=self.axes, valid=self.valid, use_kernel=self.use_kernel,
            lut_dtype=self.lut_dtype)
        return s[:Q], i[:Q]

    # ------------------------------------------------------------- memory
    def per_device_bytes(self) -> int:
        """Resident index bytes per device: the local code shard + the
        replicated codebooks (the acceptance metric vs an f32 shard)."""
        return int(self.codes.size // self.n_shards
                   + self.codebooks.size * 4)

    def memory_bytes(self) -> int:
        return int(self.codes.size + self.codebooks.size * 4 * self.n_shards)


class DistributedIVFPQ(_PlanLedger, _WriteFront, MutationMixin):
    """IVF-PQ serving under the mesh: inverted-list BLOCKS range-sharded,
    coarse structures replicated — the bucket-resident fused path at pod
    scale.

    ``DistributedPQ`` still streams every shard's full code slab per query.
    This engine shards the block-aligned inverted lists instead: each
    device owns a contiguous range of (blk, m) code blocks (plus its own
    all-pad block), and a query only touches the probed blocks that live
    on each shard — per-device scoring work scales with the probed
    candidate count, not N/S. Centroids + codebooks replicate (they are
    the small side); probe selection, visit-table expansion, and LUT
    builds run replicated outside the shard_map, and the merge is the same
    O(Q*k*shards) all-gather as every other distributed path. Bucket ids
    store global corpus rows, so no id lifting is needed.

    MUTABLE like the single-host engine, over the same
    ``repro.core.ivf.BlockListLayout`` — the layout's storage capacity is
    kept a multiple of the shard count so storage rows slice into equal
    per-shard slabs, its allocation policy routes a cluster's spilled
    blocks onto the shard already owning that cluster's slab (remote/tail
    visit steps keep reusing the per-shard pad block, exactly as before),
    and deletes tombstone slots to the -1 sentinel each shard's kernel
    already knocks out. Mutations edit the host layout; the next query
    re-device_puts the dirty slabs.

    Compressed-only serving (no exact re-rank — the raw corpus is exactly
    what this engine exists to not hold). Queries bucketize through the
    shared plan ladder (see _PlanLedger).
    """

    engine_name = "dist_ivf_pq"

    def __init__(self, mesh: Mesh, metric: str = "cosine",
                 n_clusters: int = 0, nprobe: int = 8, m: int = 8,
                 ksub: int = 256, kmeans_iters: int = 10, seed: int = 0,
                 axes=None, use_kernel=None, lut_dtype: str = "float32",
                 block_size: int = 32):
        assert metric in D.METRICS
        assert lut_dtype in kops.ADC_LUT_DTYPES, lut_dtype
        self.mesh = mesh
        self.metric = metric
        self.n_clusters = n_clusters  # 0 => sqrt(N) at load time
        self.nprobe = nprobe
        self.m = m
        self.ksub = ksub
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self.axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        self.use_kernel = use_kernel
        self.lut_dtype = lut_dtype
        self.block_size = block_size
        self.codebooks = self.centroids = None
        self.codes_bm = self.bucket_ids = self.block_table = None
        self.layout = None
        self.spp = 1
        self.blocks_per_shard = 0
        self.n = 0  # id-space size; `size` is the live count
        self.d = 0
        self.n_shards = 1
        for a in self.axes:
            self.n_shards *= mesh.shape[a]
        self._plan_init()
        self._mut_init(0)

    @property
    def size(self) -> int:
        return 0 if self.layout is None else int(self.layout.live)

    @property
    def _replicated(self):
        return NamedSharding(self.mesh, P())

    def _alloc_policy(self, cluster: int, free_rows) -> int:
        """Spilled blocks land on the shard owning the cluster's slab (its
        last block's shard); a full shard falls back to the emptiest row."""
        lay = self.layout
        if lay is None or lay.bcnt[cluster] == 0:
            return min(free_rows)
        bloc = lay.capacity // self.n_shards
        shard = int(lay.block_table[cluster, lay.bcnt[cluster] - 1]) // bloc
        same = [r for r in free_rows if r // bloc == shard]
        return min(same) if same else min(free_rows)

    def load(self, vectors) -> "DistributedIVFPQ":
        """Index raw rows — host, one device, or (at scale) already
        row-sharded over the mesh: the quantizers train on a sample and
        the rows stream through the encoder in chunks, so no device ever
        holds the whole corpus; the block slabs then shard by row range."""
        self.n, self.d = vectors.shape
        C = self.n_clusters or max(1, int(np.sqrt(self.n)))
        C = min(C, self.n)
        cent, codebooks = train_ivf_pq(
            vectors, metric=self.metric, n_clusters=C, m=self.m,
            ksub=self.ksub, iters=self.kmeans_iters, seed=self.seed)
        _, _, assign, codes = encode_ivf_pq(
            codebooks, cent, vectors, metric=self.metric, keep_rows=False)
        # the coarse structures replicate over the mesh, next to the slabs
        self.centroids = jax.device_put(cent, self._replicated)
        self.codebooks = jax.device_put(codebooks, self._replicated)
        # storage rows stay a multiple of the shard count so they slice into
        # equal per-shard slabs; the policy steers spills to the owner shard
        self.layout = BlockListLayout.from_assign(
            assign, C, blk=self.block_size, payload=codes,
            row_multiple=self.n_shards, alloc_policy=self._alloc_policy)
        self._mut_init(self.n)
        self._sync()
        return self

    # ---------------------------------------------------------- mutation
    def _encode_batch(self, vectors):
        _, _, assign, codes = encode_ivf_pq(
            self.codebooks, self.centroids, np.atleast_2d(vectors),
            metric=self.metric, keep_rows=False)
        return codes, assign

    def _after_mutation(self, shape_before) -> None:
        if self.layout.shape_key != shape_before:
            self.plan_generation += 1

    def insert(self, vectors, ids=None) -> np.ndarray:
        codes, assign = self._encode_batch(vectors)
        ids = self._take_ids(codes.shape[0], ids)
        before = self.layout.shape_key
        self.layout.insert_rows(ids, assign, codes)
        self.n = self.next_id
        self._record("inserts", len(ids))
        self._after_mutation(before)
        return ids

    def delete(self, ids) -> int:
        n = self.layout.delete_rows(ids)
        if n:
            self._record("deletes", n)
        return n

    def upsert(self, vectors, ids) -> np.ndarray:
        codes, assign = self._encode_batch(vectors)
        ids = self._check_upsert_ids(codes.shape[0], ids)
        before = self.layout.shape_key
        self.layout.delete_rows(ids)
        self.layout.insert_rows(ids, assign, codes)
        self._record("upserts", len(ids))
        self._after_mutation(before)
        return ids

    def compact(self) -> dict:
        stats = self.layout.compact()
        self._record("compactions", 1)
        return stats

    # ------------------------------------------------------------- query
    def _sync(self) -> None:
        """Re-slab the host layout onto the mesh: per-shard contiguous rows
        + one trailing all-pad block per shard, global (storage-row) visit
        numbering localized inside sharded_ivf_pq_search."""
        if not self._dirty:
            return
        lay = self.layout
        S = self.n_shards
        blk = lay.blk
        bloc = lay.capacity // S
        slots = lay.slots.reshape(S, bloc, blk)
        pad = np.full((S, 1, blk), -1, np.int32)
        slots_sharded = np.concatenate([slots, pad], axis=1).reshape(-1, blk)
        codes = lay.codes.reshape(S, bloc, blk, self.m)
        padc = np.zeros((S, 1, blk, self.m), np.uint8)
        codes_sharded = np.concatenate([codes, padc],
                                       axis=1).reshape(-1, blk, self.m)
        sharding = dist.corpus_sharding(self.mesh, self.axes)
        self.bucket_ids = jax.device_put(jnp.asarray(slots_sharded), sharding)
        self.codes_bm = jax.device_put(jnp.asarray(codes_sharded), sharding)
        self.block_table = jax.device_put(lay.block_table, self._replicated)
        self.spp = lay.steps_per_probe
        self.blocks_per_shard = bloc
        self._dirty = False

    def query(self, q, k: int = 10, *, bucketize: bool = True):
        self._sync()
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
        metric = self.metric
        if metric == "cosine":
            q = D.l2_normalize(q)
            metric = "dot"
        kk = min(k, max(self.size, 1))
        Q = q.shape[0]
        if bucketize:
            q, Q = self._plan_batch(q, kk)
        nprobe = min(self.nprobe, self.centroids.shape[0])
        s, i = _dist_ivf_pq_plan(
            self.codes_bm, self.bucket_ids, self.block_table,
            self.codebooks, self.centroids, q, mesh=self.mesh, k=kk,
            metric=metric, nprobe=nprobe, steps_per_probe=self.spp,
            blocks_per_shard=self.blocks_per_shard, axes=self.axes,
            use_kernel=self.use_kernel, lut_dtype=self.lut_dtype)
        return s[:Q], i[:Q]

    # ------------------------------------------------------------- memory
    def per_device_bytes(self) -> int:
        """Resident index bytes per device: the local block slab (codes +
        slot ids) + the replicated coarse structures."""
        S = self.n_shards
        return int(self.codes_bm.size // S + self.bucket_ids.size * 4 // S
                   + self.codebooks.size * 4 + self.centroids.size * 4
                   + self.block_table.size * 4)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "metric", "nprobe", "steps_per_probe",
                     "blocks_per_shard", "axes", "use_kernel", "lut_dtype"))
def _dist_ivf_pq_plan(codes_bm, bucket_ids, block_table, codebooks,
                      centroids, q, *, mesh, k, metric, nprobe,
                      steps_per_probe, blocks_per_shard, axes, use_kernel,
                      lut_dtype):
    """One jitted program per (batch bucket, k, dtype) plan: replicated
    probe selection + visit expansion + LUT build (the shared helpers from
    repro.core.pq), then the bucket-range-sharded search. The visit table
    uses the -1 tail sentinel — each shard retargets it (and off-shard
    blocks) at its own pad block inside sharded_ivf_pq_search."""
    Q = q.shape[0]
    c_scores = D.pairwise_scores(q, centroids,
                                 metric if metric == "dot" else "l2")
    _, probe = jax.lax.top_k(c_scores, nprobe)
    visit = expand_visit(probe, block_table,
                         steps_per_probe=steps_per_probe, pad_block=-1)
    luts, coarse = probe_luts(codebooks, centroids, q, probe, c_scores,
                              metric=metric)
    if coarse is None:
        coarse = jnp.zeros((Q, nprobe), jnp.float32)
    return dist.sharded_ivf_pq_search(
        codes_bm, bucket_ids, visit, luts, coarse, mesh=mesh, k=k,
        steps_per_probe=steps_per_probe, blocks_per_shard=blocks_per_shard,
        axes=axes, use_kernel=use_kernel, lut_dtype=lut_dtype)
