"""IVF (inverted-file) index — the dense-hardware analogue of HNSW's hierarchy.

HNSW's insight is that a coarse view of the corpus lets a query skip most of
it. Pointer-chasing graph walks don't vectorize on a systolic array, so the
coarse view here is a k-means quantizer (ScaNN / FAISS lineage): "layer 1" =
centroids, "layer 0" = probed cluster buckets. Every step is a dense gather +
MXU matmul.

Buckets are padded to a fixed capacity so query shapes are static; the pad
rows carry id -1 and score -inf.

Two inverted-list layouts live here:

  * ``build_buckets`` — the fixed-capacity (C, cap) table the plain IVF
    engine scans (one gather per probe).
  * ``BlockListLayout`` — the APPENDABLE block-aligned layout behind the
    bucket-resident fused kernel path (``kernels/ivf_adc``): cluster c owns
    an explicit list of (blk, m) storage blocks (``block_table``), appends
    go into the cluster's last ragged block and spill to a freshly
    allocated block when it fills (amortized O(1) per row), deletes
    tombstone the slot to id -1 — exactly the pad sentinel the kernel
    already knocks out, so ONLINE MUTATION NEEDS ZERO KERNEL CHANGES.
    ``build_block_lists`` remains the one-shot contiguous builder
    (kernel tests and the sharded loader use it); the layout class wraps
    it for everything mutable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances as D
from repro.core.mutable import GrowableRows, MutationMixin, row_capacity


@functools.partial(jax.jit, static_argnames=("n_clusters", "iters"))
def kmeans(key, x, *, n_clusters: int, iters: int = 10):
    """Lloyd k-means (L2). x: (N, d) f32 -> centroids (n_clusters, d)."""
    N, d = x.shape
    init_idx = jax.random.choice(key, N, (n_clusters,), replace=False)
    cent0 = jnp.take(x, init_idx, axis=0)

    def step(cent, _):
        scores = D.pairwise_scores(x, cent, "l2")  # (N, C), higher = closer
        assign = jnp.argmax(scores, axis=-1)
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        cnts = jax.ops.segment_sum(jnp.ones((N,), x.dtype), assign, n_clusters)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        # empty cluster keeps its old centroid
        return jnp.where((cnts > 0)[:, None], new, cent), None

    cent, _ = jax.lax.scan(step, cent0, None, length=iters)
    return cent


def assign_clusters(x, centroids):
    return jnp.argmax(D.pairwise_scores(x, centroids, "l2"), axis=-1)


def build_buckets(assign, n_clusters: int, ids=None):
    """Host-side inverted lists: assign (N,) -> (buckets (C, cap) int32, cap).

    Pad slots carry id -1 so query shapes stay static (shared by IVFIndex and
    IVFPQIndex). ``ids`` optionally names the row id each assignment entry
    stands for (defaults to position) — the tombstone-aware snapshot path
    lists only live ids.
    """
    assign = np.asarray(assign)
    ids = np.arange(assign.shape[0]) if ids is None else np.asarray(ids)
    counts = np.bincount(assign, minlength=n_clusters)
    cap = max(1, int(counts.max()))
    buckets = np.full((n_clusters, cap), -1, np.int32)
    fill = np.zeros(n_clusters, np.int64)
    order = np.argsort(assign, kind="stable")
    for i in order:
        c = assign[i]
        buckets[c, fill[c]] = ids[i]
        fill[c] += 1
    return buckets, cap


def assign_from_buckets(buckets, n_rows: int) -> np.ndarray:
    """(C, cap) bucket table -> (n_rows,) cluster assignment.

    THE reconstruction helper for PR-1-format (row-major) snapshots: the
    bucket table lists each cluster's rows, so assignment — and from it the
    whole block layout including per-cluster tail counts — re-derives in one
    place (previously restore and the benchmarks each hand-rolled this).
    Rows absent from the table (tombstoned ids) keep assignment 0; callers
    pass the live mask alongside.
    """
    b = np.asarray(buckets)
    assign = np.zeros(n_rows, np.int32)
    rows = np.broadcast_to(np.arange(b.shape[0], dtype=np.int32)[:, None],
                           b.shape)
    sel = b >= 0
    assign[b[sel]] = rows[sel]
    return assign


def quarter_octave(n: int) -> int:
    """Smallest rung >= n of the quarter-octave ladder: n itself up to 8,
    then the multiples of 2^(k-2) within each octave (2^k, 2^(k+1)]: 10,
    12, 14, 16, 20, 24, 28, 32, 40, ... A growing n takes O(log n)
    distinct values, each at most 25% above n."""
    if n <= 8:
        return n
    e = (n - 1).bit_length() - 3
    return -(-n >> e) << e


def build_block_lists(assign, n_clusters: int, blk: int = 32):
    """Host-side BLOCK-ALIGNED inverted lists for the bucket-resident kernel.

    assign (N,) -> (slot_rows (B+1, blk) int32, bstart (C,) int32,
    bcnt (C,) int32, steps_per_probe int). Cluster c owns the ``bcnt[c] =
    ceil(count_c / blk)`` contiguous rows starting at ``bstart[c]``; its
    last row is padded with -1 ids, and row B is a shared all-pad block
    that probe expansion points tail steps at. Pad slack is <= blk-1 per
    cluster — vs the (max_count - count_c) slack of the fixed-capacity
    ``build_buckets`` table, the layout that keeps a compressed index's
    resident bytes honest. ``steps_per_probe`` = max rows any cluster owns
    (>= 1), the static width of one probe in the kernel's visit table.

    One-shot builder for a frozen corpus; the mutable path wraps the same
    output in ``BlockListLayout`` (explicit per-cluster block tables, so
    spilled blocks need not be contiguous).
    """
    assert blk % 8 == 0, blk  # TPU sublane multiple for the code blocks
    assign = np.asarray(assign)
    counts = np.bincount(assign, minlength=n_clusters)
    bcnt = -(-counts // blk)  # ceil; an empty cluster owns 0 blocks
    spp = max(1, int(bcnt.max()))
    bstart = np.zeros(n_clusters, np.int64)
    np.cumsum(bcnt[:-1], out=bstart[1:])
    B = int(bcnt.sum())
    slots = np.full(((B + 1) * blk,), -1, np.int32)
    order = np.argsort(assign, kind="stable")
    pos = 0
    for c in range(n_clusters):
        cnt = int(counts[c])
        start = int(bstart[c]) * blk
        slots[start:start + cnt] = order[pos:pos + cnt]
        pos += cnt
    return (slots.reshape(B + 1, blk), bstart.astype(np.int32),
            bcnt.astype(np.int32), spp)


def visit_sharing(visit, *, pad_block=None):
    """Cheap sharing probe: ``{pairs, blocks, sharing}`` of a visit table
    WITHOUT building the segmented schedule. One ``np.unique`` over the
    (Q*T,) block ids instead of the full sort-and-segment — the auto
    dispatch reads this first and only pays ``build_block_schedule`` when a
    grouped grid can actually use the result."""
    visit = np.asarray(visit).reshape(-1)
    if pad_block is not None:
        visit = visit[visit != pad_block]
    pairs = int(visit.size)
    blocks = int(np.unique(visit).size)
    return {"pairs": pairs, "blocks": blocks,
            "sharing": float(pairs) / max(1, blocks)}


def build_block_schedule(visit, *, qblk: int = 8, pad_block=None):
    """Host-side SEGMENTED schedule for the blocked multi-query ADC mode.

    The per-query ``ivf_adc`` grid fetches block ``visit[q, t]`` once per
    (q, t) program — a block probed by s queries is DMA'd s times and each
    contraction is a (1, m*ksub) matvec. This builder inverts the visit
    table: the (q, t) pairs are sorted by block id and each block's run is
    cut into fixed-width groups of ``qblk`` pairs, so one program can fetch
    the block ONCE and contract it against a (qblk, m*ksub) LUT panel — a
    real MXU matmul. Partial groups pad with the query-knockout sentinel
    ``-1`` (the same masking idiom as the -1 pad slot: a sentinel pair
    scores NEG_INF and folds into a trash scoreboard row).

    visit: (Q, T) int32 block ids (the ``expand_visit`` contract).
    ``pad_block`` names the shared all-pad block; pairs visiting it are
    DROPPED from the schedule (they can contribute nothing — every slot id
    is -1), which is also where the blocked mode's pad-work saving comes
    from. The group count G pads up to a quarter-octave bucket (multiples
    of 2^(e-2) within each power-of-two octave, all-sentinel groups
    pointing at ``pad_block``) so the blocked executable recompiles
    O(log P) times per (Q, T) shape, not once per batch, while wasting at
    most ~25% of the grid on padding.

    Returns ``(sched_block (G,) int32, sched_q (G, qblk) int32,
    sched_t (G, qblk) int32, stats)`` where every real (q, t) pair appears
    in exactly one (group, slot), every group's pairs share one block, and
    ``stats`` carries ``pairs`` (real pairs kept), ``blocks`` (distinct
    blocks visited), ``sharing`` (pairs / blocks — the dispatch heuristic's
    estimate of how many queries each block DMA amortizes over), and
    ``groups`` (real groups, before the bucket pad).

    Because the sort is by block id, all of a block's groups are already
    CONTIGUOUS in the flat group list — ``stats`` additionally carries the
    run-length view the block-resident executors consume:

    * ``stats["runs"] = (run_block (R,), run_start (R,), run_len (R,))``
      int32 — run r covers groups ``[run_start[r], run_start[r] +
      run_len[r])``, all visiting block ``run_block[r]``. R pads up to a
      quarter-octave bucket of ``n_runs + 1`` so there is always at least
      one pad run (``run_len == 0``, ``run_block == pad``, ``run_start ==
      groups`` — a no-op program in the run grid).
    * ``stats["grun"] (G,) int32`` — inverse map group -> run; the G-pad
      sentinel groups point at the first pad run, so a per-group gather
      through ``grun`` lands on the pad block exactly like ``sched_block``
      does.
    * ``stats["n_runs"]`` — real runs (== ``blocks``, before the R pad).
    """
    assert qblk >= 1, qblk
    visit = np.asarray(visit)
    Q, T = visit.shape
    b = visit.reshape(-1).astype(np.int64)
    q_of = np.repeat(np.arange(Q, dtype=np.int32), T)
    t_of = np.tile(np.arange(T, dtype=np.int32), Q)
    fill = 0 if pad_block is None else int(pad_block)
    if pad_block is not None:
        keep = b != pad_block
        b, q_of, t_of = b[keep], q_of[keep], t_of[keep]
    order = np.argsort(b, kind="stable")  # stable: ties stay in visit order
    b, q_of, t_of = b[order], q_of[order], t_of[order]
    P = b.size
    if P:
        new_run = np.r_[True, b[1:] != b[:-1]]
        starts = np.flatnonzero(new_run)
        run_of = np.cumsum(new_run) - 1            # run index per pair
        rank = np.arange(P) - starts[run_of]       # position within the run
        run_len = np.diff(np.r_[starts, P])
        groups_per_run = -(-run_len // qblk)       # ceil
        gbase = np.r_[0, np.cumsum(groups_per_run)]
        gid = gbase[run_of] + rank // qblk
        slot = rank % qblk
        n_groups = int(gbase[-1])
        n_blocks = starts.size
    else:
        gid = slot = np.zeros(0, np.int64)
        n_groups = n_blocks = 0
    G = quarter_octave(max(8, n_groups))
    sched_block = np.full(G, fill, np.int32)
    sched_q = np.full((G, qblk), -1, np.int32)     # -1 = knockout sentinel
    sched_t = np.zeros((G, qblk), np.int32)
    if P:
        sched_block[gid] = b
        sched_q[gid, slot] = q_of
        sched_t[gid, slot] = t_of
    # run-length view: one entry per distinct block, padded on the same
    # quarter-octave ladder (of n_runs + 1, so >= 1 pad run always exists)
    n_runs = n_blocks
    R = quarter_octave(max(8, n_runs + 1))
    run_block = np.full(R, fill, np.int32)
    run_start = np.full(R, n_groups, np.int32)     # pad runs: empty tail
    run_len = np.zeros(R, np.int32)
    grun = np.full(G, n_runs, np.int32)            # sentinel groups -> pad run
    if P:
        run_block[:n_runs] = b[starts]
        run_start[:n_runs] = gbase[:-1]
        run_len[:n_runs] = groups_per_run
        grun[:n_groups] = np.repeat(np.arange(n_runs, dtype=np.int32),
                                    groups_per_run)
    stats = {"pairs": int(P), "blocks": int(n_blocks),
             "sharing": float(P) / max(1, n_blocks), "groups": n_groups,
             "runs": (run_block, run_start, run_len), "grun": grun,
             "n_runs": int(n_runs)}
    return sched_block, sched_q, sched_t, stats


class ScheduleCache:
    """Content-verified LRU over built block schedules.

    ``build_block_schedule`` is a host-side sort of Q*T pairs plus a
    device upload of the result — steady-state serving that re-queries the
    same plan bucket re-pays it every call. The plan ledger
    (``repro.core.db._PlanLedger``) owns one of these, keyed by
    ``(plan bucket, plan generation, nprobe)`` + the dispatcher's
    ``(qblk, Q, T)``; a hit additionally verifies the raw visit bytes
    match what was cached, so a hash-free key can never alias a mutated
    index or a different batch onto a stale schedule (it just misses and
    rebuilds). Entries hold the DEVICE arrays, so a hit also skips the
    host->device transfer.
    """

    def __init__(self, cap: int = 8):
        from collections import OrderedDict
        self.cap = int(cap)
        self._entries = OrderedDict()
        self.stats = {"hits": 0, "misses": 0}

    def get(self, key, visit_bytes: bytes):
        ent = self._entries.get(key)
        if ent is not None and ent[0] == visit_bytes:
            self._entries.move_to_end(key)
            self.stats["hits"] += 1
            return ent[1]
        self.stats["misses"] += 1
        return None

    def put(self, key, visit_bytes: bytes, built) -> None:
        self._entries[key] = (visit_bytes, built)
        self._entries.move_to_end(key)
        while len(self._entries) > self.cap:
            self._entries.popitem(last=False)


class BlockListLayout:
    """Appendable, tombstone-aware block-aligned inverted lists (host side).

    Storage is a (capacity, blk) slot table (+ an optional co-located
    (capacity, blk, m) code payload). Row ``capacity - 1`` is the reserved
    shared all-pad block; ``block_table[c]`` lists the storage rows cluster
    c owns, in visit order, padded to the static ``steps_per_probe`` width
    with -1. Row capacities are power-of-two buckets
    (``mutable.row_capacity``); ``steps_per_probe`` doubles up to 8 blocks,
    then climbs the ``quarter_octave`` ladder, so a longest list one block
    past a power of two widens every probe's visit row by at most 25%, not
    100%. Steady-state mutation never changes device-visible shapes —
    ``shape_key`` summarizes them for the plan ledger.

    Invariants:
      * appends fill the cluster's LAST block before allocating (tail pad
        slack stays <= blk - 1 per cluster, the memory_bytes honesty bound);
      * deletes tombstone ``slots[row, s] = -1`` — storage-layer only, the
        fused ``ivf_adc`` kernel and its jnp twin are untouched (a deleted
        slot scores exactly like a pad slot);
      * ``compact()`` repacks live slots into fresh dense blocks WITHOUT
        changing capacities, so reclaiming tombstoned query work never
        recompiles a query plan.

    ``row_multiple`` forces capacity to a multiple (the sharded front sets
    it to the shard count so storage rows split into equal slabs), and
    ``alloc_policy(cluster, free_rows) -> row`` lets that front steer spilled
    blocks onto the shard owning the cluster's slab.
    """

    def __init__(self, n_clusters: int, blk: int = 32, m: int = 0,
                 row_multiple: int = 1, alloc_policy=None):
        assert blk % 8 == 0, blk
        self.C = int(n_clusters)
        self.blk = int(blk)
        self.m = int(m)
        self.row_multiple = int(row_multiple)
        self.alloc_policy = alloc_policy
        self.spp_cap = 1
        cap = self._round_rows(2)
        self.slots = np.full((cap, blk), -1, np.int32)
        self.codes = np.zeros((cap, blk, m), np.uint8) if m else None
        self.block_cluster = np.full(cap, -1, np.int32)
        self.block_table = np.full((self.C, self.spp_cap), -1, np.int32)
        self.bcnt = np.zeros(self.C, np.int32)
        self.tail_fill = np.zeros(self.C, np.int32)
        self._pos = {}  # id -> (storage row, slot)
        self._free = set(range(cap - 1))  # row cap-1 reserved all-pad
        self.live = 0
        self.tombstones = 0

    # ------------------------------------------------------------ build
    @classmethod
    def from_assign(cls, assign, n_clusters: int, *, blk: int = 32,
                    payload=None, ids=None, live=None, row_multiple: int = 1,
                    alloc_policy=None) -> "BlockListLayout":
        """Build from a (N,) assignment (+ optional (N, m) payload codes).

        ``ids`` defaults to row numbers; ``live`` masks tombstoned ids out
        (restore of a mutated snapshot rebuilds compacted — same scores,
        zero slack). Rows pack per cluster in stable id order, matching
        ``build_block_lists`` for a fresh corpus, so load and restore
        produce identical layouts.
        """
        assign = np.asarray(assign)
        N = assign.shape[0]
        ids = np.arange(N, dtype=np.int64) if ids is None else np.asarray(ids)
        if live is not None:
            keep = np.asarray(live, bool)
            assign, ids = assign[keep], ids[keep]
            payload = None if payload is None else np.asarray(payload)[keep]
        m = 0 if payload is None else np.asarray(payload).shape[1]
        lay = cls(n_clusters, blk=blk, m=m, row_multiple=row_multiple,
                  alloc_policy=alloc_policy)
        need = int(-(-np.bincount(assign, minlength=n_clusters) // blk).sum())
        lay._reserve_rows(need + 2)
        order = np.argsort(assign, kind="stable")
        bounds = np.searchsorted(assign[order],
                                 np.arange(n_clusters + 1))
        for c in range(n_clusters):
            sel = order[bounds[c]:bounds[c + 1]]
            if sel.size:
                lay._bulk_append(
                    c, ids[sel],
                    None if payload is None else np.asarray(payload)[sel])
        return lay

    # -------------------------------------------------------- capacities
    @property
    def capacity(self) -> int:
        return self.slots.shape[0]

    @property
    def pad_row(self) -> int:
        return self.capacity - 1

    @property
    def steps_per_probe(self) -> int:
        return self.spp_cap

    @property
    def shape_key(self) -> tuple:
        return (self.capacity, self.spp_cap)

    @property
    def n_blocks(self) -> int:
        """Active (allocated) blocks, excluding the reserved pad row."""
        return self.capacity - 1 - len(self._free)

    def _round_rows(self, n: int) -> int:
        per = -(-n // self.row_multiple)
        return self.row_multiple * row_capacity(per, minimum=4)

    def _reserve_rows(self, n: int) -> bool:
        """Grow storage to >= n rows (pad row included); True on growth."""
        cap = self.capacity
        if n <= cap:
            return False
        new_cap = self._round_rows(n)
        grown = np.full((new_cap, self.blk), -1, np.int32)
        grown[: cap - 1] = self.slots[: cap - 1]
        self.slots = grown
        if self.codes is not None:
            gc = np.zeros((new_cap, self.blk, self.m), np.uint8)
            gc[: cap - 1] = self.codes[: cap - 1]
            self.codes = gc
        bc = np.full(new_cap, -1, np.int32)
        bc[: cap - 1] = self.block_cluster[: cap - 1]
        self.block_cluster = bc
        # the old reserved pad row joins the free pool; new pad = new_cap-1
        self._free.update(range(cap - 1, new_cap - 1))
        return True

    def reserve(self, extra_rows: int, extra_blocks_per_cluster: int = 0):
        """Pre-size capacity buckets for a planned ingest volume so the
        steady-state insert stream stays inside one shape bucket."""
        blocks = -(-int(extra_rows) // self.blk) + self.C
        self._reserve_rows(self.n_blocks + blocks + 2)
        spp = int(self.bcnt.max(initial=0)) + int(extra_blocks_per_cluster)
        while self.spp_cap < max(1, spp):
            self._grow_spp()

    def _grow_spp(self) -> None:
        cap = self.spp_cap
        self.spp_cap = 2 * cap if cap < 8 else quarter_octave(cap + 1)
        table = np.full((self.C, self.spp_cap), -1, np.int32)
        table[:, : self.block_table.shape[1]] = self.block_table
        self.block_table = table

    def _alloc_block(self, cluster: int) -> int:
        if not self._free:
            self._reserve_rows(self.capacity + 1)
        if self.alloc_policy is not None:
            row = int(self.alloc_policy(cluster, self._free))
        else:
            row = min(self._free)  # densest-first keeps slabs compact
        self._free.discard(row)
        if self.bcnt[cluster] >= self.spp_cap:
            self._grow_spp()
        self.block_table[cluster, self.bcnt[cluster]] = row
        self.bcnt[cluster] += 1
        self.block_cluster[row] = cluster
        self.tail_fill[cluster] = 0
        return row

    # --------------------------------------------------------- mutation
    def _bulk_append(self, cluster: int, ids, payload=None) -> None:
        ids = np.asarray(ids)
        done = 0
        while done < ids.size:
            if self.bcnt[cluster] == 0 or self.tail_fill[cluster] == self.blk:
                self._alloc_block(cluster)
            row = int(self.block_table[cluster, self.bcnt[cluster] - 1])
            s0 = int(self.tail_fill[cluster])
            take = min(self.blk - s0, ids.size - done)
            chunk = ids[done: done + take]
            self.slots[row, s0: s0 + take] = chunk
            if payload is not None:
                self.codes[row, s0: s0 + take] = payload[done: done + take]
            for off, i in enumerate(chunk):
                self._pos[int(i)] = (row, s0 + off)
            self.tail_fill[cluster] = s0 + take
            done += take
        self.live += int(ids.size)

    def insert_rows(self, ids, clusters, payload=None) -> None:
        """Append rows (amortized O(1) each): each lands in its cluster's
        last ragged block, spilling to a freshly allocated block when full."""
        ids = np.asarray(ids)
        clusters = np.asarray(clusters)
        order = np.argsort(clusters, kind="stable")
        bounds = np.flatnonzero(np.diff(clusters[order], prepend=-1,
                                        append=-1))
        for a, b in zip(bounds[:-1], bounds[1:]):
            sel = order[a:b]
            self._bulk_append(int(clusters[sel[0]]), ids[sel],
                              None if payload is None
                              else np.asarray(payload)[sel])

    def delete_rows(self, ids) -> int:
        """Tombstone rows: the slot's id retargets to the pad sentinel -1,
        so the fused kernel scores it exactly like a pad slot. O(1) each."""
        n = 0
        for i in np.asarray(ids).reshape(-1):
            pos = self._pos.pop(int(i), None)
            if pos is None:
                continue
            self.slots[pos] = -1
            n += 1
        self.live -= n
        self.tombstones += n
        return n

    def contains(self, i: int) -> bool:
        return int(i) in self._pos

    @property
    def tombstone_fraction(self) -> float:
        return self.tombstones / max(self.live + self.tombstones, 1)

    def compact(self) -> dict:
        """Repack live slots into dense blocks, dropping tombstones and
        restoring the <= blk-1 tail-slack invariant. Capacity buckets are
        DELIBERATELY kept, so compaction never changes device shapes (and
        therefore never recompiles a query plan)."""
        per_cluster = []
        for c in range(self.C):
            rows = self.block_table[c, : self.bcnt[c]]
            sl = self.slots[rows].reshape(-1)
            keep = sl >= 0
            pay = (self.codes[rows].reshape(-1, self.m)[keep]
                   if self.codes is not None else None)
            per_cluster.append((sl[keep], pay))
        freed = self.n_blocks
        self.slots[:] = -1
        if self.codes is not None:
            self.codes[:] = 0
        self.block_cluster[:] = -1
        self.block_table[:] = -1
        self.bcnt[:] = 0
        self.tail_fill[:] = 0
        self._pos = {}
        self._free = set(range(self.capacity - 1))
        self.live = 0
        dropped = self.tombstones
        self.tombstones = 0
        for c, (ids_c, pay) in enumerate(per_cluster):
            if ids_c.size:
                self._bulk_append(c, ids_c, pay)
        return {"dropped_tombstones": int(dropped),
                "blocks_before": int(freed), "blocks_after": self.n_blocks}

    # ------------------------------------------------------------ views
    def assign_of(self, n_rows: int) -> np.ndarray:
        """(n_rows,) assignment over the id space (dead ids read 0)."""
        assign = np.zeros(n_rows, np.int32)
        for i, (row, _s) in self._pos.items():
            assign[i] = self.block_cluster[row]
        return assign

    def live_mask(self, n_rows: int) -> np.ndarray:
        mask = np.zeros(n_rows, bool)
        if self._pos:
            mask[np.fromiter(self._pos, np.int64, len(self._pos))] = True
        return mask

    def gather_payload(self, n_rows: int) -> np.ndarray:
        """Row-major (n_rows, m) codes recovered from the slots (dead ids
        read 0) — snapshots stay at the PR-1 row-major format."""
        out = np.zeros((n_rows, self.m), np.uint8)
        for i, pos in self._pos.items():
            out[i] = self.codes[pos]
        return out

    def memory_bytes(self) -> int:
        total = self.slots.size * 4 + self.block_table.size * 4
        if self.codes is not None:
            total += self.codes.size
        return int(total)


@functools.partial(jax.jit, static_argnames=("metric", "k", "nprobe", "cap"))
def ivf_search(corpus, centroids, buckets, q, *, metric: str, k: int,
               nprobe: int, cap: int, corpus_sq=None):
    """corpus: (N, d); centroids: (C, d); buckets: (C, cap) ids (-1 = pad).

    q: (Q, d) -> (scores (Q,k), ids (Q,k)). Probes the nprobe closest
    centroids, scores only their buckets.
    """
    Q = q.shape[0]
    if metric == "cosine":
        q = D.l2_normalize(q)
        metric = "dot"
    c_scores = D.pairwise_scores(q, centroids, metric if metric == "dot" else "l2")
    _, probe = jax.lax.top_k(c_scores, nprobe)  # (Q, nprobe)
    cand = jnp.take(buckets, probe, axis=0).reshape(Q, nprobe * cap)  # ids
    valid = cand >= 0
    safe = jnp.where(valid, cand, 0)
    vecs = jnp.take(corpus, safe, axis=0)  # (Q, nprobe*cap, d)
    dots = jnp.einsum("qd,qnd->qn", q, vecs, preferred_element_type=jnp.float32)
    if metric == "dot":
        scores = dots
    else:
        sq = (jnp.take(corpus_sq, safe, axis=-1) if corpus_sq is not None
              else jnp.sum(jnp.square(vecs.astype(jnp.float32)), -1))
        q_sq = jnp.sum(jnp.square(q.astype(jnp.float32)), -1)
        scores = -(q_sq[:, None] - 2.0 * dots + sq)
    scores = jnp.where(valid, scores, -jnp.inf)
    kk = min(k, nprobe * cap)
    s, pos = jax.lax.top_k(scores, kk)
    ids = jnp.take_along_axis(cand, pos, axis=-1)
    if kk < k:  # degenerate tiny-index case: pad
        s = jnp.pad(s, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return s, ids


class IVFIndex(MutationMixin):
    """k-means coarse quantizer + probed exact scoring (TPU-adapted HNSW (a)).

    Mutable: inserts assign against the frozen centroids and append to the
    cluster's bucket row (bucket capacity doubles on overflow — a shape
    bucket change the plan ledger counts); deletes tombstone the slot to the
    -1 pad sentinel the search already knocks out; compact() repacks bucket
    rows. The raw corpus is id-indexed and append-only.
    """

    def __init__(self, metric: str = "cosine", n_clusters: int = 0, nprobe: int = 8,
                 kmeans_iters: int = 10, seed: int = 0, dtype=jnp.float32):
        assert metric in D.METRICS
        self.metric = metric
        self.n_clusters = n_clusters  # 0 => sqrt(N) at load time
        self.nprobe = nprobe
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self.dtype = jnp.dtype(dtype)
        self.corpus = self.centroids = self.buckets = self.corpus_sq = None
        self.cap = 0
        self._corpus = self._sq = None  # host mirrors (GrowableRows)
        self._buckets = None
        self._fill = self._pos = None
        self._mut_init(0)

    @property
    def size(self) -> int:
        return 0 if self._pos is None else len(self._pos)

    @property
    def shape_key(self) -> tuple:
        return (0 if self._corpus is None else self._corpus.capacity,
                self.cap)

    def load(self, vectors):
        x = jnp.asarray(vectors, jnp.float32)
        N = x.shape[0]
        C = self.n_clusters or max(1, int(np.sqrt(N)))
        C = min(C, N)
        corpus, sq = D.preprocess_corpus(x, self.metric)
        # cluster in the *search* geometry: cosine clusters unit vectors
        cent = kmeans(jax.random.PRNGKey(self.seed), corpus, n_clusters=C,
                      iters=self.kmeans_iters)
        if self.metric == "cosine":
            cent = D.l2_normalize(cent)
        assign = np.asarray(assign_clusters(corpus, cent))
        buckets, cap = build_buckets(assign, C)
        self.centroids = cent.astype(self.dtype)
        self._corpus = GrowableRows.from_array(np.asarray(corpus))
        self._sq = (GrowableRows.from_array(np.asarray(sq))
                    if sq is not None else None)
        self.cap = row_capacity(cap, minimum=1)
        self._buckets = np.full((C, self.cap), -1, np.int32)
        self._buckets[:, :cap] = buckets
        self._fill = np.bincount(assign, minlength=C).astype(np.int64)
        self._pos = {}
        for c in range(C):
            for s in range(int(self._fill[c])):
                self._pos[int(buckets[c, s])] = (c, s)
        self._mut_init(N)
        return self

    # ---------------------------------------------------------- mutation
    def _encode_batch(self, vectors):
        x = jnp.atleast_2d(jnp.asarray(vectors, jnp.float32))
        rows, sq = D.preprocess_corpus(x, self.metric)
        assign = np.asarray(assign_clusters(rows, self.centroids
                                            .astype(jnp.float32)))
        return np.asarray(rows), (None if sq is None else np.asarray(sq)), \
            assign

    def _bucket_put(self, i: int, c: int) -> None:
        if self._fill[c] == self.cap:
            self.cap *= 2
            grown = np.full((self._buckets.shape[0], self.cap), -1, np.int32)
            grown[:, : self._buckets.shape[1]] = self._buckets
            self._buckets = grown
        self._buckets[c, self._fill[c]] = i
        self._pos[i] = (c, int(self._fill[c]))
        self._fill[c] += 1

    def insert(self, vectors, ids=None) -> np.ndarray:
        rows, sq, assign = self._encode_batch(vectors)
        ids = self._take_ids(rows.shape[0], ids)
        self._write_mirrors(ids, ((self._corpus, rows), (self._sq, sq)))
        for i, c in zip(ids, assign):
            self._bucket_put(int(i), int(c))
        self._record("inserts", len(ids))
        return ids

    def delete(self, ids) -> int:
        n = 0
        for i in np.asarray(ids).reshape(-1):
            pos = self._pos.pop(int(i), None)
            if pos is None:
                continue
            self._buckets[pos] = -1
            n += 1
        if n:
            self._record("deletes", n)
        return n

    def upsert(self, vectors, ids) -> np.ndarray:
        rows, sq, assign = self._encode_batch(vectors)
        ids = self._check_upsert_ids(rows.shape[0], ids)
        self._corpus.write(ids, rows)
        if self._sq is not None:
            self._sq.write(ids, sq)
        for i, c in zip(ids, assign):
            old = self._pos.pop(int(i), None)
            if old is not None:
                self._buckets[old] = -1
            self._bucket_put(int(i), int(c))
        self._record("upserts", len(ids))
        return ids

    def compact(self) -> dict:
        """Repack each bucket row's live slots to the front (tombstone holes
        stop occupying probe positions); bucket capacity is kept."""
        dropped = 0
        for c in range(self._buckets.shape[0]):
            row = self._buckets[c, : self._fill[c]]
            keep = row[row >= 0]
            dropped += int(self._fill[c]) - keep.size
            self._buckets[c, : keep.size] = keep
            self._buckets[c, keep.size: self._fill[c]] = -1
            self._fill[c] = keep.size
            for s, i in enumerate(keep):
                self._pos[int(i)] = (c, s)
        self._record("compactions", 1)
        return {"dropped_tombstones": dropped}

    # ------------------------------------------------------------- query
    def _sync(self) -> None:
        if not self._dirty:
            return
        self.corpus = jnp.asarray(self._corpus.data).astype(self.dtype)
        self.corpus_sq = (jnp.asarray(self._sq.data)
                          if self._sq is not None else None)
        self.buckets = jnp.asarray(self._buckets)
        self._dirty = False

    def query(self, q, k: int = 10, *, allowed=None, nprobe_boost: int = 1):
        self._sync()
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32)).astype(self.dtype)
        nprobe = min(self.nprobe * max(1, int(nprobe_boost)),
                     self.centroids.shape[0])
        buckets = self.buckets
        if allowed is not None:
            # predicate bitmap -> -1 pad sentinel in the bucket table: the
            # jitted ivf_search is unchanged (invariant 6 — a filter is a
            # data change, not a shape change)
            from repro.kernels import ops as kops  # lazy: layering
            buckets = kops.mask_allowed_ids(buckets, jnp.asarray(allowed))
        return ivf_search(self.corpus, self.centroids, buckets, q,
                          metric=self.metric, k=k, nprobe=nprobe, cap=self.cap,
                          corpus_sq=self.corpus_sq)
