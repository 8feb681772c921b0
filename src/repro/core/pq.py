"""Product quantization: compressed corpus + asymmetric distance computation.

The exact engines keep the f32 corpus resident; at MS MARCO scale the HBM
footprint — not compute — caps corpus size. PQ splits each d-dim vector into
``m`` subspaces, k-means-quantizes every subspace to ``ksub`` (<= 256)
centroids, and stores one byte per subspace: d*4 bytes -> m bytes per row
(32x at d=64, m=8).

Queries stay full precision (asymmetric distance computation, ADC): per
query, one (m, ksub) lookup table of subspace partial scores is built
against the codebooks, and a corpus row's score is m table gathers + a sum —
no decode, no f32 corpus touch. Scoring dispatches through
``repro.kernels.ops``: flat scans via ``adc_topk`` (fused Pallas pq_adc
kernel on TPU, fused jnp twin on CPU/GPU) and bucket-probed scans via
``ivf_adc_topk`` (bucket-resident Pallas ivf_adc kernel / probe-looped
twin) — both engines expose the override as ``use_kernel`` and table
precision as ``lut_dtype`` ('bfloat16' halves LUT bytes at a bounded score
error; 'int8' halves them again with per-(query, subspace) scales; see
kernels/pq_adc). ``pq_topk`` below is the original scanned jnp reference,
kept as the tiling-invariance oracle and the benchmark baseline.

Two engines compose out of it:
  * ``PQIndex``       — flat ADC scan over all N codes.
  * ``IVFPQIndex``    — IVF coarse quantizer (repro.core.ivf) over PQ-coded
                        *residuals* (x - centroid), the FAISS IVFADC layout:
                        probe nprobe buckets, ADC-score only their codes —
                        stored bucket-major so the fused kernel path's work
                        scales with nprobe * cap on every metric.
Both optionally keep the raw corpus to exactly re-rank the top ``refine``
ADC candidates (recall repair; production stores park raw rows in slow
storage, so index-resident memory is still codes + codebooks).

Both engines are MUTABLE (repro.core.mutable): inserts encode against the
frozen codebooks and append — the flat engine into a capacity-doubling code
array with a live mask, IVF-PQ by assign -> residual-encode -> block append
into the ``BlockListLayout``. Deletes are tombstones expressed entirely in
the layout (slot id -> -1 pad sentinel), so the fused ADC kernels serve a
churning index without a single kernel change; ``compact()`` repacks once
the tombstone fraction crosses the engine's threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import distances as D
from repro.core.ivf import (BlockListLayout, assign_clusters,
                            assign_from_buckets, build_block_lists,
                            build_buckets, kmeans)
from repro.core.mutable import GrowableRows, MutationMixin
from repro.kernels import ops as kops


def subspace_split(x, m: int):
    """x: (N, d) -> (N, m, dsub), zero-padding d up to a multiple of m."""
    N, d = x.shape
    dsub = -(-d // m)
    pad = m * dsub - d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(N, m, dsub)


# k-means (coarse and per-subspace) trains on at most this many rows; a
# larger corpus trains on a sample drawn from the seed, so training memory
# and time stay flat in N (FAISS caps training points per centroid alike)
MAX_TRAIN_ROWS = 1 << 18
# rows per encode step: bounds the (rows, m, ksub) nearest-centroid scores
# (200 MB at m=96, ksub=256) and the (rows, C) coarse scores
ENCODE_ROWS = 2048


def device_rows(x, start: int, stop: int):
    """Rows [start, stop) of a host array, a device array, or an array
    sharded over several devices, as f32 on one device."""
    part = x[start:stop]
    if isinstance(part, jax.Array) and len(part.sharding.device_set) > 1:
        part = jax.device_put(part, jax.devices()[0])
    return jnp.asarray(part, jnp.float32)


def training_sample(key, x, n_max: int = MAX_TRAIN_ROWS):
    """The rows k-means trains on, as f32 on one device: all of ``x`` when
    it has at most ``n_max`` rows, else ``n_max`` rows drawn without
    replacement (kept in corpus order)."""
    N = x.shape[0]
    if N <= n_max:
        return device_rows(x, 0, N)
    idx = np.sort(np.asarray(jax.random.choice(key, N, (n_max,),
                                               replace=False)))
    if isinstance(x, jax.Array):
        idx = jnp.asarray(idx)
    return device_rows(x[idx], 0, n_max)


def train_pq(key, x, *, m: int, ksub: int = 256, iters: int = 10):
    """Per-subspace Lloyd k-means. x: (N, d) f32 -> codebooks (m, ksub, dsub).

    Zero-padded tail dims train like real dims (their centroids are ~0, so
    they cannot change any ranking). ksub caps at N and 256 (codes are u8).
    Subspaces are column slices of the 2-D rows: a (N, m, dsub) view would
    pad its dsub-wide minor axis to the TPU's 128 lanes.
    """
    assert ksub <= 256, "codes are stored as uint8"
    ksub = min(ksub, x.shape[0])
    x = jnp.asarray(x, jnp.float32)
    d = x.shape[1]
    dsub = -(-d // m)
    if m * dsub > d:
        x = jnp.pad(x, ((0, 0), (0, m * dsub - d)))
    keys = jax.random.split(key, m)
    return jnp.stack([
        kmeans(keys[j], x[:, j * dsub:(j + 1) * dsub], n_clusters=ksub,
               iters=iters)
        for j in range(m)
    ])


@jax.jit
def _pq_encode_rows(codebooks, x):
    m = codebooks.shape[0]
    xs = subspace_split(jnp.asarray(x, jnp.float32), m)  # (N, m, dsub)
    dots = jnp.einsum("nmd,mkd->nmk", xs, codebooks,
                      preferred_element_type=jnp.float32)
    c_sq = jnp.sum(jnp.square(codebooks), axis=-1)  # (m, ksub)
    # argmin ||x - c||^2 == argmax 2 x.c - |c|^2 (|x|^2 constant per row)
    return jnp.argmax(2.0 * dots - c_sq[None], axis=-1).astype(jnp.uint8)


def _chunked(fn, x, *args):
    """Apply a row-wise jitted ``fn(*args, rows)`` over ``x`` in
    ENCODE_ROWS chunks (the last zero-padded, so one executable serves
    all) and yield each chunk's outputs trimmed to its real rows."""
    N = x.shape[0]
    for start in range(0, N, ENCODE_ROWS):
        stop = min(start + ENCODE_ROWS, N)
        rows = device_rows(x, start, stop)
        if N > ENCODE_ROWS and stop - start < ENCODE_ROWS:
            rows = jnp.pad(rows, ((0, ENCODE_ROWS - (stop - start)), (0, 0)))
        out = fn(*args, rows)
        yield jax.tree.map(lambda a: a[:stop - start], out)


def pq_encode(codebooks, x):
    """x: (N, d) -> codes (N, m) uint8 (nearest centroid per subspace),
    encoded ENCODE_ROWS rows at a time."""
    parts = list(_chunked(_pq_encode_rows, x, codebooks))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnames=("d",))
def pq_decode(codebooks, codes, *, d: int):
    """codes: (N, m) -> reconstruction (N, d) from codebook centroids."""
    m = codebooks.shape[0]
    rec = codebooks[jnp.arange(m)[None, :], codes.astype(jnp.int32)]  # (N, m, dsub)
    return rec.reshape(codes.shape[0], -1)[:, :d]


@functools.partial(jax.jit, static_argnames=("metric",))
def adc_tables(codebooks, q, *, metric: str):
    """Per-query subspace score tables. q: (Q, d) -> luts (Q, m, ksub) f32.

    dot:  lut[q, j, c] = q_j . c          (sum over j == q . decode)
    l2:   lut[q, j, c] = -|q_j - c|^2     (sum over j == -|q - decode|^2)
    Higher = closer, matching every other engine's score convention.
    """
    m = codebooks.shape[0]
    qs = subspace_split(jnp.asarray(q, jnp.float32), m)  # (Q, m, dsub)
    dots = jnp.einsum("qmd,mkd->qmk", qs, codebooks,
                      preferred_element_type=jnp.float32)
    if metric == "dot":
        return dots
    assert metric == "l2", metric
    c_sq = jnp.sum(jnp.square(codebooks), axis=-1)  # (m, ksub)
    q_sq = jnp.sum(jnp.square(qs), axis=-1)  # (Q, m)
    return -(q_sq[:, :, None] - 2.0 * dots + c_sq[None])


def adc_scores(luts, codes):
    """Dense ADC scores. luts: (Q, m, ksub); codes: (N, m) -> (Q, N) f32.

    m gathers of (Q, N) — the jnp scoring core shared by pq_topk and the
    bucket path in ivf_pq_search.
    """
    Q = luts.shape[0]
    m = codes.shape[1]
    idx = codes.astype(jnp.int32).T  # (m, N)
    total = jnp.zeros((Q, idx.shape[1]), jnp.float32)
    for j in range(m):
        total = total + jnp.take(luts[:, j, :], idx[j], axis=1)
    return total


@functools.partial(jax.jit, static_argnames=("k", "tile"))
def pq_topk(luts, codes, *, k: int, tile: int = 4096, valid=None):
    """Flat ADC top-k over all codes, tiled like flat_search.

    luts: (Q, m, ksub); codes: (N, m) -> (scores (Q, k), ids (Q, k)).
    Peak memory O(Q * tile), never O(Q * N).
    """
    N = codes.shape[0]
    Q = luts.shape[0]
    k = min(k, N)
    if N <= tile:
        scores = adc_scores(luts, codes)
        return D.topk_scores(scores, k, valid)

    n_tiles = (N + tile - 1) // tile
    pad = n_tiles * tile - N
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    v = jnp.arange(N + pad) < N if valid is None else jnp.pad(valid, (0, pad))
    tiles = codes.reshape(n_tiles, tile, -1)
    v_t = v.reshape(n_tiles, tile)

    def step(carry, xs):
        best_s, best_i = carry
        ti, ct, vt = xs
        scores = jnp.where(vt[None, :], adc_scores(luts, ct), -jnp.inf)
        s, i = jax.lax.top_k(scores, k)
        return D.merge_topk(best_s, best_i, s, i + ti * tile, k), None

    init = (jnp.full((Q, k), -jnp.inf, jnp.float32), jnp.zeros((Q, k), jnp.int32))
    (s, i), _ = jax.lax.scan(step, init, (jnp.arange(n_tiles), tiles, v_t))
    return s, i


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def _exact_rerank(corpus, corpus_sq, cand, q, *, metric: str, k: int):
    """Re-score the top candidates exactly and re-sort. cand: (Q, R) ids
    (-1 = pad). Returns (scores (Q, k), ids (Q, k))."""
    valid = cand >= 0
    safe = jnp.where(valid, cand, 0)
    vecs = jnp.take(corpus, safe, axis=0)  # (Q, R, d)
    # exact means f32: the chip's default matmul precision would round both
    # operands to bf16 and reorder near-ties the re-rank exists to settle
    dots = jnp.einsum("qd,qrd->qr", q.astype(jnp.float32),
                      vecs.astype(jnp.float32), preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "dot":
        scores = dots
    else:
        sq = (jnp.take(corpus_sq, safe, axis=-1) if corpus_sq is not None
              else jnp.sum(jnp.square(vecs.astype(jnp.float32)), -1))
        q_sq = jnp.sum(jnp.square(q.astype(jnp.float32)), -1)
        scores = -(q_sq[:, None] - 2.0 * dots + sq)
    scores = jnp.where(valid, scores, -jnp.inf)
    s, pos = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    ids = jnp.take_along_axis(cand, pos, axis=-1)
    return _pad_to_k(*D.mask_invalid_ids(s, ids), k)


def _pad_to_k(s, ids, k: int):
    kk = s.shape[-1]
    if kk < k:
        s = jnp.pad(s, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - kk)), constant_values=-1)
    return s, ids


def pq_search(codebooks, codes, corpus, q, *, metric: str, k: int,
              refine: int = 0, corpus_sq=None, valid=None, allowed=None,
              use_kernel=None, lut_dtype: str = "float32"):
    """Flat ADC search (+ optional exact re-rank of the top ``refine``).

    Deliberately NOT one monolithic jit: an orchestrator over jitted stages
    (LUT build -> ops.adc_topk dispatch -> exact re-rank). The stage
    boundary is what lets the dispatcher materialize a bf16-rounded LUT
    once before the scan — fused into a single program, XLA re-rounds every
    gathered element (see kernels.ops._round_lut_bf16). Scoring goes
    through the backend dispatcher (Pallas kernel on TPU, fused jnp twin
    elsewhere; ``use_kernel``/``lut_dtype`` override). ``valid`` masks
    tombstoned/pad rows of a mutable corpus out of the scan; ``allowed``
    (the predicate engine's bitmap, invariant 6) ANDs into it inside the
    dispatcher. corpus is only touched (and may be None) when refine > 0.
    """
    N = codes.shape[0]
    luts = adc_tables(codebooks, q, metric=metric)
    if not refine:
        s, i = kops.adc_topk(codes, luts, k=k, valid=valid, allowed=allowed,
                             use_kernel=use_kernel, lut_dtype=lut_dtype)
        return D.mask_invalid_ids(s, i)
    R = min(max(refine, k), N)
    s, cand = kops.adc_topk(codes, luts, k=R, valid=valid, allowed=allowed,
                            use_kernel=use_kernel, lut_dtype=lut_dtype)
    _, cand = D.mask_invalid_ids(s, cand)
    return _exact_rerank(corpus, corpus_sq, cand, q, metric=metric, k=k)


def expand_visit(probe, block_table, *, steps_per_probe: int, pad_block):
    """Probe ids -> (Q, nprobe * steps_per_probe) visit table of inverted-
    list block ids. ``block_table`` (C, steps_per_probe) lists the storage
    blocks cluster c owns in visit order, -1 = absent — absent steps (tails
    of short clusters) point at ``pad_block`` (the shared all-pad row, or -1
    for the sharded front which retargets per shard). The single source of
    the visit contract — used by ivf_pq_search and the DistributedIVFPQ
    plan. An explicit table rather than (bstart, bcnt) ranges so ONLINE
    INSERTS can spill a cluster into any free block without relayout."""
    Q, nprobe = probe.shape
    rows = jnp.take(block_table, probe, axis=0)  # (Q, nprobe, spp)
    return jnp.where(rows >= 0, rows,
                     pad_block).reshape(Q, nprobe * steps_per_probe)


def block_table_from_ranges(bstart, bcnt, steps_per_probe: int):
    """(bstart, bcnt) contiguous ranges (build_block_lists output) -> the
    explicit (C, steps_per_probe) block table expand_visit consumes."""
    r = jnp.arange(steps_per_probe, dtype=jnp.int32)[None, :]
    bstart = jnp.asarray(bstart, jnp.int32)
    bcnt = jnp.asarray(bcnt, jnp.int32)
    return jnp.where(r < bcnt[:, None], bstart[:, None] + r, -1)


def probe_luts(codebooks, centroids, q, probe, c_scores, *, metric: str):
    """(luts, coarse) for the bucket-resident dispatch, per metric:
      dot: one shared (Q, m, ksub) LUT; coarse[q, p] = q . centroid_p
           (c_scores for dot IS q . centroids, so it's a gather).
      l2:  per-(query, probe) residual LUTs on t = q - centroid_p,
           coarse None (ivf_adc_topk zero-fills)."""
    Q, nprobe = probe.shape
    m = codebooks.shape[0]
    if metric == "dot":
        return (adc_tables(codebooks, q, metric="dot"),
                jnp.take_along_axis(c_scores, probe, axis=1))
    t = q[:, None, :] - jnp.take(centroids, probe, axis=0)  # (Q, nprobe, d)
    luts = adc_tables(codebooks, t.reshape(Q * nprobe, -1), metric="l2")
    return luts.reshape(Q, nprobe, m, -1), None


@functools.partial(jax.jit,
                   static_argnames=("metric", "k", "refine", "use_kernel",
                                    "lut_dtype"))
def _ivf_scan_all(codebooks, codes, centroids, corpus, corpus_sq, assign,
                  valid, q, *, metric: str, k: int, refine: int,
                  use_kernel, lut_dtype: str):
    """The PR-2 augmented-LUT escape hatch of ivf_pq_search, as its own
    jitted stage: the coarse term folds into the flat adc_topk scan as an
    (m+1)-th subspace and ALL N codes stream through (dot only)."""
    N = codes.shape[0]
    ksub = codebooks.shape[1]
    C = centroids.shape[0]
    qc = jnp.einsum("qd,cd->qc", q, centroids.astype(jnp.float32),
                    preferred_element_type=jnp.float32)  # (Q, C)
    width = max(ksub, C)
    luts = adc_tables(codebooks, q, metric="dot")  # (Q, m, ksub)
    luts = jnp.pad(luts, ((0, 0), (0, 0), (0, width - ksub)))
    coarse = jnp.pad(qc, ((0, 0), (0, width - C)))[:, None, :]
    luts_aug = jnp.concatenate([luts, coarse], axis=1)  # (Q, m+1, width)
    codes_aug = jnp.concatenate(
        [codes.astype(jnp.int32), assign.astype(jnp.int32)[:, None]],
        axis=1)  # (N, m+1)
    R = min(max(refine, k), N)
    s, ids = kops.adc_topk(codes_aug, luts_aug, k=R, valid=valid,
                           use_kernel=use_kernel, lut_dtype=lut_dtype)
    s, ids = D.mask_invalid_ids(s, ids)
    if refine:
        return _exact_rerank(corpus, corpus_sq, ids, q, metric=metric, k=k)
    return _pad_to_k(s[:, :k], ids[:, :k], k)


@functools.partial(jax.jit,
                   static_argnames=("metric", "nprobe", "steps_per_probe",
                                    "pad_block", "adaptive"))
def _ivf_probe_stage(codebooks, centroids, q, block_table, threshold, *,
                     metric: str, nprobe: int, steps_per_probe: int,
                     pad_block: int, adaptive: bool):
    """Coarse stage of ivf_pq_search: score centroids, pick probes, expand
    the visit table, build (luts, coarse). One jitted program so the whole
    coarse path fuses; the ADC dispatch that follows runs OUTSIDE jit with
    this stage's concrete outputs — that host boundary is what lets
    ``ops.ivf_adc_topk`` build the blocked segmented schedule.

    ``adaptive`` applies query-adaptive nprobe as pure masking on the
    fixed-width table: probes whose coarse-score gap to the query's best
    probe exceeds ``threshold`` have their visit steps retargeted at the
    pad block (so the blocked schedule drops the work entirely) and their
    coarse entry set to NEG_INF (so the per-query grid knocks them out).
    Probe 0 always survives. Returns (visit, luts, coarse, eff_nprobe)
    with eff_nprobe the per-query count of surviving probes."""
    c_scores = D.pairwise_scores(q, centroids,
                                 metric if metric == "dot" else "l2")
    c_top, probe = jax.lax.top_k(c_scores, nprobe)  # (Q, nprobe), descending
    visit = expand_visit(probe, block_table, steps_per_probe=steps_per_probe,
                         pad_block=pad_block)
    luts, coarse = probe_luts(codebooks, centroids, q, probe, c_scores,
                              metric=metric)
    Q = q.shape[0]
    if coarse is None:
        coarse = jnp.zeros((Q, nprobe), jnp.float32)
    if adaptive:
        active = (c_top[:, :1] - c_top) <= threshold
        active = active.at[:, 0].set(True)
        visit = jnp.where(jnp.repeat(active, steps_per_probe, axis=1),
                          visit, pad_block)
        coarse = jnp.where(active, coarse, kops.NEG_INF)
        eff = jnp.sum(active, axis=1).astype(jnp.int32)
    else:
        eff = jnp.full((Q,), nprobe, jnp.int32)
    return visit, luts, coarse, eff


def ivf_pq_search(codebooks, codes, centroids, buckets, corpus, q, *,
                  metric: str, k: int, nprobe: int, refine: int = 0,
                  corpus_sq=None, assign=None, valid=None, allowed=None,
                  block_lists=None,
                  steps_per_probe: int = 1, use_kernel=None,
                  lut_dtype: str = "float32", scan_all: bool = False,
                  adaptive_nprobe=None, adc_mode: str = "auto",
                  qblk=None, adc_stats=None, autotune=None,
                  sched_cache=None, sched_key=()):
    """IVF-ADC: probe nprobe coarse buckets, ADC-score their residual codes.

    codes are PQ codes of (x - centroid[assign]); scoring must therefore use
    residual geometry per probed bucket:
      dot: q.x = q.centroid_p + q.residual          -> one LUT on q, plus a
           per-probe scalar offset q.centroid_p.
      l2:  |q - x|^2 = |(q - centroid_p) - residual|^2 -> per-(query, probe)
           LUTs on t = q - centroid_p.

    Both metrics execute on the bucket-resident fused path
    (``kops.ivf_adc_topk``: Pallas ivf_adc kernel on TPU, fused jnp twin
    elsewhere): probes expand into a visit table over the block-aligned
    layout in ``block_lists`` = (bucket_codes (B, blk, m), bucket_ids
    (B, blk), block_table (C, steps_per_probe)) whose last storage row is
    the shared all-pad block (IVFPQIndex maintains it online via
    repro.core.ivf.BlockListLayout; the legacy 4-tuple with (bstart, bcnt)
    contiguous ranges is still accepted and converted in-graph), and work
    scales with the probed candidate count instead of N. nprobe genuinely
    prunes on EVERY backend and metric. Tombstoned rows carry slot id -1 in
    ``bucket_ids`` and score exactly like pad slots — the kernel is
    mutation-oblivious. Callers without a prebuilt layout (tests, one-off
    scans) may pass ``block_lists=None``: the fixed-capacity ``buckets``
    table is treated in-graph as a one-block-per-cluster layout (blk = cap,
    steps_per_probe forced to 1).

    ``scan_all=True`` is the explicit escape hatch to the PR-2
    augmented-LUT scan (dot only, requires row-major ``codes`` +
    ``assign``): the coarse term folds into the flat adc_topk scan as an
    (m+1)-th subspace and ALL N codes stream through — candidates are a
    superset of any nprobe's, at N/candidates times the scoring work
    (``valid`` masks tombstoned rows on this path). Useful when the probed
    candidate count approaches N (tiny corpora, recall studies); never the
    default.

    ``lut_dtype`` ('float32'/'bfloat16'/'int8') applies to either backend's
    tables. Returns (scores (Q, k), ids (Q, k)); pad slots are -inf / -1.

    Deliberately NOT one monolithic jit (the pq_search precedent): an
    orchestrator over jitted stages — coarse probe stage -> host-level
    ``kops.ivf_adc_topk`` dispatch -> jitted exact re-rank. The host
    boundary after the probe stage is what makes the visit table CONCRETE,
    which is what lets the dispatcher sort it into the blocked/run-resident
    segmented schedules (``adc_mode``/``qblk``; 'auto' consults the
    measured autotuner ledger — ``autotune`` overrides it, see
    kernels/ops and kernels/autotune). ``sched_cache``/``sched_key`` pass
    the plan ledger's ScheduleCache context through so repeated batches
    skip the host sort. Callers that must stay inside one jit (the
    distributed plan) call the stages themselves and always serve the
    per-query grid.

    ``adaptive_nprobe`` (float threshold, None = off) enables
    query-adaptive probing: probes whose coarse-score gap to the best
    probe exceeds the threshold are masked off the fixed-width visit
    table before any ADC work (see _ivf_probe_stage). ``adc_stats`` (dict,
    optional) receives the dispatch decision, schedule stats, and
    'eff_nprobe' — the mean per-query surviving probe count (== nprobe,
    sync-free, when adaptive probing is off).

    ``allowed`` (optional bool bitmap over the id space — the predicate
    engine's output, invariant 6) reaches the bucket-resident dispatch as
    a ``bucket_ids`` rewrite (filtered slots -> the -1 pad sentinel; see
    kops.ivf_adc_topk) and the scan_all path as a ``valid`` AND — either
    way the compiled programs are the unfiltered ones.
    """
    q = jnp.asarray(q, jnp.float32)
    if allowed is not None and scan_all:
        a = jnp.asarray(allowed)
        N = codes.shape[0]
        if a.shape[0] < N:
            a = jnp.pad(a, (0, N - a.shape[0]))
        a = a[:N]
        valid = a if valid is None else valid & a

    if scan_all:
        assert metric == "dot", "scan_all folds the coarse term into the " \
            "flat scan as an extra ADC subspace — dot/cosine only"
        assert codes is not None and assign is not None, \
            "scan_all needs row-major codes + assignments (IVFPQIndex keeps " \
            "them only when constructed with scan_all=True)"
        return _ivf_scan_all(codebooks, codes, centroids, corpus, corpus_sq,
                             assign, valid, q, metric=metric, k=k,
                             refine=refine, use_kernel=use_kernel,
                             lut_dtype=lut_dtype)

    if block_lists is None:
        # eager fallback: the fixed-cap bucket table IS a block layout
        # with one cap-wide block per cluster (+ the shared all-pad block)
        C, cap = buckets.shape
        bucket_ids = jnp.concatenate(
            [buckets, jnp.full((1, cap), -1, buckets.dtype)]).astype(jnp.int32)
        bucket_codes = jnp.take(codes.astype(jnp.int32),
                                jnp.clip(bucket_ids, 0), axis=0)
        block_table = jnp.arange(C, dtype=jnp.int32)[:, None]
        spp = 1
    elif len(block_lists) == 4:  # legacy contiguous-range form
        bucket_codes, bucket_ids, bstart, bcnt = block_lists
        spp = steps_per_probe
        block_table = block_table_from_ranges(bstart, bcnt, spp)
    else:
        bucket_codes, bucket_ids, block_table = block_lists
        spp = steps_per_probe
    blk = bucket_codes.shape[1]
    pad_block = bucket_ids.shape[0] - 1
    adaptive = adaptive_nprobe is not None
    threshold = jnp.float32(adaptive_nprobe if adaptive else 0.0)
    with obs.span("ivf.probe"):
        visit, luts, coarse, eff = _ivf_probe_stage(
            codebooks, centroids, q, block_table, threshold, metric=metric,
            nprobe=nprobe, steps_per_probe=spp, pad_block=pad_block,
            adaptive=adaptive)
    R = min(max(refine, k), nprobe * spp * blk)
    s, ids = kops.ivf_adc_topk(bucket_codes, bucket_ids, visit, luts, k=R,
                               coarse=coarse, steps_per_probe=spp,
                               use_kernel=use_kernel, lut_dtype=lut_dtype,
                               mode=adc_mode, qblk=qblk,
                               pad_block=pad_block, stats=adc_stats,
                               autotune=autotune, sched_cache=sched_cache,
                               sched_key=sched_key, allowed=allowed)
    if adc_stats is not None:
        # only the adaptive path has a data-dependent probe count worth a
        # host sync; with masking off every query keeps all nprobe probes
        adc_stats["eff_nprobe"] = (float(jnp.mean(eff)) if adaptive
                                   else float(nprobe))
    if refine:
        with obs.span("ivf.rerank"):
            return _exact_rerank(corpus, corpus_sq, ids, q, metric=metric,
                                 k=k)
    return _pad_to_k(s[:, :k], ids[:, :k], k)


def train_ivf_pq(x, *, metric: str, n_clusters: int, m: int, ksub: int,
                 iters: int, seed: int):
    """Train the IVF-PQ quantizers on (a sample of) the raw rows ``x`` ->
    (centroids (C, d), codebooks (m, ksub, dsub)): coarse k-means in the
    search geometry (cosine clusters unit vectors), then PQ on the
    sample's residuals. Shared by ``IVFPQIndex`` and the sharded front."""
    key = jax.random.PRNGKey(seed)
    corpus, _ = D.preprocess_corpus(
        training_sample(jax.random.fold_in(key, 2), x), metric)
    cent = kmeans(key, corpus, n_clusters=n_clusters, iters=iters)
    if metric == "cosine":
        cent = D.l2_normalize(cent)
    residuals = corpus - jnp.take(cent, assign_clusters(corpus, cent), axis=0)
    codebooks = train_pq(jax.random.fold_in(key, 1), residuals, m=m,
                         ksub=ksub, iters=iters)
    return cent, codebooks


@functools.partial(jax.jit, static_argnames=("metric",))
def _ivf_pq_encode_rows(codebooks, centroids, x, *, metric: str):
    rows, sq = D.preprocess_corpus(x, metric)
    assign = assign_clusters(rows, centroids)
    residuals = rows - jnp.take(centroids, assign, axis=0)
    return rows, sq, assign.astype(jnp.int32), _pq_encode_rows(codebooks,
                                                               residuals)


def encode_ivf_pq(codebooks, centroids, x, *, metric: str,
                  keep_rows: bool):
    """Stream raw rows ``x`` (host, device, or sharded) through the IVF-PQ
    encoder ENCODE_ROWS at a time -> host (rows or None, sq or None,
    assign (N,) int32, codes (N, m) uint8), where rows are the
    preprocessed (cosine: normalized) vectors the exact re-rank reads and
    sq their squared norms (l2 only). Device memory stays one chunk deep,
    so a corpus larger than one device's memory encodes too."""
    parts = {"rows": [], "sq": [], "assign": [], "codes": []}
    for rows, sq, assign, codes in _chunked(
            functools.partial(_ivf_pq_encode_rows, metric=metric), x,
            codebooks, centroids):
        if keep_rows:
            parts["rows"].append(np.asarray(rows))
        if sq is not None:
            parts["sq"].append(np.asarray(sq))
        parts["assign"].append(np.asarray(assign))
        parts["codes"].append(np.asarray(codes))
    return tuple(np.concatenate(parts[key]) if parts[key] else None
                 for key in ("rows", "sq", "assign", "codes"))


def _check_snapshot(state, engine: str, metric: str):
    """Codes are metric-specific (cosine trains on normalized rows, l2 LUTs
    differ from dot) — restoring across engine/metric would silently rank
    wrong, so snapshots carry both and restore refuses a mismatch."""
    got_engine = str(state.get("engine", engine))
    got_metric = str(state.get("metric", metric))
    if got_engine != engine or got_metric != metric:
        raise ValueError(
            f"snapshot was saved by engine={got_engine!r} metric={got_metric!r},"
            f" cannot restore into engine={engine!r} metric={metric!r}")


def _snapshot_live(state, n: int) -> np.ndarray:
    """Tombstone state persisted since the mutation lifecycle; PR-1-format
    snapshots (no ``live`` leaf) restore as fully live."""
    if "live" in state:
        return np.asarray(state["live"]).astype(bool).reshape(n)
    return np.ones(n, bool)


class PQIndex(MutationMixin):
    """Flat product-quantized engine: m bytes/row, ADC scan, optional exact
    re-rank of the top ``refine`` candidates (refine=0 drops the raw corpus
    entirely — pure compressed-domain search).

    Mutable: inserts ENCODE WITH THE FROZEN CODEBOOKS and append into a
    capacity-doubling code array; a staleness counter tracks how much of the
    index the codebooks never saw (``stale_fraction`` /
    ``needs_retrain``) — codebook drift repair is retraining, flagged here,
    not hidden. Deletes tombstone the live mask the ADC dispatch already
    honors.
    """

    def __init__(self, metric: str = "cosine", m: int = 8, ksub: int = 256,
                 kmeans_iters: int = 10, refine: int = 32, seed: int = 0,
                 use_kernel=None, lut_dtype: str = "float32",
                 retrain_threshold: float = 0.25):
        assert metric in D.METRICS
        assert lut_dtype in kops.ADC_LUT_DTYPES, lut_dtype
        self.metric = metric
        self.m = m
        self.ksub = ksub
        self.kmeans_iters = kmeans_iters
        self.refine = refine
        self.seed = seed
        self.use_kernel = use_kernel  # None = auto (Pallas on TPU, jnp twin off)
        self.lut_dtype = lut_dtype
        self.retrain_threshold = retrain_threshold
        self.codebooks = self.codes = self.corpus = self.corpus_sq = None
        self.valid = None
        self._codes = self._corpus = self._sq = self._valid = None
        self.d = 0
        self.inserted_since_train = 0
        self._mut_init(0)

    @property
    def size(self) -> int:
        return 0 if self._valid is None else int(self._valid.data.sum())

    @property
    def shape_key(self) -> tuple:
        return (0 if self._codes is None else self._codes.capacity,)

    @property
    def stale_fraction(self) -> float:
        """Fraction of live rows encoded after codebook training."""
        return self.inserted_since_train / max(self.size, 1)

    @property
    def needs_retrain(self) -> bool:
        return self.stale_fraction > self.retrain_threshold

    def _init_storage(self, codes, corpus, sq, live) -> None:
        n = codes.shape[0]
        self._codes = GrowableRows.from_array(np.asarray(codes))
        self._valid = GrowableRows.from_array(np.asarray(live, bool))
        self._corpus = (GrowableRows.from_array(np.asarray(corpus))
                        if corpus is not None else None)
        self._sq = (GrowableRows.from_array(np.asarray(sq))
                    if sq is not None else None)
        self.inserted_since_train = 0
        self._mut_init(n)
        self._sync()  # device mirrors valid immediately after load/restore

    def load(self, vectors):
        x = jnp.asarray(vectors, jnp.float32)
        self.d = x.shape[1]
        corpus, sq = D.preprocess_corpus(x, self.metric)
        self.codebooks = train_pq(jax.random.PRNGKey(self.seed), corpus,
                                  m=self.m, ksub=self.ksub,
                                  iters=self.kmeans_iters)
        codes = pq_encode(self.codebooks, corpus)
        self._init_storage(codes, corpus if self.refine else None, sq,
                           np.ones(x.shape[0], bool))
        return self

    # ---------------------------------------------------------- mutation
    def _encode_batch(self, vectors):
        x = jnp.atleast_2d(jnp.asarray(vectors, jnp.float32))
        rows, sq = D.preprocess_corpus(x, self.metric)
        codes = np.asarray(pq_encode(self.codebooks, rows))
        return codes, np.asarray(rows), \
            None if sq is None else np.asarray(sq)

    def _write_rows(self, ids, codes, rows, sq) -> None:
        self._write_mirrors(ids, ((self._codes, codes), (self._corpus, rows),
                                  (self._sq, sq),
                                  (self._valid, np.ones(len(ids), bool))))

    def insert(self, vectors, ids=None) -> np.ndarray:
        codes, rows, sq = self._encode_batch(vectors)
        ids = self._take_ids(codes.shape[0], ids)
        self._write_rows(ids, codes, rows, sq)
        self.inserted_since_train += len(ids)
        self._record("inserts", len(ids))
        return ids

    def delete(self, ids) -> int:
        ids = self._tombstone_valid(ids)
        if ids.size:
            self._record("deletes", ids.size)
        return int(ids.size)

    def upsert(self, vectors, ids) -> np.ndarray:
        codes, rows, sq = self._encode_batch(vectors)
        ids = self._check_upsert_ids(codes.shape[0], ids)
        self._write_rows(ids, codes, rows, sq)
        self.inserted_since_train += len(ids)
        self._record("upserts", len(ids))
        return ids

    def compact(self) -> dict:
        """Ids are addresses into the flat code array — the live mask is the
        whole tombstone story, nothing repacks. Counted for parity."""
        self._record("compactions", 1)
        return {"dropped_tombstones": 0}

    def reserve(self, extra_rows: int) -> tuple:
        """Pre-size capacity buckets for a planned ingest volume (see
        IVFPQIndex.reserve)."""
        for g in (self._codes, self._corpus, self._sq, self._valid):
            if g is not None:
                g.reserve(self.next_id + extra_rows)
        self._dirty = True
        return self.shape_key

    # ------------------------------------------------------------- query
    def _sync(self) -> None:
        if not self._dirty:
            return
        self.codes = jnp.asarray(self._codes.data)
        mask = self._valid.data.copy()
        mask[self._valid.n:] = False
        self.valid = jnp.asarray(mask)
        self.corpus = (jnp.asarray(self._corpus.data)
                       if self._corpus is not None else None)
        self.corpus_sq = (jnp.asarray(self._sq.data)
                          if self._sq is not None else None)
        self._dirty = False

    def query(self, q, k: int = 10, *, allowed=None):
        self._sync()
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
        metric = self.metric
        if metric == "cosine":
            q = D.l2_normalize(q)
            metric = "dot"  # corpus rows were normalized at load time
        return pq_search(self.codebooks, self.codes, self.corpus, q,
                         metric=metric, k=min(k, max(self.size, 1)),
                         refine=self.refine, corpus_sq=self.corpus_sq,
                         valid=self.valid, allowed=allowed,
                         use_kernel=self.use_kernel,
                         lut_dtype=self.lut_dtype)

    # ------------------------------------------------------- persistence
    def state_dict(self):
        n = self.next_id
        live = self._valid.data[:n].copy()
        state = {"engine": np.asarray("pq"), "metric": np.asarray(self.metric),
                 "codebooks": self.codebooks,
                 "codes": jnp.asarray(self._codes.data[:n]),
                 "live": live,
                 "generation": np.asarray(self.generation, np.int64),
                 "d": jnp.asarray(self.d, jnp.int32)}
        if self._corpus is not None:
            state["corpus"] = jnp.asarray(self._corpus.data[:n])
        if self._sq is not None:
            state["corpus_sq"] = jnp.asarray(self._sq.data[:n])
        return state

    def load_state(self, state):
        _check_snapshot(state, "pq", self.metric)
        self.codebooks = jnp.asarray(state["codebooks"], jnp.float32)
        codes = np.asarray(state["codes"]).astype(np.uint8)
        self.d = int(state["d"])
        n = codes.shape[0]
        corpus = (np.asarray(state["corpus"], np.float32)
                  if "corpus" in state else None)
        sq = (np.asarray(state["corpus_sq"], np.float32)
              if "corpus_sq" in state else None)
        if corpus is None:
            self.refine = 0
        self._init_storage(codes, corpus, sq, _snapshot_live(state, n))
        self.generation = int(state.get("generation", 0))
        self.m = int(self.codebooks.shape[0])
        self.ksub = int(self.codebooks.shape[1])
        return self

    def memory_bytes(self, include_raw: bool = False) -> int:
        """Index-resident bytes: codes + live mask + codebooks (+ raw
        re-rank corpus), at ALLOCATED (capacity-bucket) sizes — mutable
        storage reports what it holds, not what it wishes it held."""
        total = (self._codes.data.size + self._valid.data.size
                 + self.codebooks.size * 4)
        if self._sq is not None:
            total += self._sq.data.size * 4
        if include_raw and self._corpus is not None:
            total += self._corpus.data.size * 4
        return int(total)


class IVFPQIndex(MutationMixin):
    """IVF coarse quantizer over PQ-coded residuals + exact re-ranking —
    the memory/recall rung the exact engines cannot reach (FAISS IVFADC).

    Codes live in the BLOCK-ALIGNED bucket-major layout
    (``repro.core.ivf.BlockListLayout``: slot table + co-located codes +
    per-cluster block tables, capacity-bucketed) so the fused
    bucket-resident kernel path DMAs one probed block per grid program at
    <= blk-1 tail pad slack per cluster. The layout is the WHOLE mutation
    story: inserts assign -> residual-encode -> append into the cluster's
    last ragged block (spilling to a fresh block when full), deletes
    retarget the slot id to the -1 pad sentinel the kernel already knocks
    out, and ``compact()`` (auto-triggered past ``compact_threshold``
    tombstone fraction) repacks without changing device shapes. The
    row-major (N, m) copy is reconstructed on demand for snapshots (which
    stay at the PR-1 format, now with a ``live`` tombstone leaf and a
    generation stamp) and kept resident only under ``scan_all=True`` (the
    all-codes escape hatch also needs ``assign``).
    """

    def __init__(self, metric: str = "cosine", n_clusters: int = 0,
                 nprobe: int = 8, m: int = 8, ksub: int = 256,
                 kmeans_iters: int = 10, refine: int = 32, seed: int = 0,
                 use_kernel=None, lut_dtype: str = "float32",
                 scan_all: bool = False, block_size: int = 32,
                 compact_threshold: float = 0.3, adc_mode: str = "auto",
                 adaptive_nprobe=None, qblk=None):
        assert metric in D.METRICS
        assert lut_dtype in kops.ADC_LUT_DTYPES, lut_dtype
        assert adc_mode in kops.ADC_MODES, adc_mode
        self.metric = metric
        self.n_clusters = n_clusters  # 0 => sqrt(N) at load time
        self.nprobe = nprobe
        self.m = m
        self.ksub = ksub
        self.kmeans_iters = kmeans_iters
        self.refine = refine
        self.seed = seed
        self.use_kernel = use_kernel  # None = auto (Pallas on TPU, jnp twin off)
        self.lut_dtype = lut_dtype
        self.scan_all = scan_all  # True: PR-2 all-codes augmented-LUT scan
        self.block_size = block_size  # inverted-list block width (x8)
        self.compact_threshold = compact_threshold
        self.adc_mode = adc_mode  # grid: auto/blocked/per_query/run_resident
        self.adaptive_nprobe = adaptive_nprobe  # coarse-gap threshold, None=off
        self.qblk = qblk  # grouped-grid query-group width; None = autotuned
        # dispatch telemetry: batches served per grid (probe batches counted
        # both under their grid and under 'probes'), running sums for the
        # mean sharing factor / effective nprobe, and the grid steps of
        # the batches whose visit table came to the host with those that
        # visit a real block (serve.engine surfaces them)
        self.adc_stats = {"blocked": 0, "per_query": 0, "run_resident": 0,
                          "probes": 0, "crossover": None,
                          "sharing_sum": 0.0, "eff_nprobe_sum": 0.0,
                          "batches": 0, "steps": 0, "real_steps": 0}
        # installed by the owning VectorDB front: the plan ledger's
        # ScheduleCache + its (bucket, generation) context for this batch
        self.sched_cache = None
        self._sched_ctx = ()
        self.codebooks = self.codes = self.centroids = None
        self.codes_bm = self.bucket_ids = self.block_table = None
        self.layout = None
        self.spp = 1  # blocks per probe (static visit-table width)
        self.assign = self.valid = None
        self._codes_rm = self._assign = self._valid = None  # scan_all mirrors
        self._corpus = self._sq = None
        self.corpus = self.corpus_sq = None
        self.d = 0
        self.n = 0  # id-space size (append-only; `size` is the live count)
        self._mut_init(0)

    @property
    def size(self) -> int:
        return 0 if self.layout is None else int(self.layout.live)

    @property
    def shape_key(self) -> tuple:
        if self.layout is None:
            return (0,)
        return self.layout.shape_key + (
            0 if self._corpus is None else self._corpus.capacity,)

    def _finalize_layout(self, codes, assign, live=None):
        """Build the mutable block layout (load AND restore both land here —
        one reconstruction path, so a PR-1 row-major snapshot re-derives
        per-cluster tail counts identically to a fresh load); keep row-major
        mirrors only for scan_all."""
        codes = np.asarray(codes)
        assign = np.asarray(assign)
        n = codes.shape[0]
        C = self.centroids.shape[0]
        self.layout = BlockListLayout.from_assign(
            assign, C, blk=self.block_size, payload=codes, live=live)
        if self.scan_all:
            self._codes_rm = GrowableRows.from_array(codes)
            self._assign = GrowableRows.from_array(assign.astype(np.int32))
            self._valid = GrowableRows.from_array(
                np.ones(n, bool) if live is None else np.asarray(live, bool))
        else:
            self._codes_rm = self._assign = self._valid = None
            self.codes = self.assign = self.valid = None
        self.n = n
        self._mut_init(n)
        self._sync()  # device mirrors valid immediately after load/restore

    def load(self, vectors):
        """Index raw rows (host, device, or sharded): train the quantizers
        on a sample, then encode in chunks — the full corpus is never
        resident on the device here, only the re-rank copy ``_sync``
        uploads when ``refine`` > 0."""
        N, self.d = vectors.shape
        C = self.n_clusters or max(1, int(np.sqrt(N)))
        C = min(C, N)
        self.centroids, self.codebooks = train_ivf_pq(
            vectors, metric=self.metric, n_clusters=C, m=self.m,
            ksub=self.ksub, iters=self.kmeans_iters, seed=self.seed)
        rows, sq, assign, codes = encode_ivf_pq(
            self.codebooks, self.centroids, vectors, metric=self.metric,
            keep_rows=bool(self.refine))
        self._corpus = GrowableRows.from_array(rows) if self.refine else None
        self._sq = GrowableRows.from_array(sq) if sq is not None else None
        self._finalize_layout(codes, assign)
        return self

    # ---------------------------------------------------------- mutation
    def _encode_batch(self, vectors):
        rows, sq, assign, codes = encode_ivf_pq(
            self.codebooks, self.centroids, np.atleast_2d(vectors),
            metric=self.metric, keep_rows=True)
        return codes, assign, rows, sq

    def _write_side(self, ids, assign, codes, rows, sq) -> None:
        self._write_mirrors(ids, ((self._corpus, rows), (self._sq, sq),
                                  (self._codes_rm, codes),
                                  (self._assign, assign.astype(np.int32)),
                                  (self._valid, np.ones(len(ids), bool))))

    def insert(self, vectors, ids=None) -> np.ndarray:
        """assign -> residual-encode -> block append (amortized O(1)/row)."""
        codes, assign, rows, sq = self._encode_batch(vectors)
        ids = self._take_ids(codes.shape[0], ids)
        self.layout.insert_rows(ids, assign, codes)
        self._write_side(ids, assign, codes, rows, sq)
        self.n = self.next_id
        self._record("inserts", len(ids))
        return ids

    def delete(self, ids) -> int:
        n = self.layout.delete_rows(ids)
        if self._valid is not None:
            dead = np.asarray(ids, np.int64).reshape(-1)
            dead = dead[(dead >= 0) & (dead < self._valid.n)]
            self._valid.data[dead] = False
        if n:
            self._record("deletes", n)
            self._maybe_compact()
        return n

    def upsert(self, vectors, ids) -> np.ndarray:
        """Re-encode existing ids in place: the old slot tombstones, the row
        re-appends under ITS OWN id in its (possibly different) new cluster."""
        codes, assign, rows, sq = self._encode_batch(vectors)
        ids = self._check_upsert_ids(codes.shape[0], ids)
        self.layout.delete_rows(ids)
        self.layout.insert_rows(ids, assign, codes)
        self._write_side(ids, assign, codes, rows, sq)
        self._record("upserts", len(ids))
        self._maybe_compact()
        return ids

    def _maybe_compact(self) -> None:
        if (self.compact_threshold is not None
                and self.layout.tombstone_fraction > self.compact_threshold):
            self.compact()

    def reserve(self, extra_rows: int,
                extra_blocks_per_cluster: int = 0) -> tuple:
        """Pre-size every capacity bucket for a planned ingest volume, so
        the steady-state insert stream stays inside ONE shape bucket and
        its queries never recompile. Returns the resulting shape_key."""
        self.layout.reserve(extra_rows, extra_blocks_per_cluster)
        for g in (self._corpus, self._sq, self._codes_rm, self._assign,
                  self._valid):
            if g is not None:
                g.reserve(self.next_id + extra_rows)
        self._dirty = True
        return self.shape_key

    def compact(self) -> dict:
        """Repack the block lists, dropping tombstones (capacity buckets are
        kept, so compaction cannot recompile a query plan)."""
        stats = self.layout.compact()
        self._record("compactions", 1)
        return stats

    # ------------------------------------------------------------- query
    def _sync(self) -> None:
        if not self._dirty:
            return
        lay = self.layout
        self.codes_bm = jnp.asarray(lay.codes)
        self.bucket_ids = jnp.asarray(lay.slots)
        self.block_table = jnp.asarray(lay.block_table)
        self.spp = lay.steps_per_probe
        if self.scan_all:
            self.codes = jnp.asarray(self._codes_rm.data)
            self.assign = jnp.asarray(self._assign.data, jnp.int32)
            mask = self._valid.data.copy()
            mask[self._valid.n:] = False
            self.valid = jnp.asarray(mask)
        self.corpus = (jnp.asarray(self._corpus.data)
                       if self._corpus is not None else None)
        self.corpus_sq = (jnp.asarray(self._sq.data)
                          if self._sq is not None else None)
        self._dirty = False

    def query(self, q, k: int = 10, *, allowed=None, nprobe_boost: int = 1):
        self._sync()
        q = jnp.atleast_2d(jnp.asarray(q, jnp.float32))
        metric = self.metric
        if metric == "cosine":
            q = D.l2_normalize(q)
            metric = "dot"
        nprobe = min(self.nprobe * max(1, int(nprobe_boost)),
                     self.centroids.shape[0])
        batch_stats = {} if not self.scan_all else None
        out = ivf_pq_search(
            self.codebooks, self.codes, self.centroids, None, self.corpus, q,
            metric=metric, k=min(k, max(self.size, 1)), nprobe=nprobe,
            refine=self.refine, corpus_sq=self.corpus_sq, assign=self.assign,
            valid=self.valid, allowed=allowed,
            block_lists=(self.codes_bm, self.bucket_ids, self.block_table),
            steps_per_probe=self.spp, use_kernel=self.use_kernel,
            lut_dtype=self.lut_dtype, scan_all=self.scan_all,
            adaptive_nprobe=self.adaptive_nprobe, adc_mode=self.adc_mode,
            qblk=self.qblk, adc_stats=batch_stats,
            sched_cache=self.sched_cache,
            sched_key=self._sched_ctx + (nprobe,))
        if batch_stats:
            st = self.adc_stats
            st[batch_stats["mode"]] += 1
            st["probes"] += bool(batch_stats.get("probe"))
            if batch_stats.get("crossover") is not None:
                st["crossover"] = batch_stats["crossover"]
            st["sharing_sum"] += batch_stats["sharing"]
            st["eff_nprobe_sum"] += batch_stats["eff_nprobe"]
            st["steps"] += batch_stats["steps"]
            st["real_steps"] += batch_stats["pairs"]
            st["batches"] += 1
        return out

    # ------------------------------------------------------- persistence
    def _host_assign(self):
        """(N,) cluster assignment over the id space (dead ids read 0)."""
        if self._assign is not None:
            return np.asarray(self._assign.data[: self.n])
        return self.layout.assign_of(self.n)

    def _row_major_codes(self):
        """(N, m) uint8 codes reconstructed from the block layout —
        snapshots stay at the PR-1 format regardless of ``scan_all``."""
        if self._codes_rm is not None:
            return jnp.asarray(self._codes_rm.data[: self.n])
        return jnp.asarray(self.layout.gather_payload(self.n))

    def state_dict(self):
        live = self.layout.live_mask(self.n)
        live_ids = np.flatnonzero(live)
        buckets, _cap = build_buckets(self._host_assign()[live_ids],
                                      self.centroids.shape[0], ids=live_ids)
        state = {"engine": np.asarray("ivf_pq"),
                 "metric": np.asarray(self.metric),
                 "codebooks": self.codebooks, "codes": self._row_major_codes(),
                 "centroids": self.centroids,
                 "buckets": jnp.asarray(buckets),
                 "live": live,
                 "generation": np.asarray(self.generation, np.int64),
                 "d": jnp.asarray(self.d, jnp.int32)}
        if self._corpus is not None:
            state["corpus"] = jnp.asarray(self._corpus.data[: self.n])
        if self._sq is not None:
            state["corpus_sq"] = jnp.asarray(self._sq.data[: self.n])
        return state

    def load_state(self, state):
        _check_snapshot(state, "ivf_pq", self.metric)
        self.codebooks = jnp.asarray(state["codebooks"], jnp.float32)
        codes = np.asarray(state["codes"]).astype(np.uint8)
        n = int(codes.shape[0])
        self.centroids = jnp.asarray(state["centroids"], jnp.float32)
        self.d = int(state["d"])
        # assign is derivable from the bucket table (buckets[c] lists the
        # live rows of cluster c), so snapshots stay at the PR-1 format —
        # assign_from_buckets + _finalize_layout is the ONE reconstruction
        # path, shared with load(), so tail counts always rebuild the same
        live = _snapshot_live(state, n)
        self._corpus = (GrowableRows.from_array(
            np.asarray(state["corpus"], np.float32))
            if "corpus" in state else None)
        self._sq = (GrowableRows.from_array(
            np.asarray(state["corpus_sq"], np.float32))
            if "corpus_sq" in state else None)
        if self._corpus is None:
            self.refine = 0
        self._finalize_layout(codes, assign_from_buckets(state["buckets"], n),
                              live=live)
        self.generation = int(state.get("generation", 0))
        self.m = int(self.codebooks.shape[0])
        self.ksub = int(self.codebooks.shape[1])
        return self

    def memory_bytes(self, include_raw: bool = False) -> int:
        """Index-resident bytes: block-aligned codes + slot ids + block
        tables + codebooks + coarse structures (+ row-major codes and
        assignments under scan_all), at ALLOCATED capacity-bucket sizes."""
        total = (self.layout.memory_bytes()
                 + self.codebooks.size * 4 + self.centroids.size * 4)
        if self._codes_rm is not None:
            total += self._codes_rm.data.size
        if self._assign is not None:
            total += self._assign.data.size * 4
        if self._sq is not None:
            total += self._sq.data.size * 4
        if include_raw and self._corpus is not None:
            total += self._corpus.data.size * 4
        return int(total)
