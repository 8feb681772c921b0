"""Jit'd public wrappers around the Pallas kernels.

Handle padding/alignment (MXU wants lane multiples of 128), GQA head layout,
and backend selection: ``interpret=None`` auto-resolves to True off-TPU so
the same call sites run everywhere (interpret executes the kernel body in
Python on CPU; on TPU it lowers to Mosaic).

This module is also the backend-aware dispatcher for the ADC hot paths:
``adc_topk`` (flat scan over all codes) and ``ivf_adc_topk``
(bucket-resident scan over probed inverted-list blocks). On TPU the fused
Pallas kernels serve real queries; on CPU/GPU fused jnp twins
(``adc_topk_jnp`` / ``ivf_adc_topk_jnp``) run instead — interpret-mode
Pallas executes the kernel body block-by-block in Python and is a debugging
tool, not a serving path. Engines expose the choice as a ``use_kernel``
kwarg (None = auto by backend) and LUT precision as ``lut_dtype``
('float32' / 'bfloat16' / 'int8' with per-(query, subspace) scales).

``ivf_adc_topk`` additionally dispatches between three GRIDS (orthogonal
to the backend choice): the per-query (Q, T) grid; the blocked mode that
re-sorts the visit table by block id so each code block is fetched once
for a whole qblk-wide query group (``repro.core.ivf.build_block_schedule``);
and the block-RESIDENT run-length mode that walks the schedule's per-block
runs so each distinct block is fetched once for the WHOLE batch. The
``mode`` kwarg ('auto'/'blocked'/'per_query'/'run_resident') picks the
grid — 'auto' consults the measured online autotuner ledger
(``repro.kernels.autotune``) instead of hardcoded thresholds. All grids
exist for both backends and are bit-identical per backend.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import flash_attention as _fa
from repro.kernels import hamming as _hm
from repro.kernels import ivf_adc as _ivf
from repro.kernels import pq_adc as _pq
from repro.kernels import topk_distance as _tk
from repro.kernels.autotune import LEDGER
from repro.kernels.pq_adc import quantize_lut_int8
from repro.kernels.topk_distance import NEG_INF

ADC_LUT_DTYPES = ("float32", "bfloat16", "int8")
ADC_MODES = ("auto", "blocked", "per_query", "run_resident")

# UNTUNED fallback heuristic for the grouped ivf_adc grids, used only with
# ``autotune=False`` (and as the probe gate's board bound): the
# block-sharing schedule only pays when enough (query, step) pairs land on
# each block to amortize its fetch (sharing = pairs / distinct blocks).
# With autotuning on (the default) the dispatch thresholds come from the
# measured ledger in ``repro.kernels.autotune`` instead of these constants.
# The board bound caps the grouped twins' (Q+1, T, blk) scatter target
# (slots, i.e. ~8 bytes each) on every path.
BLOCKED_MIN_SHARING = 2.0
BLOCKED_MIN_QUERIES = 32
BLOCKED_MAX_BOARD_SLOTS = 1 << 25
DEFAULT_QBLK = 8  # f32 sublane tile — groups land MXU-aligned
# The kernel backend's grouped grids pre-gather one m*ksub LUT row per
# scheduled (query, block) slot into an HBM panel — 96 KiB a slot at m=96,
# ksub=256 in f32, so a low-sharing batch's panel runs to gigabytes — and
# prefetch the schedule into the core's 1 MiB of scalar memory. Auto
# dispatch keeps a batch off those grids unless both bounds hold.
GROUPED_MAX_PANEL_BYTES = 256 << 20
GROUPED_MAX_SMEM_BYTES = 512 << 10


def _kernel_grouped_fits(pairs: int, blocks: int, qblk: int,
                         row_bytes: int) -> bool:
    """Upper bounds, before the schedule is built, on the grouped kernels'
    panel bytes and scalar-prefetch bytes (``build_block_schedule`` pads
    groups and runs by at most a quarter)."""
    slots = (pairs + blocks * qblk) * 5 // 4   # >= G * qblk after the pad
    runs = (blocks + 1) * 5 // 4 + 8
    smem = 4 * (2 * slots + slots // qblk + 3 * runs)
    return (slots * row_bytes <= GROUPED_MAX_PANEL_BYTES
            and smem <= GROUPED_MAX_SMEM_BYTES)


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@jax.jit
def mask_allowed_ids(bucket_ids, allowed):
    """Retarget slots whose id fails the predicate bitmap at the -1 pad
    sentinel. bucket_ids: (..., ) int32 global ids (-1 = pad/tombstone);
    allowed: (n,) bool over the id space (ids >= n read as disallowed).

    This is invariant 6's implementation point for the bucket-resident
    paths: a filtered batch rewrites the DATA the kernels consume — the
    grids, schedules, and compiled executables never change, and slots a
    predicate rejects are indistinguishable from tombstones. With an
    all-true bitmap the output equals the input bit-for-bit.
    """
    n = allowed.shape[0]
    safe = jnp.clip(bucket_ids, 0, n - 1)
    ok = (bucket_ids >= 0) & (bucket_ids < n) & jnp.take(allowed, safe)
    return jnp.where(ok, bucket_ids, -1)


def resolve_adc_backend(use_kernel=None) -> str:
    """'kernel' (fused Pallas pq_adc) or 'jnp' (fused gather twin).

    None auto-selects by backend: the Pallas kernel on TPU, the jnp twin
    everywhere else. ``use_kernel=True`` forces the kernel (interpret mode
    off-TPU — parity testing, not speed); False forces the jnp twin.
    """
    if use_kernel is None:
        return "kernel" if jax.default_backend() == "tpu" else "jnp"
    return "kernel" if use_kernel else "jnp"


def _pad_axis(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    blk_q: int = 128, blk_k: int = 128, interpret=None):
    """q: (B, Sq, H, dh); k/v: (B, Sk, KV, dh) -> (B, Sq, H, dh).

    GQA: KV heads are repeated to H before the kernel; dh pads to 128 lanes.
    """
    interpret = _auto_interpret(interpret)
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = float(scale if scale is not None else 1.0 / np.sqrt(dh))
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, -1, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, -1, dh)
    qf, _ = _pad_axis(qf, 2, 128)
    kf, _ = _pad_axis(kf, 2, 128)
    vf, _ = _pad_axis(vf, 2, 128)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, scale=scale,
                            blk_q=blk_q, blk_k=blk_k, interpret=interpret)
    o = o[..., :dh].reshape(B, H, Sq, dh)
    return jnp.moveaxis(o, 1, 2)


def topk_distance(corpus, q, *, k: int, metric: str = "dot", corpus_sq=None,
                  valid=None, blk_n: int = 512, interpret=None):
    """Fused exact top-k. corpus: (N, d); q: (Q, d); metric in {dot, l2}.

    Pads N to the tile size; pad rows (and rows where ``valid`` is False) are
    knocked out inside the kernel via the additive score bias.
    """
    interpret = _auto_interpret(interpret)
    N, d = corpus.shape
    blk_n = min(blk_n, N)
    corpus, _ = _pad_axis(corpus, 0, blk_n)
    Np = corpus.shape[0]
    l2 = metric == "l2"
    if l2:
        if corpus_sq is None:
            corpus_sq = jnp.sum(jnp.square(corpus.astype(jnp.float32)), axis=-1)
        else:
            corpus_sq, _ = _pad_axis(corpus_sq.astype(jnp.float32), 0, blk_n)
        bias = -corpus_sq
    else:
        bias = jnp.zeros((Np,), jnp.float32)
    keep = jnp.arange(Np) < N
    if valid is not None:
        keep = keep & jnp.pad(valid, (0, Np - valid.shape[0]))
    bias = jnp.where(keep, bias, -1e30)
    return _tk.topk_distance(corpus, q, k=k, l2=l2, bias=bias, blk_n=blk_n,
                             interpret=interpret)


def pq_adc(codes, luts, *, k: int, valid=None, blk_n: int = 256,
           interpret=None, lut_dtype: str = "float32"):
    """Fused PQ ADC top-k. codes: (N, m); luts: (Q, m, ksub).

    Pads N to the tile size; pad rows (and rows where ``valid`` is False) are
    knocked out inside the kernel via the additive score bias. ``lut_dtype``
    selects the in-kernel table precision (f32 or bf16).
    """
    interpret = _auto_interpret(interpret)
    N = codes.shape[0]
    blk_n = min(blk_n, N)
    codes = codes.astype(jnp.int32)
    codes, _ = _pad_axis(codes, 0, blk_n)
    Np = codes.shape[0]
    keep = jnp.arange(Np) < N
    if valid is not None:
        keep = keep & jnp.pad(valid, (0, Np - valid.shape[0]))
    bias = jnp.where(keep, 0.0, -1e30)
    return _pq.pq_adc(codes, luts, k=k, bias=bias, blk_n=blk_n,
                      interpret=interpret, lut_dtype=lut_dtype)


@jax.jit
def _round_lut_bf16(luts):
    """bf16-round LUT values, f32 storage (bit-identical to
    astype(bf16).astype(f32)). Dispatched as its OWN executable from
    adc_topk so the rounded table materializes once — fused into the
    scoring program, XLA CPU re-rounds every gathered element instead
    (~8 converts per scored row, a measured ~15% tax)."""
    return jax.lax.reduce_precision(luts, exponent_bits=8, mantissa_bits=7)


def _twolevel_topk(scores, k: int, group: int = 16):
    """Exact top-k via group-max prefilter: any row holding a global top-k
    score also holds its group's max, and that max outranks every max of a
    group with no top-k member — so the true top-k lives inside the top-k
    groups-by-max. One vectorized max pass + a top-k over N/group + a top-k
    over k*group beats one top-k over N (the partial sort dominates).

    Ties across groups can swap equal-scored ids vs lax.top_k; scores are
    continuous f32 in every caller.
    """
    Q, N = scores.shape
    pad = (-N) % group
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    n_groups = scores.shape[1] // group
    gmax = scores.reshape(Q, n_groups, group).max(-1)
    kg = min(k, n_groups)
    _, gids = jax.lax.top_k(gmax, kg)
    members = (gids[:, :, None] * group
               + jnp.arange(group)[None, None, :]).reshape(Q, kg * group)
    cand = jnp.take_along_axis(scores, members, axis=1)
    s, pos = jax.lax.top_k(cand, k)
    return s, jnp.take_along_axis(members, pos, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "tile", "lut_dtype"))
def adc_topk_jnp(codes, luts, *, k: int, valid=None, tile: int = 32768,
                 lut_dtype: str = "float32"):
    """Fused jnp twin of the pq_adc kernel: m LUT gathers, f32 accumulate,
    one exact two-level top-k per (large) row tile, merged pairwise.

    Unlike the PR-1 ``pq_topk`` scan (lax.scan over 4k-row tiles), the whole
    gather+sum+select per tile is one fused XLA program over row tiles big
    enough that the selection epilogue is noise, and the selection itself is
    the group-max two-level scheme — together ~2x over the scan on CPU.
    ``lut_dtype="bfloat16"`` rounds the table to bf16 (the exact values the
    TPU kernel contracts, so the recall guard tests the real thing) but
    keeps f32 *storage* for the gathers off-TPU — XLA CPU gathers 32-bit
    lanes faster than 16-bit, so widening is free accuracy-wise.
    ``lut_dtype="int8"`` gathers absmax-quantized int8 entries and applies
    the per-(query, subspace) scale — value-identical to the kernel's int8
    per-subspace contraction (same quantizer, same f32 sum order). Tiles
    bound peak score memory at O(Q * tile), mirroring the kernel's VMEM
    streaming.
    """
    N, m = codes.shape
    Q = luts.shape[0]
    k = min(k, N)
    scales = None
    if lut_dtype == "bfloat16":
        luts = _round_lut_bf16(luts)
    elif lut_dtype == "int8":
        luts, scales = quantize_lut_int8(luts)

    def gather(j, idx_j):
        g = jnp.take(luts[:, j, :], idx_j, axis=1)
        if scales is None:
            return g
        return g.astype(jnp.float32) * scales[:, j][:, None]

    idx = codes.astype(jnp.int32).T  # (m, N): per-subspace rows contiguous
    best = None
    for start in range(0, N, tile):  # static unroll: N // tile + 1 fused blocks
        stop = min(start + tile, N)
        total = gather(0, idx[0, start:stop])
        for j in range(1, m):
            total = total + gather(j, idx[j, start:stop])
        if valid is not None:
            total = jnp.where(valid[start:stop][None, :], total, -jnp.inf)
        s, i = _twolevel_topk(total, min(k, stop - start))
        i = (i + start).astype(jnp.int32)
        if best is None:
            best = (s, i)
        else:
            cs = jnp.concatenate([best[0], s], axis=-1)
            ci = jnp.concatenate([best[1], i], axis=-1)
            s, pos = jax.lax.top_k(cs, k)
            best = (s, jnp.take_along_axis(ci, pos, axis=-1))
    s, i = best
    if s.shape[-1] < k:
        s = jnp.pad(s, ((0, 0), (0, k - s.shape[-1])), constant_values=-jnp.inf)
        i = jnp.pad(i, ((0, 0), (0, k - i.shape[-1])), constant_values=-1)
    return s, i


def adc_topk(codes, luts, *, k: int, valid=None, allowed=None,
             use_kernel=None, lut_dtype: str = "float32", blk_n: int = 256,
             tile: int = 32768, interpret=None):
    """Backend-aware PQ ADC top-k dispatch — THE compressed hot-path entry.

    codes: (N, m) uint8/int32; luts: (Q, m, ksub) f32. TPU (or
    ``use_kernel=True``) routes to the fused Pallas kernel, everything else
    to the fused jnp twin; both honor ``lut_dtype``
    ('float32'/'bfloat16'/'int8') and a row ``valid`` mask, and return
    (scores (Q, k) f32, ids (Q, k) int32) with identical semantics.

    ``allowed`` is the predicate engine's bitmap over the id space
    (invariant 6): it simply ANDs into ``valid`` — rows a filter rejects
    are knocked out exactly like tombstones, by the same score bias, in
    the same executables. None (the unfiltered hot path) changes nothing.

    When called with concrete (non-traced) arrays, the bf16 rounding runs
    as its own executable before the scan — see _round_lut_bf16; inside an
    enclosing jit the rounding inlines into the scan instead (same values,
    slower on CPU). int8 quantization stays in-graph on both backends (its
    output changes dtype, so there is no free f32-lane widening to exploit).
    """
    assert lut_dtype in ADC_LUT_DTYPES, lut_dtype
    if allowed is not None:
        N = codes.shape[0]
        a = jnp.asarray(allowed)
        if a.shape[0] < N:  # id space can trail the capacity bucket
            a = jnp.pad(a, (0, N - a.shape[0]))
        a = a[:N]
        valid = a if valid is None else valid & a
    if resolve_adc_backend(use_kernel) == "kernel":
        s, i = pq_adc(codes, luts, k=k, valid=valid, blk_n=blk_n,
                      interpret=interpret, lut_dtype=lut_dtype)
        # the kernel knocks rows out with a finite -1e30 score bias; map
        # anything at or below half of it to (-inf, -1) so both backends
        # expose the same sentinel (isneginf-keyed callers — e.g. the
        # tombstone normalization in the mutable engines — see the knockout
        # on every backend). Mirrors ivf_adc_topk's normalization.
        bad = s <= 0.5 * NEG_INF
        return jnp.where(bad, -jnp.inf, s), jnp.where(bad, -1, i)
    if lut_dtype == "bfloat16" and not isinstance(luts, jax.core.Tracer):
        luts = _round_lut_bf16(luts)  # materialize at the jit boundary
        lut_dtype = "float32"
    return adc_topk_jnp(codes, luts, k=k, valid=valid, tile=tile,
                        lut_dtype=lut_dtype)


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "lut_dtype",
                                    "probe_chunk"))
def ivf_adc_topk_jnp(bucket_codes, bucket_ids, visit, luts, coarse, *,
                     k: int, steps_per_probe: int = 1,
                     lut_dtype: str = "float32", probe_chunk=None):
    """Fused jnp twin of the ivf_adc kernel: a static-unrolled loop over
    CHUNKS of probes, each iteration one fused gather+sum+select over that
    chunk's block runs, folded into a running (Q, k) scoreboard.

    The chunk size bounds peak memory at O(Q * probe_chunk *
    steps_per_probe * blk) candidate slots (auto-sized to the same ~32k
    slot budget as adc_topk_jnp's row tiles) — the full candidate set of a
    large-nprobe query never materializes at once, and the block-aligned
    slots carry <= blk-1 pad slack per cluster instead of the bucket-table
    slack the old (Q, nprobe, cap, m) gather path paid. One fused XLA
    program per chunk keeps the CPU path at big-gather speed instead of
    per-probe op overhead.

    bucket_codes: (B, blk, m); bucket_ids: (B, blk) int32 (-1 pad); visit:
    (Q, T) int32 block ids, T = nprobe * steps_per_probe (see
    kernels/ivf_adc for the layout); luts: (Q, m, ksub) (shared) or
    (Q, nprobe, m, ksub) (per-probe); coarse: (Q, nprobe) f32 (centroid
    term + probe knockout). Same NEG_INF sentinel semantics as the kernel
    (dispatcher normalizes).
    """
    B, blk, m = bucket_codes.shape
    Q, T = visit.shape
    spp = steps_per_probe
    nprobe = T // spp
    run = spp * blk  # candidate slots per probe
    per_probe = luts.ndim == 4
    scales = None
    if lut_dtype == "bfloat16":
        luts = _round_lut_bf16(luts)
    elif lut_dtype == "int8":
        luts, scales = quantize_lut_int8(luts)
    if probe_chunk is None:
        probe_chunk = max(1, min(nprobe, 32768 // run))
    codes_i = bucket_codes.astype(jnp.int32)
    best_s = jnp.full((Q, k), NEG_INF, jnp.float32)
    best_i = jnp.full((Q, k), -1, jnp.int32)
    for start in range(0, nprobe, probe_chunk):  # static unroll
        stop = min(start + probe_chunk, nprobe)
        pc = stop - start
        v = visit[:, start * spp:stop * spp]  # (Q, pc*spp)
        cp = jnp.take(codes_i, v, axis=0).reshape(Q, pc, run, m)
        ip = jnp.take(bucket_ids, v, axis=0).reshape(Q, pc, run)
        s = None
        for j in range(m):
            if per_probe:
                g = jnp.take_along_axis(luts[:, start:stop, j, :],
                                        cp[..., j], axis=2)  # (Q, pc, run)
                if scales is not None:
                    g = (g.astype(jnp.float32)
                         * scales[:, start:stop, j][:, :, None])
            else:
                g = jnp.take_along_axis(
                    luts[:, j, :], cp[..., j].reshape(Q, pc * run),
                    axis=1).reshape(Q, pc, run)
                if scales is not None:
                    g = g.astype(jnp.float32) * scales[:, j][:, None, None]
            s = g if s is None else s + g
        s = s.astype(jnp.float32) + coarse[:, start:stop][:, :, None]
        s = jnp.where(ip >= 0, s, NEG_INF).reshape(Q, pc * run)
        ip = ip.reshape(Q, pc * run)
        ts, pos = jax.lax.top_k(s, min(k, pc * run))
        ti = jnp.take_along_axis(ip, pos, axis=-1)
        cs = jnp.concatenate([best_s, ts], axis=1)
        ci = jnp.concatenate([best_i, ti], axis=1)
        best_s, pos = jax.lax.top_k(cs, k)
        best_i = jnp.take_along_axis(ci, pos, axis=-1)
    return best_s, best_i


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "lut_dtype"))
def ivf_adc_blocked_jnp(bucket_codes, bucket_ids, sched_block, sched_q,
                        sched_t, luts, coarse, *, k: int,
                        steps_per_probe: int = 1,
                        lut_dtype: str = "float32"):
    """Fused jnp twin of the BLOCKED ivf_adc mode, over a segmented
    schedule from ``repro.core.ivf.build_block_schedule``.

    Where ``ivf_adc_topk_jnp`` gathers codes per (query, step) pair — Q*T
    block fetches — this path fetches each scheduled block once (G rows),
    scores it against its qblk-wide query group with the same per-subspace
    flat LUT gathers in the same j order (bit-identical sums), scatters
    the (G, qblk, blk) scores back into a (Q+1, T, blk) board keyed by the
    schedule's (query, step) coordinates (row Q is the sentinel trash
    row), and runs ONE top-k per query over the board. Pairs the schedule
    dropped (pad blocks) simply stay at the board's NEG_INF init — the
    same knockout the per-query grid applies slot by slot.

    sched_block: (G,) int32; sched_q/sched_t: (G, qblk) int32, -1 in
    sched_q = knockout sentinel. Other args/results as ``ivf_adc_topk_jnp``.
    """
    B, blk, m = bucket_codes.shape
    G, qblk = sched_q.shape
    Q, nprobe = coarse.shape
    T = nprobe * steps_per_probe
    per_probe = luts.ndim == 4
    ksub = luts.shape[-1]
    scales = None
    if lut_dtype == "bfloat16":
        luts = _round_lut_bf16(luts)
    elif lut_dtype == "int8":
        luts, scales = quantize_lut_int8(luts)
    codes_g = jnp.take(bucket_codes.astype(jnp.int32), sched_block, axis=0)
    ids_g = jnp.take(bucket_ids, sched_block, axis=0)        # (G, blk)
    qs = jnp.clip(sched_q, 0)                                # sentinel -> 0
    p_of = sched_t // steps_per_probe
    n_rows = Q * nprobe if per_probe else Q
    row = qs * nprobe + p_of if per_probe else qs            # LUT row per pair
    luts_flat = luts.reshape(n_rows, m, ksub)
    s = None
    for j in range(m):
        g = jnp.take(luts_flat[:, j, :].reshape(-1),
                     row[:, :, None] * ksub + codes_g[:, None, :, j])
        if scales is not None:
            sc = jnp.take(scales.reshape(n_rows, m)[:, j], row)
            g = g.astype(jnp.float32) * sc[:, :, None]
        s = g if s is None else s + g                        # (G, qblk, blk)
    cpair = jnp.take(coarse.astype(jnp.float32).reshape(-1),
                     qs * nprobe + p_of)                     # (G, qblk)
    cpair = jnp.where(sched_q >= 0, cpair, NEG_INF)          # sentinel knockout
    s = s.astype(jnp.float32) + cpair[:, :, None]
    s = jnp.where(ids_g[:, None, :] >= 0, s, NEG_INF)
    qrow = jnp.where(sched_q >= 0, sched_q, Q)
    board_s = jnp.full((Q + 1, T, blk), NEG_INF, jnp.float32)
    board_i = jnp.full((Q + 1, T, blk), -1, jnp.int32)
    board_s = board_s.at[qrow, sched_t].set(s)
    board_i = board_i.at[qrow, sched_t].set(
        jnp.broadcast_to(ids_g[:, None, :], s.shape))
    kk = min(k, T * blk)
    bs, pos = jax.lax.top_k(board_s[:Q].reshape(Q, T * blk), kk)
    bi = jnp.take_along_axis(board_i[:Q].reshape(Q, T * blk), pos, axis=1)
    if kk < k:
        bs = jnp.pad(bs, ((0, 0), (0, k - kk)), constant_values=NEG_INF)
        bi = jnp.pad(bi, ((0, 0), (0, k - kk)), constant_values=-1)
    return bs, bi


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "lut_dtype"))
def ivf_adc_run_resident_jnp(bucket_codes, bucket_ids, run_block, grun,
                             sched_q, sched_t, visit, luts, coarse, *, k: int,
                             steps_per_probe: int = 1,
                             lut_dtype: str = "float32"):
    """Fused jnp twin of the BLOCK-RESIDENT run-length ivf_adc mode.

    The blocked twin fetches each scheduled block once per GROUP — a block
    shared by s queries at qblk=8 is still gathered ceil(s/8) times from
    the full (B, blk, m) table. This path consumes the run-length view
    (``stats["runs"]``/``stats["grun"]`` from ``build_block_schedule``):
    the distinct blocks are gathered ONCE into a compact (R, blk, m) hot
    panel and every group reads its codes back through the (G,) ``grun``
    map — per-batch code traffic from the big table drops from G to R
    rows. The scatter board also sheds its id half: ids are recovered
    AFTER the top-k from ``bucket_ids[visit[q, t], slot]`` (identical by
    construction to what the blocked twin scatters), so the (Q+1, T, blk)
    int32 board scatter disappears entirely.

    Scoring is the same per-subspace flat LUT gathers in the same j order
    as both other twins — bit-identical sums. run_block: (R,) int32;
    grun: (G,) int32 group -> run; sched_q/sched_t: (G, qblk) int32;
    visit: (Q, T) int32 (id recovery). Other args/results as
    ``ivf_adc_blocked_jnp``.
    """
    B, blk, m = bucket_codes.shape
    G, qblk = sched_q.shape
    Q, nprobe = coarse.shape
    T = nprobe * steps_per_probe
    per_probe = luts.ndim == 4
    ksub = luts.shape[-1]
    scales = None
    if lut_dtype == "bfloat16":
        luts = _round_lut_bf16(luts)
    elif lut_dtype == "int8":
        luts, scales = quantize_lut_int8(luts)
    # block-resident gather: each distinct block leaves the big table once
    codes_r = jnp.take(bucket_codes.astype(jnp.int32), run_block, axis=0)
    valid_r = jnp.take(bucket_ids, run_block, axis=0) >= 0   # (R, blk)
    codes_g = jnp.take(codes_r, grun, axis=0)                # (G, blk, m)
    valid_g = jnp.take(valid_r, grun, axis=0)                # (G, blk)
    qs = jnp.clip(sched_q, 0)
    p_of = sched_t // steps_per_probe
    n_rows = Q * nprobe if per_probe else Q
    row = qs * nprobe + p_of if per_probe else qs
    luts_flat = luts.reshape(n_rows, m, ksub)
    s = None
    for j in range(m):
        g = jnp.take(luts_flat[:, j, :].reshape(-1),
                     row[:, :, None] * ksub + codes_g[:, None, :, j])
        if scales is not None:
            sc = jnp.take(scales.reshape(n_rows, m)[:, j], row)
            g = g.astype(jnp.float32) * sc[:, :, None]
        s = g if s is None else s + g                        # (G, qblk, blk)
    cpair = jnp.take(coarse.astype(jnp.float32).reshape(-1),
                     qs * nprobe + p_of)                     # (G, qblk)
    cpair = jnp.where(sched_q >= 0, cpair, NEG_INF)          # sentinel knockout
    s = s.astype(jnp.float32) + cpair[:, :, None]
    s = jnp.where(valid_g[:, None, :], s, NEG_INF)
    qrow = jnp.where(sched_q >= 0, sched_q, Q)
    board_s = jnp.full((Q + 1, T, blk), NEG_INF, jnp.float32)
    board_s = board_s.at[qrow, sched_t].set(s)
    kk = min(k, T * blk)
    bs, pos = jax.lax.top_k(board_s[:Q].reshape(Q, T * blk), kk)
    # id recovery: board position (q, t, slot) holds bucket_ids[visit[q, t],
    # slot] whenever it was scored; unscored positions are NEG_INF and
    # normalize to -1 below — exactly the blocked twin's board_i contents
    t_of = pos // blk
    slot_of = pos % blk
    blk_of = jnp.take_along_axis(visit.astype(jnp.int32), t_of, axis=1)
    bi = bucket_ids[blk_of, slot_of]
    bi = jnp.where(bs <= 0.5 * NEG_INF, -1, bi)
    if kk < k:
        bs = jnp.pad(bs, ((0, 0), (0, k - kk)), constant_values=NEG_INF)
        bi = jnp.pad(bi, ((0, 0), (0, k - kk)), constant_values=-1)
    return bs, bi


def _build_schedule_cached(visit_np, qblk, pad_block, cache, base_key, Q, T):
    """Build (or fetch from the plan ledger's ScheduleCache) the
    DEVICE-resident segmented schedule for one (visit table, qblk). A hit
    skips the host sort AND the host->device upload; the cache verifies
    the raw visit bytes so a stale entry can never alias (see
    ``repro.core.ivf.ScheduleCache``)."""
    key = (base_key, qblk,
           None if pad_block is None else int(pad_block), Q, T)
    vbytes = visit_np.tobytes() if cache is not None else None
    if cache is not None:
        hit = cache.get(key, vbytes)
        if hit is not None:
            return hit
    from repro.core.ivf import build_block_schedule  # lazy: layering
    sb, sq, st, s2 = build_block_schedule(visit_np, qblk=qblk,
                                          pad_block=pad_block)
    rb, rs, rl = s2["runs"]
    built = {"sb": jnp.asarray(sb), "sq": jnp.asarray(sq),
             "st": jnp.asarray(st), "rb": jnp.asarray(rb),
             "rs": jnp.asarray(rs), "rl": jnp.asarray(rl),
             "grun": jnp.asarray(s2["grun"]), "groups": s2["groups"],
             "n_runs": s2["n_runs"]}
    if cache is not None:
        cache.put(key, vbytes, built)
    return built


def ivf_adc_topk(bucket_codes, bucket_ids, visit, luts, *, k: int,
                 coarse=None, steps_per_probe: int = 1, use_kernel=None,
                 lut_dtype: str = "float32", interpret=None,
                 mode: str = "auto", qblk=None,
                 pad_block=None, stats=None, autotune=None,
                 sched_cache=None, sched_key=(), allowed=None):
    """Backend-aware bucket-resident IVF-ADC top-k — the IVF-PQ hot-path
    entry. Work scales with the probed candidate count, not N.

    bucket_codes: (B, blk, m) uint8/int32 codes in the BLOCK-ALIGNED
    bucket-major layout (row b of ``bucket_ids`` names the global row each
    slot holds, -1 = pad; see repro.core.ivf.build_block_lists); visit:
    (Q, T) int32 block ids with T = nprobe * steps_per_probe, step t
    serving probe t // steps_per_probe (tail steps of short clusters point
    at an all-pad block); luts: (Q, m, ksub) f32 shared tables (dot — pass
    the centroid term via ``coarse``) or (Q, nprobe, m, ksub) per-probe
    residual tables (l2); ``coarse``: optional (Q, nprobe) f32 additive
    per-probe term — callers also use it as a probe knockout by passing
    NEG_INF entries (sharded serving masks off-shard probes this way).

    TPU (or ``use_kernel=True``) runs the Pallas ivf_adc kernels
    (scalar-prefetch block gather), else the fused jnp twins. Both honor
    ``lut_dtype`` ('float32'/'bfloat16'/'int8'). Unfilled/knocked-out
    slots are normalized to (-inf, -1) — anything at or below NEG_INF/2 is
    treated as knocked out (real ADC scores live many orders of magnitude
    above). Returns (scores (Q, k) f32, ids (Q, k) int32) with global row
    ids.

    ``mode`` selects the grid: 'per_query' is the (Q, T) grid above;
    'blocked' re-sorts the (concrete) visit table into a segmented
    block-sharing schedule (``repro.core.ivf.build_block_schedule`` with
    group width ``qblk``; ``pad_block`` names the all-pad block so its
    pairs are dropped) and runs the group-per-program grid — each code
    block is fetched once per qblk queries; 'run_resident' walks the same
    schedule's per-block RUNS so each distinct block is fetched once for
    the whole batch. All grids are bit-identical per backend on the same
    visit table (forced grouped modes raise under jit — the schedule is
    host-built). The per-query kernel skips the steps on ``pad_block``.

    'auto' resolves the grid from the MEASURED online autotuner
    (``repro.kernels.autotune``): the first batches of each
    (backend, m, ksub, blk, lut_dtype) key each time one candidate grid
    (serving its bit-identical result), after which dispatch is a ledger
    lookup — grouped iff the batch's cheap sharing probe (one np.unique,
    no schedule build) clears the fitted crossover. ``autotune=False``
    falls back to the PR-8 constant thresholds (BLOCKED_MIN_SHARING etc.);
    passing an ``AutoTuner`` instance overrides the process ledger (tests).
    Inside jit the visit table is traced, so 'auto' silently serves
    per-query.

    ``sched_cache``/``sched_key``: optional ``repro.core.ivf.ScheduleCache``
    + caller context key (the plan ledger passes (bucket, generation,
    nprobe)) so steady-state serving stops re-sorting identical visit
    tables. If ``stats`` is a dict, the dispatch decision is written into
    it ('mode', 'sharing', 'pairs', 'blocks', 'groups', 'qblk', 'probe',
    'crossover', and 'steps' = Q * T where the visit table came to the
    host, else 0; 'pairs' of them visit a real block).

    Host spans (``repro.obs``): ``ivf.visit_sync`` (the visit table's
    round trip), ``ivf.sharing`` (the sharing probe and grid decision),
    ``ivf.schedule`` (grouped grids) and ``ivf.adc`` (the grid launch,
    the autotuner's timed calls included).

    ``allowed`` (optional (n,) bool bitmap over the id space — the
    predicate engine's output) rewrites ``bucket_ids`` through
    ``mask_allowed_ids`` before any grid runs: filtered-out slots become
    the -1 pad sentinel every mode already knocks out, so the SAME
    compiled executables serve filtered and unfiltered batches on every
    adc_mode and backend (invariant 6). The visit table, schedule, and
    schedule cache are untouched — a filter is a data change, not a
    shape or program change.
    """
    assert lut_dtype in ADC_LUT_DTYPES, lut_dtype
    assert mode in ADC_MODES, mode
    if allowed is not None:
        bucket_ids = mask_allowed_ids(bucket_ids.astype(jnp.int32),
                                      jnp.asarray(allowed))
    Q, T = visit.shape
    nprobe = T // steps_per_probe
    if coarse is None:
        coarse = jnp.zeros((Q, nprobe), jnp.float32)
    traced = isinstance(visit, jax.core.Tracer)
    if mode in ("blocked", "run_resident") and traced:
        raise ValueError(
            f"mode={mode!r} needs a concrete visit table (the segmented "
            "schedule is built on the host); under jit use mode='auto' "
            "(falls back to the per-query grid) or hoist the dispatch out "
            "of the traced region.")
    backend = resolve_adc_backend(use_kernel)
    blk = bucket_codes.shape[1]
    m = bucket_codes.shape[2]
    sstats = {"mode": "per_query", "sharing": 0.0, "pairs": 0, "blocks": 0,
              "groups": 0, "qblk": 0, "probe": False, "crossover": None,
              "steps": 0}
    grid = "per_query"
    eff_qblk = DEFAULT_QBLK if qblk is None else qblk
    probe_cfg = tuner = tkey = visit_np = None
    if not traced and mode != "per_query":
        from repro.core.ivf import visit_sharing  # lazy: layering
        with obs.span("ivf.visit_sync"):
            visit_np = np.asarray(visit)
        with obs.span("ivf.sharing") as sharing_span:
            # cheap dispatch input: one np.unique, no sort-and-segment — the
            # full schedule is only built when a grouped grid will consume it
            sstats.update(visit_sharing(visit_np, pad_block=pad_block),
                          steps=Q * T)
            board_ok = (Q + 1) * T * blk <= BLOCKED_MAX_BOARD_SLOTS
            row_bytes = m * luts.shape[-1] * (
                4 if lut_dtype == "float32" else 2 if lut_dtype == "bfloat16"
                else 5)  # int8 entries + their share of the f32 scales

            def fits(qb):
                # the scatter board bounds the twins, panel + SMEM the kernels
                if backend == "jnp":
                    return board_ok
                return _kernel_grouped_fits(sstats["pairs"], sstats["blocks"],
                                            qb, row_bytes)
            if mode != "auto":
                grid = mode
            elif autotune is False:
                # PR-8 constant heuristic, kept as the untuned escape hatch
                if (Q >= BLOCKED_MIN_QUERIES and fits(eff_qblk)
                        and sstats["sharing"] >= BLOCKED_MIN_SHARING):
                    grid = "blocked"
            else:
                tuner = LEDGER if autotune is None else autotune
                tkey = (backend, m, luts.shape[-1], blk, lut_dtype)
                entry = tuner.lookup(tkey)
                if entry is not None:
                    sstats["crossover"] = entry["crossover"]
                    e_qblk = entry["qblk"] if qblk is None else qblk
                    if (sstats["pairs"] > 0 and fits(e_qblk)
                            and sstats["sharing"] >= entry["crossover"]):
                        grid = entry["grouped_mode"]
                        eff_qblk = e_qblk
                elif sstats["pairs"] > 0:
                    probe_cfg = tuner.next_probe(tkey)
                    p_qblk = (probe_cfg[1] or eff_qblk) if probe_cfg else 0
                    if probe_cfg is not None and fits(p_qblk):
                        grid = probe_cfg[0]
                        eff_qblk = p_qblk
                        sstats["probe"] = True
                    else:
                        probe_cfg = None  # probes wait for a batch that fits
            sharing_span.attrs["sharing"] = sstats["sharing"]
    built = None
    if grid != "per_query":
        with obs.span("ivf.schedule") as sp:
            built = _build_schedule_cached(visit_np, eff_qblk, pad_block,
                                           sched_cache, sched_key, Q, T)
            sp.attrs["groups"] = built["groups"]
        sstats["groups"] = built["groups"]
        sstats["qblk"] = eff_qblk
    sstats["mode"] = grid
    if stats is not None:
        stats.update(sstats)
    bids = bucket_ids.astype(jnp.int32)

    def _jnp_luts():
        if lut_dtype == "bfloat16" and not isinstance(luts, jax.core.Tracer):
            # materialize the rounded table at the jit boundary (see
            # _round_lut_bf16)
            return _round_lut_bf16(luts), "float32"
        return luts, lut_dtype

    def _run(g):
        if g == "per_query":
            if backend == "kernel":
                return _ivf.ivf_adc(
                    bucket_codes, bids, visit.astype(jnp.int32), luts,
                    coarse, k=k, steps_per_probe=steps_per_probe,
                    interpret=_auto_interpret(interpret),
                    lut_dtype=lut_dtype,
                    pad_block=None if pad_block is None else int(pad_block))
            lj, ld = _jnp_luts()
            return ivf_adc_topk_jnp(
                bucket_codes, bids, visit.astype(jnp.int32), lj, coarse,
                k=k, steps_per_probe=steps_per_probe, lut_dtype=ld)
        if g == "blocked":
            if backend == "kernel":
                return _ivf.ivf_adc_blocked(
                    bucket_codes, bids, built["sb"], built["sq"],
                    built["st"], luts, coarse, k=k,
                    steps_per_probe=steps_per_probe,
                    interpret=_auto_interpret(interpret),
                    lut_dtype=lut_dtype)
            lj, ld = _jnp_luts()
            return ivf_adc_blocked_jnp(
                bucket_codes, bids, built["sb"], built["sq"], built["st"],
                lj, coarse, k=k, steps_per_probe=steps_per_probe,
                lut_dtype=ld)
        if backend == "kernel":
            return _ivf.ivf_adc_run_resident(
                bucket_codes, bids, built["rb"], built["rs"], built["rl"],
                built["sq"], built["st"], luts, coarse, k=k,
                steps_per_probe=steps_per_probe,
                interpret=_auto_interpret(interpret), lut_dtype=lut_dtype)
        lj, ld = _jnp_luts()
        return ivf_adc_run_resident_jnp(
            bucket_codes, bids, built["rb"], built["grun"], built["sq"],
            built["st"], visit.astype(jnp.int32), lj, coarse, k=k,
            steps_per_probe=steps_per_probe, lut_dtype=ld)

    with obs.span("ivf.adc", grid=grid, steps=sstats["steps"],
                  real_steps=sstats["pairs"], probe=sstats["probe"]):
        if probe_cfg is not None:
            # measured probe: a warm-up call absorbs compiles/gathers, then
            # one timed call (the schedule is prebuilt — the host sort is
            # identical across grouped candidates, so it cancels out of the
            # comparison)
            jax.block_until_ready(_run(grid))
            t0 = time.perf_counter()
            s, i = _run(grid)
            jax.block_until_ready((s, i))
            tuner.record(tkey, probe_cfg, sstats["sharing"],
                         time.perf_counter() - t0)
            entry = tuner.lookup(tkey)
            if entry is not None and stats is not None:
                stats["crossover"] = entry["crossover"]
        else:
            s, i = _run(grid)
    bad = s <= 0.5 * NEG_INF
    return jnp.where(bad, -jnp.inf, s), jnp.where(bad, -1, i)


def hamming(q_codes, c_codes, *, blk_n: int = 1024, interpret=None):
    """q: (T, Q, W); c: (T, N, W) uint32 -> (Q, N) int32 min-over-tables."""
    interpret = _auto_interpret(interpret)
    T, Q, W = q_codes.shape
    N = c_codes.shape[1]
    blk_n = min(blk_n, N)
    c_codes, _ = _pad_axis(c_codes, 1, blk_n)
    out = _hm.hamming(q_codes, c_codes, blk_n=blk_n, interpret=interpret)
    return out[:, :N]
