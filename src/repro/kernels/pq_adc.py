"""Fused PQ asymmetric-distance + top-k Pallas kernel — the compressed-
corpus twin of topk_distance.py.

ADC's hot loop is a table gather: score[q, n] = sum_j lut[q, j, codes[n, j]].
Mosaic has no vector gather, but each subspace's gather IS a matmul against
a one-hot expansion of that subspace's codes: with sel_j[n, c] =
(codes[n, j] == c), lut[:, j, :] x sel_j^T is one (Q, ksub) x (ksub, blk_n)
MXU contraction whose every output has exactly ONE nonzero term — so it is
exact, and the m partials fold in subspace order j = 0..m-1, the same f32
left fold the jnp twins and the oracle in ``kernels/ref.py`` perform. Scores
are therefore bit-identical across backends and grids (``adc_block_scores``
below is the one scoring body every ADC kernel shares). The f32 path asks
the MXU for full f32 precision explicitly; a one-pass bf16 contraction
would round every table entry. Only one (blk_n, ksub) selector is live at a
time, so VMEM stays flat in m: a single (blk_n, m*ksub) one-hot at m=96,
ksub=256 would be 25 MB at blk_n=256, past the 16 MiB scoped limit.

Corpus code tiles (blk_n, m) stream through VMEM against one resident
(bq, m*ksub) block of query LUT rows; the running (bq, k) best-score/best-id
scoreboard lives in VMEM scratch exactly like topk_distance.py (same
unrolled knockout top-k). The grid is (Q / bq, N / blk_n): query blocks keep
the resident table small (a 64-query f32 table at m*ksub = 24576 is 6 MiB,
double-buffered 12). HBM traffic is codes-read per query block + (Q, k) out
— the f32 corpus is never touched, which is the entire point of PQ.

Mixed precision (``lut_dtype="bfloat16"``): the resident LUT is stored and
contracted in bf16 against a bf16 one-hot — each partial is still one exact
product, accumulated in f32, so the only precision loss is the one bf16
rounding of each table entry: |score - score_f32| <= m * 2^-8 * max|lut|
(each of the m gathered partials carries at most half-ulp bf16 error, 2^-9
relative). Tests pin this bound against the f32 oracle.

``lut_dtype="int8"`` drops the resident table another 2x below bf16: each
(query, subspace) LUT row is absmax-quantized (``quantize_lut_int8``) and
each subspace contracts int8 x int8 -> int32 on the MXU — EXACT integer
partials, since the one-hot just selects one int8 entry — which are then
scaled by the f32 per-(query, subspace) scale and summed:
    score = sum_j scale[q, j] * lut_i8[q, j, codes[n, j]].
Quantization error is <= scale/2 = max|lut_j| / 254 per subspace (sum:
m * max|lut| / 254).

``bias`` (1, N) folds pad-row knockout into the score add (built by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_distance import NEG_INF, _select_topk

# query rows per resident LUT block: one sublane tile
QUERY_BLOCK = 8


def quantize_lut_int8(luts):
    """Per-(query, subspace) absmax int8 quantization of ADC tables.

    luts: (..., m, ksub) f32 -> (lut_i8 (..., m, ksub) int8, scales (..., m)
    f32) with lut_i8 = round(lut / scale) in [-127, 127] and
    scale = max|lut_row| / 127. Shared by the flat pq_adc and the
    bucket-resident ivf_adc kernels and their jnp twins, so every backend
    quantizes bit-identically.
    """
    absmax = jnp.max(jnp.abs(luts), axis=-1)  # (..., m)
    scales = (jnp.maximum(absmax, 1e-30) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(luts / scales[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scales


def adc_block_scores(lut, codes, ksub: int, scale=None):
    """One block's ADC scores, inside a kernel body.

    lut: (R, m*ksub) f32 / bf16 / int8 table rows;
    codes: (rows, m) int32; scale: (R, m) f32 for int8 tables
    -> (R, rows) f32 with score[r, n] = sum_j lut[r, j*ksub + codes[n, j]]
    (times scale[r, j] per term for int8), summed in subspace order.
    """
    rows, m = codes.shape
    int8 = scale is not None
    acc = jnp.int32 if int8 else jnp.float32
    precision = (jax.lax.Precision.HIGHEST if lut.dtype == jnp.float32
                 else None)
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, ksub), 1)
    s = None
    for j in range(m):  # static unroll: one exact one-hot contraction each
        sel = (codes[:, j:j + 1] == sub).astype(lut.dtype)
        pj = jax.lax.dot_general(
            lut[:, j * ksub:(j + 1) * ksub], sel, (((1,), (1,)), ((), ())),
            preferred_element_type=acc, precision=precision)
        if int8:
            pj = pj.astype(jnp.float32) * scale[:, j:j + 1]
        s = pj if s is None else s + pj
    return s


def _pq_adc_kernel(c_ref, l_ref, bias_ref, *refs,
                   blk_n: int, n_blocks: int, k: int, ksub: int, int8: bool):
    if int8:
        sc_ref, s_out, i_out, bs_ref, bi_ref = refs
    else:
        sc_ref = None
        s_out, i_out, bs_ref, bi_ref = refs
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    s = adc_block_scores(l_ref[...], c_ref[...], ksub,
                         None if sc_ref is None else sc_ref[...])  # (bq, blk_n)
    s = s + bias_ref[...]
    ids = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    comb_s = jnp.concatenate([bs_ref[...], s], axis=1)
    comb_i = jnp.concatenate([bi_ref[...], ids], axis=1)
    bs_ref[...], bi_ref[...] = _select_topk(comb_s, comb_i, k)

    @pl.when(ni == n_blocks - 1)
    def _finalize():
        s_out[...] = bs_ref[...]
        i_out[...] = bi_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "blk_n", "interpret", "lut_dtype"))
def pq_adc(codes, luts, *, k: int, bias=None, blk_n: int = 256,
           interpret: bool = False, lut_dtype: str = "float32"):
    """codes: (N, m) int32; luts: (Q, m, ksub) f32
    -> (scores (Q, k) f32, ids (Q, k) int32).

    score[q, n] = sum_j luts[q, j, codes[n, j]] + bias[n]. N must divide by
    blk_n, and blk_n must be N or a multiple of 128 (the bias block's lane
    width); ``bias`` carries the pad/invalid-row knockout (ops.py builds
    it). ``lut_dtype="bfloat16"`` contracts the table in bf16 (f32
    accumulate, 2x MXU rate); ``"int8"`` stores it as absmax-quantized int8
    with per-(query, subspace) f32 scales (parity bounds in the module
    docstring).
    """
    N, m = codes.shape
    Q, m_l, ksub = luts.shape
    assert m == m_l, (m, m_l)
    blk_n = min(blk_n, N)
    assert N % blk_n == 0, (N, blk_n)
    assert blk_n == N or blk_n % 128 == 0, blk_n
    n_blocks = N // blk_n
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    scales = None
    if lut_dtype == "int8":
        luts, scales = quantize_lut_int8(luts)
        luts_flat = luts.reshape(Q, m * ksub)
    else:
        luts_flat = luts.astype(jnp.dtype(lut_dtype)).reshape(Q, m * ksub)
    # query rows pad to whole LUT blocks; padded rows are sliced off below
    bq = QUERY_BLOCK
    Qp = -(-Q // bq) * bq
    luts_flat = jnp.pad(luts_flat, ((0, Qp - Q), (0, 0)))

    in_specs = [
        pl.BlockSpec((blk_n, m), lambda qi, n: (n, 0)),
        pl.BlockSpec((bq, m * ksub), lambda qi, n: (qi, 0)),
        pl.BlockSpec((1, blk_n), lambda qi, n: (0, n)),
    ]
    args = [codes.astype(jnp.int32), luts_flat,
            bias.astype(jnp.float32).reshape(1, N)]
    if scales is not None:
        in_specs.append(pl.BlockSpec((bq, m), lambda qi, n: (qi, 0)))
        args.append(jnp.pad(scales, ((0, Qp - Q), (0, 0))))

    kernel = functools.partial(_pq_adc_kernel, blk_n=blk_n, n_blocks=n_blocks,
                               k=k, ksub=ksub, int8=scales is not None)
    s, i = pl.pallas_call(
        kernel,
        grid=(Qp // bq, n_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), lambda qi, n: (qi, 0)),
            pl.BlockSpec((bq, k), lambda qi, n: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return s[:Q], i[:Q]
