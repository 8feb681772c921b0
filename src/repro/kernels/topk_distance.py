"""Fused distance + top-k Pallas kernel — the vector DB's query hot path.

The paper's query loop scores the corpus then sorts; done naively the (Q, N)
score matrix round-trips through HBM. Here corpus tiles of (blk_n, d) stream
through VMEM, each tile's scores come off the MXU ((Q, d) x (d, blk_n)), and
a running (Q, k) best-score/best-id scoreboard lives in VMEM scratch across
grid steps — HBM traffic is corpus-read + (Q, k) out, nothing else.

Top-k inside the kernel is k rounds of (max, argmax, one-hot knockout) over
the concatenated (running || tile) scores — only max/argmax/iota/where, all
Mosaic-friendly vector ops (lax.top_k does not lower to TPU). k is static
and small (<= 64), so the rounds unroll.

Grid: (N / blk_n,), sequential on TPU. The (N,) score bias rides as a
(1, N) row so its (1, blk_n) blocks sit on the lane tiling. l2 mode fuses the -|c|^2 epilogue from
a precomputed corpus_sq tile; q_sq is a rank-0 shift that cannot change the
ranking and is added by the ops.py wrapper for score parity with the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _select_topk(scores, ids, k: int):
    """(Q, C) scores/ids -> (Q, k) best, by k unrolled knockout rounds."""
    Q, C = scores.shape
    out_s = []
    out_i = []
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, C), 1)
    for _ in range(k):
        m = jnp.max(scores, axis=-1)  # (Q,)
        am = jnp.argmax(scores, axis=-1).astype(jnp.int32)  # (Q,)
        hit = col == am[:, None]  # exactly one per row
        out_s.append(m)
        out_i.append(jnp.sum(jnp.where(hit, ids, 0), axis=-1))
        scores = jnp.where(hit, NEG_INF, scores)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _topk_kernel(c_ref, q_ref, bias_ref, s_out, i_out, bs_ref, bi_ref, *,
                 blk_n: int, n_blocks: int, k: int, l2: bool):
    ni = pl.program_id(0)

    @pl.when(ni == 0)
    def _init():
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    q = q_ref[...].astype(jnp.float32)          # (Q, d)
    c = c_ref[...].astype(jnp.float32)          # (blk_n, d)
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, blk_n)
    if l2:
        s = 2.0 * s
    # bias folds in the metric epilogue (-|c|^2 for l2) AND pad-row knockout
    s = s + bias_ref[...]
    Q = s.shape[0]
    ids = ni * blk_n + jax.lax.broadcasted_iota(jnp.int32, (Q, blk_n), 1)

    comb_s = jnp.concatenate([bs_ref[...], s], axis=1)
    comb_i = jnp.concatenate([bi_ref[...], ids], axis=1)
    bs_ref[...], bi_ref[...] = _select_topk(comb_s, comb_i, k)

    @pl.when(ni == n_blocks - 1)
    def _finalize():
        s_out[...] = bs_ref[...]
        i_out[...] = bi_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "l2", "blk_n", "interpret"))
def topk_distance(corpus, q, *, k: int, l2: bool = False, bias=None,
                  blk_n: int = 512, interpret: bool = False):
    """corpus: (N, d); q: (Q, d) -> (scores (Q, k) f32, ids (Q, k) int32).

    Scores are dot products (l2=False) or -(|q|^2 - 2 q.c + |c|^2) (l2=True).
    ``bias`` (N,) is added to every query's scores — the l2 -|c|^2 epilogue
    and/or -inf pad-row knockout (built by ops.py). N must divide by blk_n,
    and blk_n must be N or a multiple of 128 (the bias block's lane width).
    """
    N, d = corpus.shape
    Q = q.shape[0]
    blk_n = min(blk_n, N)
    assert N % blk_n == 0, (N, blk_n)
    assert blk_n == N or blk_n % 128 == 0, blk_n
    n_blocks = N // blk_n
    if bias is None:
        bias = (-jnp.sum(jnp.square(corpus.astype(jnp.float32)), axis=-1)
                if l2 else jnp.zeros((N,), jnp.float32))

    kernel = functools.partial(_topk_kernel, blk_n=blk_n, n_blocks=n_blocks,
                               k=k, l2=l2)
    s, i = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((blk_n, d), lambda n: (n, 0)),
            pl.BlockSpec((Q, d), lambda n: (0, 0)),
            pl.BlockSpec((1, blk_n), lambda n: (0, n)),
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda n: (0, 0)),
            pl.BlockSpec((Q, k), lambda n: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q, k), jnp.float32),
            pltpu.VMEM((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(corpus, q, bias.astype(jnp.float32).reshape(1, N))
    if l2:
        s = s - jnp.sum(jnp.square(q.astype(jnp.float32)), axis=-1, keepdims=True)
    return s, i
