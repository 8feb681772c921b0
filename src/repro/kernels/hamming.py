"""Hamming-distance Pallas kernel — the LSH engine's ranking pass.

XOR + popcount between the query signatures and every packed corpus code,
min-reduced over hash tables. Integer VPU work, no MXU: popcount is the
classic SWAR bit-slide (Mosaic has no population-count primitive), five
shift/mask/multiply steps per uint32 word.

Grid: (N / blk_n,); corpus-code tiles stream through VMEM word-major,
(T, W, blk_n) with the rows on the lanes, while the query codes stay
resident as (T, W, Q, 1) columns: each (table, word) is one (Q, 1) x
(1, blk_n) XOR broadcast on full (Q, blk_n) vregs — no 4-D broadcast with
the W-wide word axis on the lanes. Output block (Q, blk_n) per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _popcount32(v):
    """SWAR popcount over uint32 lanes."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _hamming_kernel(q_ref, c_ref, o_ref):
    T, W = c_ref.shape[:2]
    best = None
    for t in range(T):  # static unroll: T tables x W words
        d = None
        for w in range(W):
            x = jnp.bitwise_xor(q_ref[t, w], c_ref[t, w:w + 1, :])
            pc = _popcount32(x)  # (Q, blk_n)
            d = pc if d is None else d + pc
        best = d if best is None else jnp.minimum(best, d)
    o_ref[...] = best


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret"))
def hamming(q_codes, c_codes, *, blk_n: int = 1024, interpret: bool = False):
    """q: (T, Q, W) uint32; c: (T, N, W) uint32 -> (Q, N) int32 min-Hamming.
    N must divide by blk_n, and blk_n must be N or a multiple of 128."""
    T, Q, W = q_codes.shape
    N = c_codes.shape[1]
    blk_n = min(blk_n, N)
    assert N % blk_n == 0, (N, blk_n)
    assert blk_n == N or blk_n % 128 == 0, blk_n
    return pl.pallas_call(
        _hamming_kernel,
        grid=(N // blk_n,),
        in_specs=[
            pl.BlockSpec((T, W, Q, 1), lambda n: (0, 0, 0, 0)),
            pl.BlockSpec((T, W, blk_n), lambda n: (0, 0, n)),
        ],
        out_specs=pl.BlockSpec((Q, blk_n), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.int32),
        interpret=interpret,
    )(jnp.transpose(q_codes, (0, 2, 1))[..., None],
      jnp.transpose(c_codes, (0, 2, 1)))
