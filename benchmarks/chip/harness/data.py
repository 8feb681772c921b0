"""Corpora and query streams, made on the device from a seed.

A corpus is a three-level hierarchy of Gaussian clusters: ``n_top`` topic
centres, ``n_sub`` sub-centres spread around them, and rows spread around
the sub-centres. Row i belongs to sub-centre ``i % n_sub``, and sub-centre
s to topic ``s % n_top``. The spreads come from the configuration's
``generator`` block; they set how far a query's exact top-10 spreads over
the index's inverted lists. A query is a fresh row of the same
distribution around a sub-centre that the traffic's topic law picks.

The hierarchy itself is drawn from the configuration's ``geometry_seed``;
the run's seed draws a random rotation of the whole space, applied to the
corpus and the queries alike, and the query stream. Every seed thus holds
different rows with the same cosine geometry: k-means (rotation-equivariant
up to rounding) builds lists of the same sizes, so every seed asks the
index for the same work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 65_536  # rows made per jitted call; the last chunk is ragged


def root_key(seed: int):
    """A PRNG key for any whole seed, 32 bits or more."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class Shape:
    """The cluster hierarchy of one corpus (sizes from the config)."""

    def __init__(self, rows: int, dim: int, gen: dict):
        self.rows, self.dim = int(rows), int(dim)
        self.geometry_seed = int(gen["geometry_seed"])
        self.n_sub = max(1, self.rows // int(gen["rows_per_sub"]))
        self.n_top = max(1, self.rows // int(gen["rows_per_top"]))
        self.sub_spread = float(gen["sub_spread"])
        self.noise = float(gen["noise"])


@functools.partial(jax.jit, static_argnames=("n_top", "n_sub", "dim",
                                             "spread"))
def _centres(key, *, n_top, n_sub, dim, spread):
    k1, k2 = jax.random.split(key)
    top = jax.random.normal(k1, (n_top, dim), jnp.float32)
    return (jnp.take(top, jnp.arange(n_sub) % n_top, axis=0)
            + spread * jax.random.normal(k2, (n_sub, dim), jnp.float32))


@functools.partial(jax.jit, static_argnames=("rows", "noise"),
                   donate_argnums=(0,))
def _fill(buf, key, sub, rot, at, *, rows, noise):
    """Write rows [at, at + rows) of the rotated corpus into ``buf``."""
    ids = at + jnp.arange(rows, dtype=jnp.int32)
    eps = jax.random.normal(jax.random.fold_in(key, at),
                            (rows, buf.shape[1]), jnp.float32)
    part = jnp.take(sub, ids % sub.shape[0], axis=0) + noise * eps
    part = jnp.dot(part, rot, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.dynamic_update_slice(buf, part, (at, 0))


def rotation(seed: int, dim: int):
    """A random orthogonal (dim, dim) matrix from the seed: QR of a Gaussian
    matrix in float64 on the host, signs fixed so the draw is uniform."""
    g = np.random.default_rng([seed, 0x5EED]).standard_normal((dim, dim))
    q, r = np.linalg.qr(g)
    return jnp.asarray(q * np.sign(np.diag(r)), jnp.float32)


def centres(shape: Shape):
    return _centres(jax.random.fold_in(root_key(shape.geometry_seed), 1),
                    n_top=shape.n_top, n_sub=shape.n_sub, dim=shape.dim,
                    spread=shape.sub_spread)


def make_corpus(shape: Shape, seed: int):
    """(rows, dim) f32 on the default device, made chunk by chunk into one
    buffer (peak: the corpus plus one chunk)."""
    sub = centres(shape)
    rot = rotation(seed, shape.dim)
    key = jax.random.fold_in(root_key(shape.geometry_seed), 2)
    rows = min(CHUNK, shape.rows)
    buf = jnp.zeros((shape.rows, shape.dim), jnp.float32)
    for start in range(0, shape.rows, rows):
        # a ragged last chunk is made as the last full chunk of the buffer
        at = min(start, shape.rows - rows)
        buf = _fill(buf, key, sub, rot, jnp.int32(at), rows=rows,
                    noise=shape.noise)
    return buf.block_until_ready()


def topic_subs(shape: Shape, topic: dict, n: int, rng) -> np.ndarray:
    """Sub-centre of each of n queries under the traffic's topic law; the
    one law so far is ``uniform`` over the sub-centres."""
    if topic["kind"] != "uniform":
        raise ValueError(f"unknown topic law {topic['kind']!r}")
    return rng.integers(0, shape.n_sub, n)


@functools.partial(jax.jit, static_argnames=("noise",))
def _queries(sub, rot, subs, key, *, noise):
    eps = jax.random.normal(key, (subs.shape[0], sub.shape[1]), jnp.float32)
    q = jnp.take(sub, subs, axis=0) + noise * eps
    return jnp.dot(q, rot, precision=jax.lax.Precision.HIGHEST)


def make_queries(shape: Shape, seed: int, subs: np.ndarray, stream: int):
    """Host (n, dim) f32 queries around the given sub-centres; ``stream``
    keeps the warm-up and the window's queries apart."""
    key = jax.random.fold_in(jax.random.fold_in(root_key(seed), 3), stream)
    sub, rot = centres(shape), rotation(seed, shape.dim)
    out = []
    for b in range(0, len(subs), 4096):
        part = subs[b:b + 4096]
        pad = 4096 - len(part)
        q = _queries(sub, rot,
                     jnp.asarray(np.pad(part, (0, pad)), jnp.int32),
                     jax.random.fold_in(key, b), noise=shape.noise)
        out.append(np.asarray(q)[:len(part)])
    return (np.concatenate(out) if out
            else np.zeros((0, shape.dim), np.float32))


def exp_gaps(n: int, rate: float, rng) -> np.ndarray:
    """n Poisson inter-arrival gaps: the exponential law's n mid-quantiles,
    in an order drawn from ``rng``. Every seed offers the same set of gaps
    (the same load) in another order."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / rate)


def n_arrivals(rate: float, seconds: float) -> int:
    return max(1, int(math.floor(rate * seconds)))
