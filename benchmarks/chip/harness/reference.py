"""The plain reference and the comparison that decides ``correct``.

The reference knows nothing of the program: cosine top-k by a full scan of
the corpus, which is regenerated from the seed, in f32 at HIGHEST matmul
precision, streamed over row chunks. The served answers are judged by
what they say:

* ``score_gap`` -- the largest distance between a served score and the
  exact cosine of the id it was served with. The configuration states an
  exact f32 re-rank, so the served scores must be the exact scores.
* ``malformed`` -- answers that are not k distinct ids inside the corpus,
  with finite scores ranked best first.
* ``unanswered`` -- queries due in the window whose answer never came, or
  came as an error.
* ``recall_miss`` -- 1 - recall@k of the well-formed answers against the
  exact top-k. The re-rank only orders the candidates the ADC stage hands
  it, so a fault below it (lists skipped, a wrong table) shows here and
  nowhere else. An approximate index misses some by design; the limit
  sits far above what sound runs miss and below what such faults do.
  ``recall_at_10``, the end-to-end metric, is 1 minus it.

The control puts the same scan in the program's place one precision lower
(bf16 operands, f32 accumulation: the chip's default for an f32 matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_CHUNK = 65_536
QUERY_BLOCK = 1024


def _normalize(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "low", "step"))
def _chunk_topk(x, qn, at, start, *, k, low, step):
    """Top-k of the queries over rows [start, at + step) of x."""
    rn = _normalize(jax.lax.dynamic_slice(x, (at, 0), (step, x.shape[1])))
    if low:
        s = jnp.dot(qn.astype(jnp.bfloat16), rn.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
    else:
        s = jnp.dot(qn, rn.T, precision=HIGHEST)
    s = jnp.where(at + jnp.arange(step) < start, -jnp.inf, s)
    s, i = jax.lax.top_k(s, k)
    return s, i + at


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(s_a, i_a, s_b, i_b, *, k):
    s = jnp.concatenate([s_a, s_b], axis=1)
    i = jnp.concatenate([i_a, i_b], axis=1)
    s, pos = jax.lax.top_k(s, k)
    return s, jnp.take_along_axis(i, pos, axis=1)


def scan_topk(x, q: np.ndarray, k: int, *, low: bool = False):
    """(scores, ids) host arrays of the exact cosine top-k of each query
    over the rows of ``x``; ``low`` is the control's precision."""
    n = x.shape[0]
    step = min(ROW_CHUNK, n)
    out_s, out_i = [], []
    for b in range(0, len(q), QUERY_BLOCK):
        qb = q[b:b + QUERY_BLOCK]
        pad = QUERY_BLOCK - len(qb) if len(q) > QUERY_BLOCK else 0
        qn = _normalize(jnp.asarray(np.pad(qb, ((0, pad), (0, 0)))))
        best = None
        for start in range(0, n, step):
            at = min(start, n - step)  # ragged end: overlap the last chunk
            s, i = _chunk_topk(x, qn, jnp.int32(at), jnp.int32(start), k=k,
                               low=low, step=step)
            best = (s, i) if best is None else _merge(*best, s, i, k=k)
        out_s.append(np.asarray(best[0])[:len(qb)])
        out_i.append(np.asarray(best[1])[:len(qb)])
    return np.concatenate(out_s), np.concatenate(out_i)


@jax.jit
def _scores_of(x, qn, ids):
    rows = _normalize(jnp.take(x, ids, axis=0))  # (Q, k, d)
    return jnp.sum(qn[:, None, :] * rows, axis=-1)  # exact f32 products


def scores_of(x, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Exact cosine of each query with each of its served ids (f32
    elementwise products, no matmul rounding)."""
    out = []
    for b in range(0, len(q), QUERY_BLOCK):
        qn = _normalize(jnp.asarray(q[b:b + QUERY_BLOCK]))
        safe = np.clip(ids[b:b + QUERY_BLOCK], 0, x.shape[0] - 1)
        out.append(np.asarray(_scores_of(x, qn, jnp.asarray(safe))))
    return np.concatenate(out) if out else np.zeros(ids.shape, np.float32)


def well_formed(s: np.ndarray, i: np.ndarray, k: int, n: int) -> bool:
    return (s.shape == (k,) and i.shape == (k,) and np.isfinite(s).all()
            and ((i >= 0) & (i < n)).all() and len(set(i.tolist())) == k
            and bool((np.diff(s) <= 0).all()))


def compare(answers, x, q: np.ndarray, k: int, ref_ids: np.ndarray):
    """Judge the served answers against the reference.

    ``answers[j]`` is (scores, ids) for query j, or None where none came.
    Returns a dict that maps each compared number to its value."""
    n = x.shape[0]
    ok = [a is not None and well_formed(np.asarray(a[0]), np.asarray(a[1]),
                                        k, n) for a in answers]
    unanswered = sum(a is None for a in answers)
    malformed = sum(a is not None and not g for a, g in zip(answers, ok))
    rows = [j for j, g in enumerate(ok) if g]
    gap, recall = 0.0, 0.0  # nothing well formed: nothing recalled
    if rows:
        s = np.stack([np.asarray(answers[j][0]) for j in rows])
        i = np.stack([np.asarray(answers[j][1]) for j in rows])
        exact = scores_of(x, q[rows], i)
        gap = float(np.max(np.abs(s - exact)))
        hits = sum(len(set(a.tolist()) & set(b.tolist()))
                   for a, b in zip(i, ref_ids[rows]))
        recall = hits / (len(rows) * k)
    return {"score_gap": gap, "malformed": malformed,
            "unanswered": unanswered, "recall_miss": 1.0 - recall}
