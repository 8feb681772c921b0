"""Work of the ADC stage, counted on the algorithm and not on a kernel.

IVF-ADC scores every real row of each query's nprobe nearest lists: one
lookup-and-add per subspace, reading the row's m code bytes and its 4-byte
id, plus the query's (m, ksub) f32 table. Pad slots, pad blocks and pad
queries are no part of the work, whatever an implementation spends on them.
"""
from __future__ import annotations

import numpy as np


def list_rows(layout) -> np.ndarray:
    """Real rows per inverted list, read from the index's block layout."""
    owner = np.asarray(layout.block_cluster)
    live = (np.asarray(layout.slots) >= 0).sum(axis=1)
    mine = owner >= 0
    return np.bincount(owner[mine], weights=live[mine],
                       minlength=int(layout.C)).astype(np.int64)


def probed_rows(centroids, queries, nprobe: int, sizes) -> int:
    """Rows in the nprobe lists whose centroids score highest (cosine)."""
    c = np.asarray(centroids, np.float64)
    q = np.asarray(queries, np.float64)
    q = q / np.linalg.norm(q, axis=1, keepdims=True)
    total = 0
    for b in range(0, len(q), 1024):
        s = q[b:b + 1024] @ c.T
        probe = np.argpartition(-s, nprobe - 1, axis=1)[:, :nprobe]
        total += int(sizes[probe].sum())
    return total


def count(run):
    """{'ops', 'bytes', 'queries'} of the ADC stage over the queries the
    window sent, or None where the index is not an IVF-PQ index."""
    idx = getattr(run.db, "index", None)
    layout = getattr(idx, "layout", None)
    if layout is None or getattr(idx, "centroids", None) is None:
        return None
    m, ksub = int(idx.m), int(idx.ksub)
    nprobe = min(int(idx.nprobe), int(idx.centroids.shape[0]))
    rows = probed_rows(idx.centroids, run.queries, nprobe, list_rows(layout))
    n_q = len(run.queries)
    return {"ops": rows * m, "bytes": rows * (m + 4) + n_q * m * ksub * 4,
            "queries": n_q, "rows": rows}
