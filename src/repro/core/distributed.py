"""Distributed search: corpus row-sharded over the mesh, queries replicated.

The SPMD program of the paper's query path at pod scale:

  1. every device scores the replicated query batch against its corpus rows
     (local flat/int8 top-k — MXU matmul + on-chip top-k, no HBM round trip);
  2. local ids are lifted to global ids with the device's row offset;
  3. the (Q, k) winners per device are all-gathered — k*n_shards candidates,
     a tiny tensor compared to the corpus — and merged by one more top-k.

Step 3's all-gather is the ONLY collective in the query path, and it moves
O(Q*k*shards) bytes vs the O(N*d) a gather-the-corpus design would. A
hierarchical variant merges within a pod before crossing the (slower)
pod-interconnect axis, shrinking inter-pod bytes by the intra-pod shard
count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distances as D
from repro.core.flat import flat_search
from repro.kernels import ops as kops


def _merge_local_topk(s, i, *, k: int, axes, hierarchical: bool = True):
    """The shared SPMD merge tail (runs INSIDE a shard_map body): pad the
    local (Q, k') candidates to k, optionally pre-merge along the fast
    inner axes so only k survivors cross the outer (pod) axis, then
    all-gather + top-k. The only collective in every query path."""
    if s.shape[-1] < k:
        s = jnp.pad(s, ((0, 0), (0, k - s.shape[-1])),
                    constant_values=-jnp.inf)
        i = jnp.pad(i, ((0, 0), (0, k - i.shape[-1])), constant_values=-1)
    if hierarchical and len(axes) > 1:
        for a in reversed(axes[1:]):
            s_all = jax.lax.all_gather(s, a, axis=1, tiled=True)
            i_all = jax.lax.all_gather(i, a, axis=1, tiled=True)
            s, pos = jax.lax.top_k(s_all, k)
            i = jnp.take_along_axis(i_all, pos, axis=-1)
        merge_axes = (axes[0],)
    else:
        merge_axes = axes
    s_all = jax.lax.all_gather(s, merge_axes, axis=1, tiled=True)
    i_all = jax.lax.all_gather(i, merge_axes, axis=1, tiled=True)
    s, pos = jax.lax.top_k(s_all, k)
    return s, jnp.take_along_axis(i_all, pos, axis=-1)


def corpus_sharding(mesh: Mesh, axes=None):
    """Row-sharding spec over every mesh axis (flattened)."""
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    return NamedSharding(mesh, P(axes, None))


def pad_to_shards(x, n_shards: int):
    """Pad rows to a multiple of n_shards; returns (padded, valid mask)."""
    N = x.shape[0]
    pad = (-N) % n_shards
    valid = jnp.arange(N + pad) < N
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, valid


def sharded_flat_search(corpus, q, *, mesh: Mesh, k: int, metric: str = "cosine",
                        axes=None, valid=None, tile: int = 65536,
                        hierarchical: bool = True):
    """Exact distributed top-k. corpus (N, d) row-sharded; q (Q, d) replicated.

    N must be divisible by the product of the shard axes (use pad_to_shards).
    Returns (scores (Q, k), global ids (Q, k)).
    """
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    N = corpus.shape[0]
    assert N % n_shards == 0, (N, n_shards)
    local_n = N // n_shards

    in_specs = (P(axes, None), P(None, None)) + ((P(axes),) if valid is not None else ())
    out_specs = (P(None, None), P(None, None))

    def local_search(c_blk, q_rep, *maybe_valid):
        # flat index of this shard along the flattened corpus axes
        idx = 0
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        v_blk = maybe_valid[0] if maybe_valid else None
        s, i = flat_search(c_blk, q_rep, metric=metric, k=min(k, local_n),
                           tile=tile, valid=v_blk)
        i = i + idx * local_n  # global ids
        return _merge_local_topk(s, i, k=k, axes=axes,
                                 hierarchical=hierarchical)

    args = (corpus, q) + ((valid,) if valid is not None else ())
    return jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)(*args)


def sharded_pq_search(codes, luts, *, mesh: Mesh, k: int, axes=None,
                      valid=None, hierarchical: bool = True, use_kernel=None,
                      lut_dtype: str = "float32"):
    """Compressed distributed top-k: PQ codes row-sharded, LUTs replicated.

    The same SPMD program as sharded_flat_search with the local exact scan
    swapped for the fused ADC dispatch (Pallas kernel per shard on TPU, jnp
    twin elsewhere): every device ADC-scores the replicated (Q, m, ksub)
    LUTs against its local (N/S, m) uint8 codes, then the identical
    local-top-k + hierarchical all-gather merge runs. Per-device resident
    bytes are N*m/S + the replicated tables instead of N*d*4/S — the whole
    point of serving PQ under the mesh.

    codes (N, m) must divide by the shard count (pad_to_shards). Returns
    (scores (Q, k), global ids (Q, k)).
    """
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    N = codes.shape[0]
    assert N % n_shards == 0, (N, n_shards)
    local_n = N // n_shards

    in_specs = ((P(axes, None), P(None, None, None))
                + ((P(axes),) if valid is not None else ()))
    out_specs = (P(None, None), P(None, None))

    def local_search(c_blk, luts_rep, *maybe_valid):
        idx = 0
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        v_blk = maybe_valid[0] if maybe_valid else None
        s, i = kops.adc_topk(c_blk, luts_rep, k=min(k, local_n), valid=v_blk,
                             use_kernel=use_kernel, lut_dtype=lut_dtype)
        i = i + idx * local_n  # global ids
        return _merge_local_topk(s, i, k=k, axes=axes,
                                 hierarchical=hierarchical)

    args = (codes, luts) + ((valid,) if valid is not None else ())
    return jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)(*args)


def sharded_ivf_pq_search(bucket_codes, bucket_ids, visit, luts, coarse, *,
                          mesh: Mesh, k: int, steps_per_probe: int = 1,
                          blocks_per_shard: int, axes=None,
                          hierarchical: bool = True, use_kernel=None,
                          lut_dtype: str = "float32"):
    """Bucket-range-sharded IVF-PQ top-k: each device owns a contiguous
    range of inverted-list BLOCKS (plus its own all-pad block), queries /
    LUTs / visit tables replicated.

    The caller computes probes and expands them into a ``visit`` table in
    GLOBAL block numbering [0, S * blocks_per_shard), with tail steps of
    short clusters already pointing at -1. Each shard keeps the steps whose
    block falls in its range (localized to its (blocks_per_shard + 1, blk)
    slab) and retargets every other step — off-shard probes AND -1 tails —
    at its local all-pad block, so they knock out on id without any score
    surgery, and the per-query kernel, told that block, skips them. The
    local bucket-resident ADC dispatch (Pallas ivf_adc kernel
    per shard on TPU, jnp twin elsewhere) then runs unchanged, local ids
    are already global corpus rows (bucket_ids store them), and the same
    local-top-k + hierarchical all-gather merge as the flat/pq paths
    finishes the query — still O(Q*k*shards) collective bytes.

    bucket_codes: (S*(blocks_per_shard+1), blk, m) — the per-shard slabs
    concatenated, each ending in its pad block (DistributedIVFPQ builds
    this at load); bucket_ids likewise; visit: (Q, T) int32,
    T = nprobe * steps_per_probe; luts: (Q, m, ksub) or (Q, nprobe, m,
    ksub); coarse: (Q, nprobe) f32. Returns (scores (Q, k), ids (Q, k)).
    """
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    assert bucket_codes.shape[0] == n_shards * (blocks_per_shard + 1), (
        bucket_codes.shape, n_shards, blocks_per_shard)
    local_cand = (blocks_per_shard + 1) * bucket_codes.shape[1]

    in_specs = (P(axes, None, None), P(axes, None), P(None, None),
                P(*((None,) * luts.ndim)), P(None, None))
    out_specs = (P(None, None), P(None, None))

    def local_search(c_blk, id_blk, visit_rep, luts_rep, coarse_rep):
        idx = 0
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        off = idx * blocks_per_shard
        in_shard = (visit_rep >= off) & (visit_rep < off + blocks_per_shard)
        v_loc = jnp.where(in_shard, visit_rep - off, blocks_per_shard)
        kk = min(k, local_cand)
        s, i = kops.ivf_adc_topk(c_blk, id_blk, v_loc, luts_rep, k=kk,
                                 coarse=coarse_rep,
                                 steps_per_probe=steps_per_probe,
                                 use_kernel=use_kernel, lut_dtype=lut_dtype,
                                 pad_block=blocks_per_shard)
        return _merge_local_topk(s, i, k=k, axes=axes,
                                 hierarchical=hierarchical)

    return jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)(
                         bucket_codes, bucket_ids, visit, luts, coarse)


def gspmd_flat_search(corpus, q, *, mesh: Mesh, k: int, metric: str = "cosine",
                      axes=None, valid=None):
    """Same program expressed with sharding constraints only (GSPMD chooses
    the collectives). Used by the dry-run serve_step so the compiler's own
    schedule is what the roofline reads."""
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    corpus = jax.lax.with_sharding_constraint(corpus, NamedSharding(mesh, P(axes, None)))
    q = jax.lax.with_sharding_constraint(q, NamedSharding(mesh, P(None, None)))
    scores = D.pairwise_scores(q, corpus, metric)
    if valid is not None:
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    s, i = jax.lax.top_k(scores, k)
    return jax.lax.with_sharding_constraint((s, i), NamedSharding(mesh, P(None, None)))


def two_level_search(corpus, q, *, mesh: Mesh, k: int, q_axes, c_axes,
                     tile: int = 4096, n_valid: int = None, metric: str = "dot"):
    """Batched distributed top-k: queries sharded over `q_axes`, corpus rows
    over `c_axes` (disjoint). Each device runs a tiled local top-k (running
    (Q_loc, k) scoreboard, never a full (Q_loc, N_loc) matrix), then merges
    k survivors across `c_axes` — the bulk-scoring path (recsys serve_bulk:
    262k users x 1M items would otherwise be a petabyte score matrix).
    """
    q_axes = tuple(q_axes)
    c_axes = tuple(c_axes)
    n_c = 1
    for a in c_axes:
        n_c *= mesh.shape[a]
    N = corpus.shape[0]
    assert N % n_c == 0, (N, n_c)
    local_n = N // n_c

    def local(c_blk, q_blk):
        idx = 0
        for a in c_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        base = idx * local_n
        valid = (None if n_valid is None
                 else (base + jnp.arange(local_n)) < n_valid)
        kk = min(k, local_n)
        s, i = flat_search(c_blk, q_blk, metric=metric, k=kk, tile=tile,
                           valid=valid)
        i = i + base
        if kk < k:
            s = jnp.pad(s, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, k - kk)), constant_values=-1)
        s_all = jax.lax.all_gather(s, c_axes, axis=1, tiled=True)
        i_all = jax.lax.all_gather(i, c_axes, axis=1, tiled=True)
        s, pos = jax.lax.top_k(s_all, k)
        return s, jnp.take_along_axis(i_all, pos, axis=-1)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(c_axes, None), P(q_axes, None)),
        out_specs=(P(q_axes, None), P(q_axes, None)),
        check_vma=False)(corpus, q)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_candidate_sets(scores, ids, k: int):
    """(S, Q, k') per-shard candidates -> global (Q, k). Host-side merge for
    multi-process serving fronts."""
    S, Q, kk = scores.shape
    s = jnp.moveaxis(scores, 0, 1).reshape(Q, S * kk)
    i = jnp.moveaxis(ids, 0, 1).reshape(Q, S * kk)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=-1)
