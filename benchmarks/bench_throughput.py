"""Embedding + query-path throughput.

Measures (CPU walltime; the TPU numbers live in the dry-run roofline):
  * encoder forward tokens/s at several batch sizes (mini-SBERT smoke),
  * end-to-end insert pipeline split: embed time vs index time — reproducing
    the paper's ">99% of wall time was SBERT" observation,
  * dense vs chunked attention walltime at growing sequence length,
  * the PQ ADC hot path: PR-1 jnp ``pq_topk`` scan vs the fused dispatch
    (f32 and bf16-LUT twins of the Pallas kernel) — QPS and recall@10 per
    path, plus the served ``pq`` engine end to end,
  * the IVF-ADC bucket path: the bucket-resident fused dispatch vs the
    PR-2 all-codes augmented-LUT scan and the PR-2 jnp gather path over an
    nprobe sweep, the f32/bf16/int8 LUT ladder, and the served ``ivf_pq``
    engines (the second CI recall gate); the committed full-size run is
    ``BENCH_ivf_adc.json``,
  * the mutation lifecycle (``mutation_paths``): sustained insert QPS
    (amortized vs spill-heavy), query QPS at 0/10/30% tombstones +
    compact() cost, 1:8 write/read interleaved serving, and recall@10
    after 20% churn vs a rebuilt-from-scratch index (the third CI gate);
    the committed full-size run is ``BENCH_mutation.json``,
  * the durability lifecycle (``wal_paths``): write QPS with no WAL vs
    fsync-per-record vs group commit through the async front, recovery
    walltime vs WAL tail length, and a crash-mid-ingest recovery whose
    top-k must match an uncrashed twin bit-for-bit (the recovery CI
    gate); the committed full-size run is ``BENCH_wal.json``,
  * filtered + hybrid search (``filtered_paths``): filtered-vs-post-filter
    exact-parity gate rows on a full-coverage ivf_pq, filtered QPS +
    recall at ~1/10/50% predicate selectivity on the served engine, and
    dense vs BM25 vs fused MRR on word-noised MarcoLike queries (the
    hybrid CI gate); the committed full-size run is
    ``BENCH_filtered.json``,
  * ``DistributedPQ`` per-device resident bytes vs a replicated f32 corpus
    on a forced multi-device host mesh (subprocess).

``main(json_path=...)`` additionally dumps every section's rows as JSON —
CI uploads it as the smoke artifact and gates on the pq recall field;
``BENCH_pq_adc.json`` at the repo root is the committed full-size baseline.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import VectorDB
from repro.data import MarcoLike
from repro.kernels.autotune import LEDGER
from repro.models import encoder as enc_lib


def _timeit(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def encoder_throughput():
    cfg = get_arch("thistle-sbert").smoke
    params = enc_lib.init(cfg, jax.random.PRNGKey(0))
    enc = jax.jit(lambda t: enc_lib.encode(params, cfg, t))
    rows = []
    for B in (8, 32, 128):
        toks = jnp.ones((B, 48), jnp.int32)
        dt = _timeit(enc, toks)
        rows.append({"batch": B, "tokens_per_s": B * 48 / dt, "sec_per_batch": dt})
    return rows


def insert_split(N: int = 1000):
    """Embed-vs-index wall time split for a full corpus insert."""
    cfg = get_arch("thistle-sbert").smoke
    params = enc_lib.init(cfg, jax.random.PRNGKey(0))
    enc = jax.jit(lambda t: enc_lib.encode(params, cfg, t))
    data = MarcoLike(n_passages=N, vocab_size=cfg.vocab_size)
    toks = jnp.asarray(data.passages[:, :48] % cfg.vocab_size)
    enc(toks[:128])  # compile
    t0 = time.perf_counter()
    embs = []
    for i in range(0, N, 128):
        chunk = toks[i:i + 128]
        if chunk.shape[0] < 128:
            chunk = jnp.pad(chunk, ((0, 128 - chunk.shape[0]), (0, 0)))
        embs.append(np.asarray(enc(chunk)))
    emb = np.concatenate(embs)[:N]
    t_embed = time.perf_counter() - t0
    t0 = time.perf_counter()
    db = VectorDB("flat").load(emb)
    _ = db.query(emb[:1], k=1)
    t_index = time.perf_counter() - t0
    return {"N": N, "embed_s": t_embed, "index_s": t_index,
            "embed_frac": t_embed / (t_embed + t_index)}


def _clustered(rng, n, d, n_clusters, scale=2.0):
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * scale
    return (centers[rng.integers(0, n_clusters, n)]
            + rng.normal(size=(n, d)).astype(np.float32))


def pq_adc_paths(N: int = 10_000, d: int = 64, n_queries: int = 256,
                 k: int = 10, m: int = 8, seed: int = 0):
    """QPS + recall@10 for every ADC scoring path on a clustered corpus.

    Paths (same codes, same LUT build, scoring only):
      * jnp_pq_topk — the PR-1 scanned gather baseline,
      * fused_f32   — ops.adc_topk jnp twin (fused gather+sum+top_k),
      * fused_bf16  — same with bf16 LUTs (half the gathered bytes),
    plus the served ``pq`` engine end to end (LUT build + fused bf16 scan +
    exact refine) whose recall@10 is the CI gate.
    """
    from repro.core.pq import adc_tables, pq_encode, pq_topk, train_pq
    from repro.kernels import ops as kops

    rng = np.random.default_rng(seed)
    n_clusters = max(8, N // 100)
    corpus = _clustered(rng, N, d, n_clusters)
    q = _clustered(rng, n_queries, d, n_clusters)
    exact = VectorDB("flat", metric="cosine").load(corpus)
    eids = np.asarray(exact.query(q, k=k, bucketize=False)[1])

    corpus_n = np.asarray(corpus / np.linalg.norm(corpus, axis=-1, keepdims=True))
    qn = jnp.asarray(q / np.linalg.norm(q, axis=-1, keepdims=True))
    cb = train_pq(jax.random.PRNGKey(seed), jnp.asarray(corpus_n), m=m)
    codes = pq_encode(cb, jnp.asarray(corpus_n))
    luts = adc_tables(cb, qn, metric="dot")

    def recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[i]) & set(eids[i])) / k
                              for i in range(n_queries)]))

    # the served engine config (refine=128 exact re-rank, the recall-floor
    # setting from tests/test_pq.py): what the CI recall gate reads
    db_f32 = VectorDB("pq", metric="cosine", m=m, refine=128).load(corpus)
    db_bf16 = VectorDB("pq", metric="cosine", m=m, refine=128,
                       lut_dtype="bfloat16").load(corpus)
    paths = {
        "jnp_pq_topk": lambda: pq_topk(luts, codes, k=k),
        "fused_f32": lambda: kops.adc_topk(codes, luts, k=k,
                                           use_kernel=False),
        "fused_bf16": lambda: kops.adc_topk(codes, luts, k=k,
                                            use_kernel=False,
                                            lut_dtype="bfloat16"),
        "engine_pq_f32": lambda: db_f32.query(q, k=k),
        "engine_pq_bf16": lambda: db_bf16.query(q, k=k),
    }
    # round-robin the reps so every path sees the same background load and
    # the min-of-reps ratio is stable on noisy shared hosts
    for fn in paths.values():
        jax.block_until_ready(fn())  # compile
    walls = {name: float("inf") for name in paths}
    for _ in range(15):
        for name, fn in paths.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls[name] = min(walls[name], time.perf_counter() - t0)
    rows = [{"path": name, "N": N, "qps": n_queries / walls[name],
             "recall_at_10": recall(paths[name]()[1])}
            for name in paths]

    base = next(r for r in rows if r["path"] == "jnp_pq_topk")
    fused = next(r for r in rows if r["path"] == "fused_bf16")
    rows.append({"path": "speedup_bf16_vs_pq_topk", "N": N,
                 "qps": fused["qps"] / base["qps"],
                 "recall_at_10": fused["recall_at_10"] - base["recall_at_10"]})
    return rows


def _gather_baseline(db, q, k: int, nprobe: int):
    """The PR-2 jnp gather path, reconstructed as a baseline: probe, gather
    the full (Q, nprobe, cap, m) bucket-code tensor, LUT-sum, top-k. This
    is what ivf_pq used to run for l2/true-nprobe before the
    bucket-resident kernel path — kept here (and as kernels.ref.ivf_adc_ref)
    so the speedup rows keep an honest denominator."""
    import functools

    from repro.core.ivf import build_buckets
    from repro.core.pq import adc_tables

    idx = db.index
    assign = idx._host_assign()
    buckets, cap = build_buckets(assign, idx.centroids.shape[0])
    buckets = jnp.asarray(buckets)
    codes = idx._row_major_codes()

    @functools.partial(jax.jit, static_argnames=("k", "nprobe", "cap"))
    def search(codebooks, codes, centroids, buckets, qq, *, k, nprobe, cap):
        Q = qq.shape[0]
        m = codebooks.shape[0]
        c_scores = jnp.einsum("qd,cd->qc", qq, centroids,
                              preferred_element_type=jnp.float32)
        _, probe = jax.lax.top_k(c_scores, nprobe)
        cand = jnp.take(buckets, probe, axis=0)  # (Q, nprobe, cap)
        valid = cand >= 0
        safe = jnp.where(valid, cand, 0)
        bucket_codes = jnp.take(codes.astype(jnp.int32), safe, axis=0)
        luts = adc_tables(codebooks, qq, metric="dot")
        flat = bucket_codes.reshape(Q, nprobe * cap, m)
        s = jnp.zeros((Q, nprobe * cap), jnp.float32)
        for j in range(m):
            s = s + jnp.take_along_axis(luts[:, j, :], flat[..., j], axis=1)
        s = s.reshape(Q, nprobe, cap) + jnp.take_along_axis(
            c_scores, probe, axis=1)[:, :, None]
        s = jnp.where(valid, s, -jnp.inf).reshape(Q, nprobe * cap)
        s, pos = jax.lax.top_k(s, k)
        return s, jnp.take_along_axis(cand.reshape(Q, nprobe * cap), pos,
                                      axis=-1)

    qq = jnp.asarray(q / np.linalg.norm(q, axis=-1, keepdims=True))
    return lambda: search(idx.codebooks, codes, idx.centroids, buckets, qq,
                          k=k, nprobe=nprobe, cap=cap)


def ivf_adc_paths(N: int = 10_000, d: int = 64, n_queries: int = 256,
                  k: int = 10, m: int = 8, nprobes=(1, 4, 8, 32),
                  seed: int = 0):
    """The tentpole measurement: QPS + recall@10 of the bucket-resident
    fused IVF-ADC path vs the PR-2 all-codes augmented-LUT scan and the
    PR-2 jnp gather path, swept over nprobe — scoring work should scale
    with the probed candidate count, so the bucket path's margin grows as
    nprobe shrinks. Also rows for l2 on the fused path (previously
    jnp-gather-only), the f32/bf16/int8 LUT-dtype ladder, and the served
    ``ivf_pq`` engines (refine=128) whose recall@10 is the CI gate.

    PR-8 rows: ``bucket_blocked_np*`` runs the block-sharing segmented-
    schedule grid (adc_mode='blocked') against the per-query
    ``bucket_fused_np*`` rows on identical visit tables;
    ``speedup_blocked_vs_perquery_np*`` holds the ratio and
    ``parity_blocked_vs_perquery_np*`` the exact-match fractions (qps =
    ids, recall_at_10 = scores; CI gates both == 1.0).
    ``bucket_adaptive_np*`` adds query-adaptive nprobe (coarse-gap
    threshold 0.3) at the largest swept nprobe.

    PR-9 rows: ``bucket_runres_np*`` / ``bucket_runres_hs`` run the
    run-resident grid (each distinct block fetched once per batch) with
    matching ``speedup_runres_vs_perquery_np*`` /
    ``parity_runres_vs_perquery_np*`` / ``*_hs`` derived rows, plus
    ``speedup_runres_vs_blocked_hs`` (the new grid vs the PR-8 one —
    CI gates >= 1.0 at the high-sharing shape). ``bucket_auto_hs`` serves
    the same shape through ``adc_mode='auto'`` AFTER the online autotuner
    finished its probe phase (the probe batches run pre-timing), so it
    measures the steady-state ledger dispatch; ``autotune_decision``
    exports the fitted ledger entry (metric = chosen grouped grid,
    nprobe = chosen qblk, qps = crossover sharing, recall_at_10 = the
    sharing the probes measured, ``decision`` = the full dict — the CI
    smoke artifact reads it).

    All ivf_pq instances share seed/geometry, so every path probes the
    same buckets at equal nprobe and recall deltas isolate the scoring
    backend.
    """
    rng = np.random.default_rng(seed)
    n_clusters = max(8, N // 100)
    corpus = _clustered(rng, N, d, n_clusters)
    q = _clustered(rng, n_queries, d, n_clusters)
    exact = VectorDB("flat", metric="cosine").load(corpus)
    eids = np.asarray(exact.query(q, k=k, bucketize=False)[1])
    # the high-sharing gate rows always use a 512-query batch — larger
    # than the main batch in both --quick and full runs
    q_hs = q if n_queries >= 512 else _clustered(rng, 512, d, n_clusters)
    eids_hs = (eids if q_hs is q
               else np.asarray(exact.query(q_hs, k=k, bucketize=False)[1]))

    def recall(ids):
        ids = np.asarray(ids)
        ref = eids if ids.shape[0] == n_queries else eids_hs
        return float(np.mean([len(set(ids[i]) & set(ref[i])) / k
                              for i in range(ids.shape[0])]))

    kw = dict(metric="cosine", m=m, refine=0)
    paths = {}
    for p in nprobes:
        db = VectorDB("ivf_pq", nprobe=p, adc_mode="per_query",
                      **kw).load(corpus)
        db_bl = VectorDB("ivf_pq", nprobe=p, adc_mode="blocked",
                         **kw).load(corpus)
        db_rr = VectorDB("ivf_pq", nprobe=p, adc_mode="run_resident",
                         **kw).load(corpus)
        db_l2 = VectorDB("ivf_pq", metric="l2", m=m, refine=0,
                         nprobe=p).load(corpus)
        # bucket_fused_* keeps its historical meaning — the per-query grid
        # every prior BENCH row measured; bucket_blocked_* is the
        # block-sharing segmented-schedule grid over the SAME visit table;
        # bucket_runres_* walks that schedule's per-block runs (one fetch
        # per distinct block per batch)
        paths[f"bucket_fused_np{p}"] = (
            lambda db=db: db.query(q, k=k, bucketize=False), "dot", p)
        paths[f"bucket_blocked_np{p}"] = (
            lambda db=db_bl: db.query(q, k=k, bucketize=False), "dot", p)
        paths[f"bucket_runres_np{p}"] = (
            lambda db=db_rr: db.query(q, k=k, bucketize=False), "dot", p)
        paths[f"bucket_fused_l2_np{p}"] = (
            lambda db=db_l2: db.query(q, k=k, bucketize=False), "l2", p)
        paths[f"jnp_gather_np{p}"] = (
            _gather_baseline(db, q, k, min(p, db.index.centroids.shape[0])),
            "dot", p)
    p_ad = nprobes[-1]
    db_ad = VectorDB("ivf_pq", nprobe=p_ad, adaptive_nprobe=0.3,
                     **kw).load(corpus)
    paths[f"bucket_adaptive_np{p_ad}"] = (
        lambda: db_ad.query(q, k=k, bucketize=False), "dot", p_ad)
    # the high-sharing configuration the CI blocked gate reads: a large
    # batch (q_hs, 512 queries even in --quick) at a deep nprobe, where
    # each probed block serves many query groups and the shared DMA +
    # MXU contraction amortizes the segmented-schedule overhead. Fixed at
    # Q=512/nprobe=16 so quick and full runs gate the same shape.
    p_hs = 16
    db_hs_pq = VectorDB("ivf_pq", nprobe=p_hs, adc_mode="per_query",
                        **kw).load(corpus)
    db_hs_bl = VectorDB("ivf_pq", nprobe=p_hs, adc_mode="blocked",
                        **kw).load(corpus)
    db_hs_rr = VectorDB("ivf_pq", nprobe=p_hs, adc_mode="run_resident",
                        **kw).load(corpus)
    db_hs_auto = VectorDB("ivf_pq", nprobe=p_hs, adc_mode="auto",
                          **kw).load(corpus)
    paths["bucket_perquery_hs"] = (
        lambda: db_hs_pq.query(q_hs, k=k, bucketize=False), "dot", p_hs)
    paths["bucket_blocked_hs"] = (
        lambda: db_hs_bl.query(q_hs, k=k, bucketize=False), "dot", p_hs)
    paths["bucket_runres_hs"] = (
        lambda: db_hs_rr.query(q_hs, k=k, bucketize=False), "dot", p_hs)
    # steady-state measured-autotuner dispatch at the same shape: reset
    # the process ledger, then drive the whole probe phase to completion
    # BEFORE the timed reps so the row measures the ledger lookup, not the
    # probes (each probe batch still served a bit-identical answer)
    LEDGER.reset()
    for _ in range(len(LEDGER.candidates) * LEDGER.reps + 1):
        jax.block_until_ready(db_hs_auto.query(q_hs, k=k, bucketize=False))
    assert db_hs_auto.adc_stats["crossover"] is not None, \
        "autotuner probe phase did not converge before timing"
    paths["bucket_auto_hs"] = (
        lambda: db_hs_auto.query(q_hs, k=k, bucketize=False), "dot", p_hs)
    scan_db = VectorDB("ivf_pq", nprobe=nprobes[0], scan_all=True,
                       **kw).load(corpus)
    paths["all_codes_scan"] = (
        lambda: scan_db.query(q, k=k, bucketize=False), "dot", 0)
    for dt in ("bfloat16", "int8"):  # LUT ladder at the middle nprobe
        db = VectorDB("ivf_pq", nprobe=8, lut_dtype=dt, **kw).load(corpus)
        paths[f"bucket_fused_np8_{dt}"] = (
            lambda db=db: db.query(q, k=k, bucketize=False), "dot", 8)
    for dt in ("float32", "int8"):  # the served engines the CI gate reads
        db = VectorDB("ivf_pq", metric="cosine", m=m, nprobe=32, refine=128,
                      lut_dtype=dt).load(corpus)
        name = f"engine_ivf_pq_{'f32' if dt == 'float32' else dt}"
        paths[name] = (lambda db=db: db.query(q, k=k), "cosine", 32)

    for fn, _, _ in paths.values():
        jax.block_until_ready(fn())  # compile
    walls = {name: float("inf") for name in paths}
    for _ in range(15):  # interleaved min-of-reps (see pq_adc_paths)
        for name, (fn, _, _) in paths.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls[name] = min(walls[name], time.perf_counter() - t0)
    rows = [{"path": name, "metric": metric, "nprobe": p, "N": N,
             "qps": (512 if name.endswith("_hs") else n_queries)
             / walls[name],
             "recall_at_10": recall(fn()[1])}
            for name, (fn, metric, p) in paths.items()]

    scan = next(r for r in rows if r["path"] == "all_codes_scan")
    for p in nprobes:
        b = next(r for r in rows if r["path"] == f"bucket_fused_np{p}")
        bl = next(r for r in rows if r["path"] == f"bucket_blocked_np{p}")
        g = next(r for r in rows if r["path"] == f"jnp_gather_np{p}")
        rows.append({"path": f"speedup_bucket_vs_scan_np{p}", "metric": "dot",
                     "nprobe": p, "N": N, "qps": b["qps"] / scan["qps"],
                     "recall_at_10": b["recall_at_10"] - scan["recall_at_10"]})
        rows.append({"path": f"speedup_bucket_vs_gather_np{p}",
                     "metric": "dot", "nprobe": p, "N": N,
                     "qps": b["qps"] / g["qps"],
                     "recall_at_10": b["recall_at_10"] - g["recall_at_10"]})
        # the PR-8 tentpole gate: blocked grid vs the per-query grid on
        # identical visit tables — qps holds the ratio, recall the delta
        rows.append({"path": f"speedup_blocked_vs_perquery_np{p}",
                     "metric": "dot", "nprobe": p, "N": N,
                     "qps": bl["qps"] / b["qps"],
                     "recall_at_10": bl["recall_at_10"] - b["recall_at_10"]})
        rr = next(r for r in rows
                  if r["path"] == f"bucket_runres_np{p}")
        rows.append({"path": f"speedup_runres_vs_perquery_np{p}",
                     "metric": "dot", "nprobe": p, "N": N,
                     "qps": rr["qps"] / b["qps"],
                     "recall_at_10": rr["recall_at_10"] - b["recall_at_10"]})
        # exact-match parity between the grids: qps = fraction of
        # identical ids, recall_at_10 = fraction of bit-identical scores
        # (both must be 1.0 — CI gates on it)
        sp, ip = paths[f"bucket_fused_np{p}"][0]()
        sb, ib = paths[f"bucket_blocked_np{p}"][0]()
        sr, ir = paths[f"bucket_runres_np{p}"][0]()
        rows.append({"path": f"parity_blocked_vs_perquery_np{p}",
                     "metric": "dot", "nprobe": p, "N": N,
                     "qps": float(np.mean(np.asarray(ip) == np.asarray(ib))),
                     "recall_at_10": float(np.mean(
                         np.asarray(sp) == np.asarray(sb)))})
        rows.append({"path": f"parity_runres_vs_perquery_np{p}",
                     "metric": "dot", "nprobe": p, "N": N,
                     "qps": float(np.mean(np.asarray(ip) == np.asarray(ir))),
                     "recall_at_10": float(np.mean(
                         np.asarray(sp) == np.asarray(sr)))})
    hp = next(r for r in rows if r["path"] == "bucket_perquery_hs")
    hb = next(r for r in rows if r["path"] == "bucket_blocked_hs")
    rows.append({"path": "speedup_blocked_vs_perquery_hs", "metric": "dot",
                 "nprobe": 8, "N": N, "qps": hb["qps"] / hp["qps"],
                 "recall_at_10": hb["recall_at_10"] - hp["recall_at_10"]})
    sp, ip = paths["bucket_perquery_hs"][0]()
    sb, ib = paths["bucket_blocked_hs"][0]()
    rows.append({"path": "parity_blocked_vs_perquery_hs", "metric": "dot",
                 "nprobe": 8, "N": N,
                 "qps": float(np.mean(np.asarray(ip) == np.asarray(ib))),
                 "recall_at_10": float(np.mean(
                     np.asarray(sp) == np.asarray(sb)))})
    hr = next(r for r in rows if r["path"] == "bucket_runres_hs")
    rows.append({"path": "speedup_runres_vs_perquery_hs", "metric": "dot",
                 "nprobe": p_hs, "N": N, "qps": hr["qps"] / hp["qps"],
                 "recall_at_10": hr["recall_at_10"] - hp["recall_at_10"]})
    # the PR-9 tentpole gate: one-fetch-per-block vs the PR-8 grid at the
    # shape built to favor grouping — CI gates qps >= 1.0
    rows.append({"path": "speedup_runres_vs_blocked_hs", "metric": "dot",
                 "nprobe": p_hs, "N": N, "qps": hr["qps"] / hb["qps"],
                 "recall_at_10": hr["recall_at_10"] - hb["recall_at_10"]})
    sr, ir = paths["bucket_runres_hs"][0]()
    rows.append({"path": "parity_runres_vs_perquery_hs", "metric": "dot",
                 "nprobe": p_hs, "N": N,
                 "qps": float(np.mean(np.asarray(ip) == np.asarray(ir))),
                 "recall_at_10": float(np.mean(
                     np.asarray(sp) == np.asarray(sr)))})
    sa, ia = paths["bucket_auto_hs"][0]()
    rows.append({"path": "parity_auto_vs_perquery_hs", "metric": "dot",
                 "nprobe": p_hs, "N": N,
                 "qps": float(np.mean(np.asarray(ip) == np.asarray(ia))),
                 "recall_at_10": float(np.mean(
                     np.asarray(sp) == np.asarray(sa)))})
    # export the fitted ledger entry the auto row dispatched on: metric =
    # chosen grouped grid, nprobe = chosen qblk, qps = crossover sharing,
    # recall_at_10 = median probed sharing; the full dict rides along for
    # the CI autotune artifact
    for key_str, dec in LEDGER.decisions().items():
        rows.append({"path": "autotune_decision", "metric":
                     dec["grouped_mode"], "nprobe": dec["qblk"], "N": N,
                     "qps": dec["crossover"],
                     "recall_at_10": dec["sharing"],
                     "ledger_key": key_str, "decision": dec})
    return rows


def mutation_paths(N: int = 10_000, d: int = 64, n_queries: int = 256,
                   k: int = 10, m: int = 8, seed: int = 0):
    """The streaming-ingestion scenario the mutation lifecycle opens:

      * sustained insert QPS — amortized (capacity pre-reserved, every
        batch appends into existing buckets) vs spill-heavy (no reserve:
        the stream keeps overflowing capacity buckets and growing spp),
      * query QPS at 0 / 10 / 30% tombstones (deleted slots ride through
        the fused kernel as pad — the probed work does NOT shrink until
        compaction), then compact() cost and the post-compact query rate,
      * interleaved serving: QueryEngine absorbing writes and reads 1:8
        under the read-your-writes pump,
      * recall@10 after 20% churn (delete 20%, insert 20% new) vs a
        REBUILT-from-scratch index on the same live corpus — the CI gate:
        churned recall must stay >= 0.95x rebuilt (frozen
        centroids/codebooks never saw the inserted rows).
    """
    from repro.serve import QueryEngine

    rng = np.random.default_rng(seed)
    n_clusters = max(8, N // 100)
    # one pool, one set of cluster centers: the insert stream is drawn from
    # the SAME distribution the codebooks trained on (steady-state churn;
    # distribution SHIFT is the retrain trigger pq.stale_fraction flags)
    pool = _clustered(rng, 2 * N, d, n_clusters)
    corpus, extra = pool[:N], pool[N:]
    q = _clustered(rng, n_queries, d, n_clusters)
    kw = dict(metric="cosine", m=m, refine=0, compact_threshold=None)
    rows = []

    # ---- sustained insert QPS: amortized vs spill-heavy
    half, batch = N // 2, 50
    for label, pre_reserve in (("amortized", True), ("spill_heavy", False)):
        db = VectorDB("ivf_pq", **kw).load(corpus[:half])
        if pre_reserve:
            db.reserve(half + batch, 8)
        # compile this db's encode-path shapes outside the timer (the
        # eager centroid ops key on C, so a shared warm db won't do)
        db.insert(extra[:batch])
        t0 = time.perf_counter()
        for s0 in range(half, N, batch):
            db.insert(corpus[s0:s0 + batch])
        dt = time.perf_counter() - t0
        rows.append({"path": f"insert_qps_{label}", "N": N,
                     "rows_per_s": (N - half) / dt,
                     "plan_generation": db.plan_generation})

    # ---- query QPS vs tombstone fraction, then compact cost
    db = VectorDB("ivf_pq", nprobe=8, **kw).load(corpus)
    order = rng.permutation(N)
    deleted = 0
    for frac in (0.0, 0.1, 0.3):
        want = int(N * frac)
        if want > deleted:
            db.delete(order[deleted:want])
            deleted = want
        fn = lambda: db.query(q, k=k, bucketize=False)
        jax.block_until_ready(fn())  # compile + sync
        wall = float("inf")
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            wall = min(wall, time.perf_counter() - t0)
        rows.append({"path": f"query_qps_tomb{int(frac * 100)}", "N": N,
                     "qps": n_queries / wall,
                     "tombstone_fraction": db.index.layout.tombstone_fraction})
    t0 = time.perf_counter()
    db.compact()
    compact_s = time.perf_counter() - t0
    jax.block_until_ready(db.query(q, k=k, bucketize=False))  # re-sync
    wall = float("inf")
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(db.query(q, k=k, bucketize=False))
        wall = min(wall, time.perf_counter() - t0)
    rows.append({"path": "compact", "N": N, "compact_s": compact_s,
                 "qps_after": n_queries / wall})

    # ---- interleaved serving, writes:reads 1:8
    db = VectorDB("ivf_pq", nprobe=8, **kw).load(corpus)
    db.reserve(2048, 8)
    eng = QueryEngine(db, max_batch=8, max_wait_ms=0.0)
    t0 = time.perf_counter()
    served = 0
    for i in range(64):
        eng.submit_write("insert", extra[i * 8:(i + 1) * 8])
        for j in range(8):
            eng.submit(q[(i * 8 + j) % n_queries], k=k)
        served += eng.pump(force=True)
    served += eng.drain()
    dt = time.perf_counter() - t0
    st = eng.latency_stats()
    rows.append({"path": "interleaved_1to8", "N": N,
                 "reads_per_s": served / dt,
                 "write_rows_per_s": st["write_inserts"] / dt,
                 "p50_ms": st["p50_ms"],
                 "plan_misses": st["plan_misses"]})

    # ---- 20% churn recall vs rebuilt-from-scratch oracle (the CI gate)
    gate_kw = dict(metric="cosine", m=m, nprobe=32, refine=128)
    db = VectorDB("ivf_pq", **gate_kw).load(corpus)
    churn = int(0.2 * N)
    db.delete(order[:churn])
    new_ids = db.insert(extra[:churn])
    live = np.concatenate([order[churn:], new_ids])
    live_rows = np.concatenate([corpus[order[churn:]], extra[:churn]])
    exact = VectorDB("flat", metric="cosine").load(live_rows)
    _, eidx = exact.query(q, k=k, bucketize=False)
    eids = live[np.asarray(eidx)]  # exact ids in the churned id space
    rebuilt = VectorDB("ivf_pq", **gate_kw).load(live_rows)

    def recall(ids, ref):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[i]) & set(ref[i])) / k
                              for i in range(n_queries)]))

    r_churn = recall(db.query(q, k=k, bucketize=False)[1], eids)
    r_rebuilt = recall(rebuilt.query(q, k=k, bucketize=False)[1],
                       np.asarray(eidx))
    rows.append({"path": "recall_churn20", "N": N, "recall_at_10": r_churn,
                 "recall_rebuilt": r_rebuilt,
                 "ratio_vs_rebuilt": r_churn / max(r_rebuilt, 1e-9)})
    return rows


def wal_paths(n_writes: int = 400, wal_lengths=(200, 1000), N: int = 4096,
              d: int = 64, seed: int = 0):
    """The durability lifecycle: what the WAL costs and how fast it recovers.

      * raw log append throughput, fsync-per-record vs group commit — the
        durability layer alone, no engine apply, so the ratio isolates the
        fsync policy (the committed full-size criterion: group commit
        >= 5x fsync-per-record),
      * end-to-end write QPS through the async front for the three
        durability arms — no WAL / fsync-per-record / group commit — same
        engine, same 1-row insert stream, acks held until the covering
        fsync: what durability costs a serving stack whose walltime also
        contains the engine apply,
      * recovery walltime vs WAL length: restore = snapshot load + L-record
        tail replay through the mutation API,
      * recovery_smoke — crash mid-ingest at ``wal.append.post``, recover,
        and compare top-10 ids against an uncrashed twin that applied
        exactly the surviving prefix: parity must be 1.0 (the CI recovery
        gate; recovery is bit-for-bit, not best-effort).
    """
    import shutil
    import tempfile

    from repro.core.wal import WriteAheadLog
    from repro.ft.faults import SimulatedCrash, inject_crashes
    from repro.serve import AsyncQueryEngine

    rng = np.random.default_rng(seed)
    n_clusters = max(8, N // 128)
    corpus = _clustered(rng, N, d, n_clusters)
    stream = _clustered(rng, max(n_writes + 1, max(wal_lengths), 64), d,
                        n_clusters)
    kw = dict(metric="cosine", m=8, nprobe=8, refine=0,
              compact_threshold=None)
    root = tempfile.mkdtemp(prefix="bench_wal")
    rows = []
    try:
        base = os.path.join(root, "base")
        VectorDB("ivf_pq", **kw).load(corpus).save_index(base, step=0)

        # ---- raw log appends: the fsync policy in isolation (the
        # committed >= 5x group-commit criterion reads these two rows)
        raw_n = max(200, n_writes)
        row1 = stream[:1]
        raw_qps = {}
        for arm, interval in (("wal_append_fsync_each", 0.0),
                              ("wal_append_group_commit", 5.0)):
            wal, _ = WriteAheadLog.open(os.path.join(root, f"{arm}.log"),
                                        fsync_interval_ms=interval)
            t0 = time.perf_counter()
            for i in range(raw_n):
                wal.append("insert", row1, np.array([i]))
            wal.sync()
            raw_qps[arm] = raw_n / (time.perf_counter() - t0)
            rows.append({"path": arm, "n_writes": raw_n,
                         "writes_per_s": raw_qps[arm],
                         "wal_records": wal.appends,
                         "wal_fsyncs": wal.fsyncs})
            wal.close()
        rows.append({"path": "speedup_group_commit_vs_fsync_each",
                     "n_writes": raw_n,
                     "writes_per_s": raw_qps["wal_append_group_commit"]
                     / raw_qps["wal_append_fsync_each"]})

        def fresh(arm, durable, interval):
            work = os.path.join(root, arm)
            shutil.copytree(base, work)
            db = VectorDB("ivf_pq", **kw).restore_index(
                work, durable=durable, fsync_interval_ms=interval)
            db.reserve(n_writes + 64, 8)  # keep the append path amortized
            return db

        # ---- write QPS per durability arm
        qps = {}
        for arm, durable, interval in (("wal_off", False, 0.0),
                                       ("wal_fsync_each", True, 0.0),
                                       ("wal_group_commit", True, 5.0)):
            db = fresh(arm, durable, interval)
            eng_kw = {"fsync_interval_ms": interval} if durable else {}
            with AsyncQueryEngine(db, max_batch=64, max_wait_ms=0.5,
                                  **eng_kw) as eng:
                eng.submit_write("insert", stream[:1]).result(timeout=60)
                t0 = time.perf_counter()
                futs = [eng.submit_write("insert", stream[i:i + 1])
                        for i in range(1, n_writes + 1)]
                for f in futs:
                    f.result(timeout=300)
                qps[arm] = n_writes / (time.perf_counter() - t0)
            st = db.wal_stats or {}
            rows.append({"path": arm, "n_writes": n_writes,
                         "writes_per_s": qps[arm],
                         "wal_records": int(st.get("records", 0)),
                         "wal_fsyncs": int(st.get("fsyncs", 0))})
            if db.wal is not None:
                db.wal.close()

        # ---- recovery walltime vs WAL tail length
        for L in wal_lengths:
            work = os.path.join(root, f"recover_{L}")
            shutil.copytree(base, work)
            db = VectorDB("ivf_pq", **kw).restore_index(
                work, durable=True, fsync_interval_ms=50.0)
            db.reserve(L + 64, 8)
            for i in range(L):
                db.insert(stream[i:i + 1])
            db.wal.close()
            t0 = time.perf_counter()
            db2 = VectorDB("ivf_pq", **kw).restore_index(work, durable=True)
            dt = time.perf_counter() - t0
            assert db2.wal.recovered_records == L, db2.wal.stats
            rows.append({"path": f"recovery_wal{L}", "wal_records": L,
                         "recovery_s": dt, "replays_per_s": L / dt})
            db2.wal.close()

        # ---- crash mid-ingest, recover, bit-for-bit parity (the CI gate)
        work = os.path.join(root, "smoke")
        shutil.copytree(base, work)
        db = VectorDB("ivf_pq", **kw).restore_index(work, durable=True)
        n_batches, crash_at = 16, 9
        with inject_crashes("wal.append.post", hits=crash_at):
            try:
                for i in range(n_batches):
                    db.insert(stream[i * 4:(i + 1) * 4])
            except SimulatedCrash:
                pass
        recovered = VectorDB("ivf_pq", **kw).restore_index(work, durable=True)
        twin = VectorDB("ivf_pq", **kw).restore_index(base)
        for i in range(crash_at):  # append.post: the crashing record is on disk
            twin.insert(stream[i * 4:(i + 1) * 4])
        q = _clustered(rng, 64, d, n_clusters)
        parity = float(np.mean(np.asarray(recovered.query(q, k=10)[1])
                               == np.asarray(twin.query(q, k=10)[1])))
        rows.append({"path": "recovery_smoke",
                     "crashpoint": "wal.append.post",
                     "wal_records": int(recovered.wal.stats["replayed"]),
                     "parity": parity})
        recovered.wal.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return rows


def filtered_paths(N: int = 10_000, d: int = 64, n_queries: int = 256,
                   k: int = 10, m: int = 8, hybrid_passages: int = 400,
                   seed: int = 0):
    """Filtered + hybrid search (PR-10): what a metadata predicate costs
    and what BM25 fusion buys.

      * ``filtered_parity_sel{1,10,50}`` — the CI gate rows: on a
        full-coverage ivf_pq (nprobe = n_clusters, refine=0) the filtered
        top-k must EXACTLY equal the engine's own unfiltered full ranking
        post-filtered on the host (``qps`` = identical-id fraction,
        ``recall_at_10`` = bit-identical-score fraction; both must be 1.0
        — invariant 6: a filter is a mask change, not a scoring change);
        ``filtered_parity_alltrue`` pins the all-true bitmap bit-identical
        to no filter at all,
      * ``filtered_qps_sel{1,10,50}`` vs ``filtered_qps_unfiltered`` —
        throughput of the served nprobe=8 engine as the predicate narrows
        (the selectivity-aware nprobe boost is in play on the filtered
        rows), with recall@10 against the exact FILTERED oracle — a flat
        engine under the same predicate (min of 15 interleaved reps),
      * ``hybrid_mrr`` — dense-only vs BM25-only vs fused (alpha=0.5) MRR
        on MarcoLike with deliberately degraded dense queries (jittered
        bag-of-words encoder + word-noised query texts): lexical evidence
        must recover rank, so the CI gate is mrr_hybrid >= mrr_dense.
    """
    from repro.data.marco import simple_tokenizer
    from repro.search import Eq, Range

    rng = np.random.default_rng(seed)
    rows = []

    def post_filter(scores, ids, allowed, kk):
        # host oracle: keep the engine's own ranking order, drop rows the
        # bitmap rejects (stable — lax.top_k ties break by position)
        out_s = np.full((ids.shape[0], kk), -np.inf, np.float32)
        out_i = np.full((ids.shape[0], kk), -1, np.int32)
        for r in range(ids.shape[0]):
            keep = [(s, i) for s, i in zip(scores[r], ids[r])
                    if i >= 0 and allowed[i]][:kk]
            for c, (s, i) in enumerate(keep):
                out_s[r, c] = s
                out_i[r, c] = i
        return out_s, out_i

    # ---- exact-parity gate: fixed small corpus (functional, not perf)
    Np, Qp = 2000, 32
    n_cl_p = max(8, Np // 100)
    corpus_p = _clustered(rng, Np, d, n_cl_p)
    meta_p = {"tag": (np.arange(Np) % 100).tolist()}
    db_gate = VectorDB("ivf_pq", metric="cosine", m=m, n_clusters=n_cl_p,
                       nprobe=n_cl_p, refine=0).load(corpus_p, meta=meta_p)
    qp = corpus_p[:Qp] + 0.01
    full_s, full_i = map(np.asarray,
                         db_gate.query(qp, k=Np, bucketize=False))
    sels = [("sel1", Eq("tag", 7)), ("sel10", Range("tag", hi=9)),
            ("sel50", Range("tag", hi=49))]
    for label, pred in sels:
        allowed = db_gate.metastore.mask(pred, Np)
        want_s, want_i = post_filter(full_s, full_i, allowed, k)
        got_s, got_i = map(np.asarray,
                           db_gate.query(qp, k=k, bucketize=False,
                                         where=pred))
        rows.append({"path": f"filtered_parity_{label}", "N": Np,
                     "selectivity": float(allowed.mean()),
                     "qps": float(np.mean(got_i == want_i)),
                     "recall_at_10": float(np.mean(got_s == want_s))})
    s0, i0 = map(np.asarray, db_gate.query(qp, k=k, bucketize=False))
    s1, i1 = map(np.asarray,
                 db_gate.query(qp, k=k, bucketize=False,
                               where=Range("tag", lo=0)))
    rows.append({"path": "filtered_parity_alltrue", "N": Np,
                 "selectivity": 1.0,
                 "qps": float(np.mean(i0 == i1)),
                 "recall_at_10": float(np.mean(s0 == s1))})

    # ---- filtered QPS + recall on the served engine
    n_clusters = max(8, N // 100)
    corpus = _clustered(rng, N, d, n_clusters)
    meta = {"tag": (np.arange(N) % 100).tolist()}
    q = _clustered(rng, n_queries, d, n_clusters)
    db = VectorDB("ivf_pq", metric="cosine", m=m, nprobe=8,
                  refine=0).load(corpus, meta=meta)
    exact = VectorDB("flat", metric="cosine").load(corpus, meta=meta)

    def recall_vs(ids, ref):
        ids, ref = np.asarray(ids), np.asarray(ref)
        per = []
        for r in range(ids.shape[0]):
            want = set(int(x) for x in ref[r] if x >= 0)
            if want:
                got = set(int(x) for x in ids[r] if x >= 0)
                per.append(len(got & want) / len(want))
        return float(np.mean(per))

    paths = {"filtered_qps_unfiltered":
             (lambda: db.query(q, k=k, bucketize=False), None)}
    for label, pred in sels:
        paths[f"filtered_qps_{label}"] = (
            lambda pred=pred: db.query(q, k=k, bucketize=False,
                                       where=pred), pred)
    for fn, _ in paths.values():
        jax.block_until_ready(fn())  # compile (incl. the boosted nprobe)
    walls = {name: float("inf") for name in paths}
    for _ in range(15):  # interleaved min-of-reps (see pq_adc_paths)
        for name, (fn, _) in paths.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls[name] = min(walls[name], time.perf_counter() - t0)
    for name, (fn, pred) in paths.items():
        ref = np.asarray(exact.query(q, k=k, bucketize=False,
                                     **({"where": pred} if pred else {}))[1])
        sel = (float(db.metastore.mask(pred, N).mean()) if pred is not None
               else 1.0)
        rows.append({"path": name, "N": N, "selectivity": sel,
                     "qps": n_queries / walls[name],
                     "recall_at_10": recall_vs(fn()[1], ref)})

    # ---- hybrid fusion MRR on degraded dense queries (the CI gate)
    mk = MarcoLike(n_passages=hybrid_passages, seed=2)
    rng_h = np.random.default_rng(7)
    d_h = 24
    proj = rng_h.normal(size=(mk.vocab_size, d_h)).astype(np.float32) / 5.0
    jitter = rng_h.normal(size=(hybrid_passages, d_h)).astype(np.float32) * 2.0

    def enc_bow(texts, jit=None):
        out = np.zeros((len(texts), d_h), np.float32)
        for r, t in enumerate(texts):
            toks = simple_tokenizer(t, mk.vocab_size, 64)
            out[r] = proj[toks[toks >= 2]].sum(0)
        return out if jit is None else out + jit

    texts = mk.passage_texts()
    hdb = VectorDB("flat", metric="cosine").load(enc_bow(texts))
    hdb.enable_lexical(texts=texts)
    qt = mk.query_texts(noise=0.5)
    qv = enc_bow(qt, jitter)  # deliberately degraded dense queries

    def mrr(ids):
        out = 0.0
        for r, row in enumerate(np.asarray(ids)):
            hit = np.where(row == r)[0]
            if hit.size:
                out += 1.0 / (hit[0] + 1)
        return out / len(ids)

    arms = {
        "dense": lambda: hdb.query(qv, k=k),
        "hybrid": lambda: hdb.query(qv, k=k, hybrid=0.5, hybrid_texts=qt),
        "lex": lambda: hdb.query(qv, k=k, hybrid=0.0, hybrid_texts=qt),
    }
    for fn in arms.values():
        jax.block_until_ready(fn())  # compile
    hwalls = {name: float("inf") for name in arms}
    for _ in range(15):
        for name, fn in arms.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            hwalls[name] = min(hwalls[name], time.perf_counter() - t0)
    mrrs = {name: mrr(fn()[1]) for name, fn in arms.items()}
    rows.append({"path": "hybrid_mrr", "N": hybrid_passages, "alpha": 0.5,
                 "mrr_dense": mrrs["dense"], "mrr_hybrid": mrrs["hybrid"],
                 "mrr_lex": mrrs["lex"],
                 "qps_dense": hybrid_passages / hwalls["dense"],
                 "qps_hybrid": hybrid_passages / hwalls["hybrid"]})
    return rows


_DIST_PQ_SNIPPET = """
import json
import jax, numpy as np
from repro.core import DistributedPQ, VectorDB
mesh = jax.make_mesh(({shards},), ('data',))
rng = np.random.default_rng(0)
corpus = rng.normal(size=({N}, {d})).astype(np.float32)
q = corpus[:32] + 0.01 * rng.normal(size=(32, {d})).astype(np.float32)
dpq = DistributedPQ(mesh, metric='cosine', m=8).load(corpus)
ids = np.asarray(dpq.query(q, k=10)[1])
ref = np.asarray(VectorDB('pq', metric='cosine', refine=0)
                 .load(corpus).query(q, k=10, bucketize=False)[1])
overlap = float(np.mean([len(set(ids[i]) & set(ref[i])) / 10
                         for i in range(32)]))
print(json.dumps({{
    'shards': {shards}, 'N': {N}, 'd': {d},
    'per_device_bytes': dpq.per_device_bytes(),
    'f32_corpus_bytes': int(corpus.nbytes),
    'frac_of_replicated_f32': dpq.per_device_bytes() / corpus.nbytes,
    'overlap_vs_single_host_pq': overlap}}))
"""


def distributed_pq_memory(shards: int = 4, N: int = 4096, d: int = 64):
    """Per-device resident bytes of DistributedPQ vs the replicated f32
    corpus, on a forced {shards}-device host mesh (own process: jax pins the
    device count at first init). The child is held to the CPU: it counts
    bytes, and on a TPU host the parent already holds the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={shards}",
               PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _DIST_PQ_SNIPPET.format(shards=shards, N=N, d=d)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def attention_scaling(sizes=(256, 512, 1024)):
    from repro.models.attention import _chunked_attention, _dense_attention
    rows = []
    for S in sizes:
        q = jax.random.normal(jax.random.PRNGKey(0), (1, S, 2, 2, 64))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, S, 2, 64))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, S, 2, 64))
        dense = jax.jit(lambda q, k, v: _dense_attention(
            q, k, v, scale=0.125, causal=True, window=None, q_offset=0))
        chunk = jax.jit(lambda q, k, v: _chunked_attention(
            q, k, v, scale=0.125, causal=True, window=None, q_offset=0,
            q_chunk=128, k_chunk=128))
        rows.append({"seq": S, "dense_s": _timeit(dense, q, k, v),
                     "chunked_s": _timeit(chunk, q, k, v)})
    return rows


def main(quick: bool = False, json_path: str | None = None):
    results = {}
    print("name,key,value")
    results["encoder"] = encoder_throughput()
    for r in results["encoder"]:
        print(f"throughput,encoder_b{r['batch']}_tok_per_s,{r['tokens_per_s']:.1f}")
    s = insert_split(300 if quick else 1000)
    results["insert_split"] = s
    print(f"throughput,insert_embed_s,{s['embed_s']:.3f}")
    print(f"throughput,insert_index_s,{s['index_s']:.3f}")
    print(f"throughput,insert_embed_frac,{s['embed_frac']:.4f}")
    results["attention"] = attention_scaling((256, 512) if quick else
                                             (256, 512, 1024))
    for r in results["attention"]:
        print(f"throughput,attn_s{r['seq']}_dense_s,{r['dense_s']:.4f}")
        print(f"throughput,attn_s{r['seq']}_chunked_s,{r['chunked_s']:.4f}")
    results["pq_adc"] = pq_adc_paths(
        N=2000 if quick else 10_000, n_queries=64 if quick else 256)
    print("name,path,N,qps,recall_at_10")
    for r in results["pq_adc"]:
        print(f"pq_adc,{r['path']},{r['N']},{r['qps']:.1f},"
              f"{r['recall_at_10']:.4f}")
    results["ivf_adc"] = ivf_adc_paths(
        N=2000 if quick else 10_000, n_queries=64 if quick else 256,
        nprobes=(1, 8) if quick else (1, 4, 8, 32))
    print("name,path,metric,nprobe,N,qps,recall_at_10")
    for r in results["ivf_adc"]:
        print(f"ivf_adc,{r['path']},{r['metric']},{r['nprobe']},{r['N']},"
              f"{r['qps']:.1f},{r['recall_at_10']:.4f}")
    results["mutation"] = mutation_paths(
        N=2000 if quick else 10_000, n_queries=64 if quick else 256)
    print("name,path,N,fields")
    for r in results["mutation"]:
        extras = ",".join(f"{kk}={vv:.4f}" if isinstance(vv, float)
                          else f"{kk}={vv}" for kk, vv in r.items()
                          if kk not in ("path", "N"))
        print(f"mutation,{r['path']},{r['N']},{extras}")
    results["wal"] = wal_paths(
        n_writes=60 if quick else 400,
        wal_lengths=(30,) if quick else (200, 1000),
        N=1024 if quick else 4096)
    print("name,path,fields")
    for r in results["wal"]:
        extras = ",".join(f"{kk}={vv:.4f}" if isinstance(vv, float)
                          else f"{kk}={vv}" for kk, vv in r.items()
                          if kk != "path")
        print(f"wal,{r['path']},{extras}")
    results["filtered"] = filtered_paths(
        N=2000 if quick else 10_000, n_queries=64 if quick else 256,
        hybrid_passages=80 if quick else 400)
    print("name,path,fields")
    for r in results["filtered"]:
        extras = ",".join(f"{kk}={vv:.4f}" if isinstance(vv, float)
                          else f"{kk}={vv}" for kk, vv in r.items()
                          if kk != "path")
        print(f"filtered,{r['path']},{extras}")
    results["distributed_pq"] = distributed_pq_memory(
        shards=4, N=2048 if quick else 4096)
    dp = results["distributed_pq"]
    print(f"distributed_pq,per_device_bytes,{dp['per_device_bytes']}")
    print(f"distributed_pq,frac_of_replicated_f32,"
          f"{dp['frac_of_replicated_f32']:.4f}")
    print(f"distributed_pq,overlap_vs_single_host_pq,"
          f"{dp['overlap_vs_single_host_pq']:.4f}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
    return results


if __name__ == "__main__":
    main(json_path=sys.argv[sys.argv.index("--json") + 1]
         if "--json" in sys.argv else None)
