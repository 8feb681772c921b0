"""Quickstart: build a Thistle-style vector DB, load texts, query, compare
engines — the paper's core loop in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Where to go next: docs/ARCHITECTURE.md for the layer map,
docs/BENCHMARKS.md for every committed BENCH_*.json baseline and how to
reproduce it, examples/serve_vectordb.py for the serving fronts.
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import VectorDB
from repro.data import MarcoLike, simple_tokenizer


def bow_hash_encoder(texts, dim: int = 256):
    """text -> hashed bag-of-words embedding (swap in SBERT from
    examples/train_sbert.py for the neural path)."""
    toks = np.stack([simple_tokenizer(t, 30_000, 48) for t in texts])
    out = np.zeros((len(toks), dim), np.float32)
    rows = np.repeat(np.arange(len(toks)), toks.shape[1])
    cols = (toks.astype(np.int64) * 2654435761 % dim).reshape(-1)
    np.add.at(out, (rows, cols), (toks > 0).astype(np.float32).reshape(-1))
    return out / np.maximum(np.linalg.norm(out, axis=-1, keepdims=True), 1e-9)


def main():
    data = MarcoLike(n_passages=500, noise=0.15, seed=0)
    passages = data.passage_texts()
    queries = data.query_texts()

    def encoder(texts):
        return bow_hash_encoder(list(texts))

    print(f"corpus: {len(passages)} passages")
    for engine in ("flat", "ivf", "graph", "lsh", "int8", "pq", "ivf_pq"):
        db = VectorDB(engine, metric="cosine")
        db.load_texts(passages, encoder)
        scores, ids, hits = db.query_texts(queries[:200], encoder, k=3)
        acc = float(np.mean(np.asarray(ids)[:, 0] == np.arange(200)))
        print(f"  {engine:6s} top-1 accuracy on 200 queries: {acc:.3f}")

    # the compressed engine: m bytes/row + codebooks instead of the f32 corpus
    # (ksub=64 keeps codebook overhead small at this toy corpus size; the
    # ratio climbs with N since codes dominate codebooks at scale)
    db = VectorDB("ivf_pq", metric="cosine", m=8, ksub=64, nprobe=16)
    db.load_texts(passages, encoder)
    raw = 4 * db.index.d * len(passages)  # f32 corpus bytes
    print(f"\nivf_pq resident index: {db.index.memory_bytes()/1024:.0f} KiB "
          f"vs {raw/1024:.0f} KiB raw corpus "
          f"({raw/db.index.memory_bytes():.1f}x compression)")

    # the fused query hot path: PQ engines dispatch scoring through
    # repro.kernels.ops.adc_topk — the Pallas pq_adc kernel on TPU, a fused
    # jnp twin on CPU/GPU. use_kernel forces either backend (True runs the
    # kernel in interpret mode off-TPU — parity checks, not speed), and
    # lut_dtype="bfloat16" serves from bf16 score tables: ~2x MXU rate on
    # TPU at a bounded score error (see repro/kernels/pq_adc.py)
    db = VectorDB("pq", metric="cosine", m=8, ksub=64, lut_dtype="bfloat16")
    db.load_texts(passages, encoder)
    _, ids, _ = db.query_texts(queries[:200], encoder, k=3)
    acc = float(np.mean(np.asarray(ids)[:, 0] == np.arange(200)))
    print(f"pq (fused dispatch, bf16 LUTs) top-1: {acc:.3f}")

    # repeated queries reuse one compiled plan per (engine, bucket, k,
    # dtype): batches of 3, 4, and 3 all pad to bucket 4 — one compile
    # (miss), then hits; misses stay flat while hits grow
    for batch in (queries[:3], queries[3:7], queries[7:10]):
        db.query_texts(batch, encoder, k=3)
    print(f"query plans: {db.plan_stats}")

    # IVF-PQ's bucket-resident fused path: nprobe now genuinely prunes
    # scoring work on every metric and backend (the kernel gathers only the
    # probed buckets' block-aligned code lists), and lut_dtype="int8"
    # serves from absmax-quantized tables — 4x smaller than f32, per-
    # (query, subspace) scales, recall within the bf16 guard. Sweep nprobe
    # to trade recall for work, and read the serving engine's latency_stats
    # for p50/p99 plus the plan-cache counters.
    from repro.serve import QueryEngine
    q_emb = encoder(queries[:64])
    print("\nivf_pq int8-LUT nprobe sweep (top-1 acc / p50 ms):")
    for nprobe in (1, 4, 16):
        db = VectorDB("ivf_pq", metric="cosine", m=8, ksub=64,
                      nprobe=nprobe, lut_dtype="int8")
        db.load_texts(passages, encoder)
        eng = QueryEngine(db, max_batch=32, max_wait_ms=0.0)
        rids = [eng.submit(q_emb[i], k=3) for i in range(64)]
        eng.drain()
        ids = np.stack([eng.result(r)[1] for r in rids])
        acc = float(np.mean(ids[:, 0] == np.arange(64)))
        st = eng.latency_stats()
        print(f"  nprobe={nprobe:2d} acc={acc:.3f} p50={st['p50_ms']:.2f}ms "
              f"plans: {st['plan_hits']} hits / {st['plan_misses']} misses")

    # the MUTATION LIFECYCLE: a database, not a frozen index. Writes go
    # through the serving engine's queue (read-your-writes: a query
    # submitted after a write observes it), deletes are tombstones the
    # fused kernel scores as pad (zero kernel changes), compact() repacks
    # the block lists without changing compiled shapes, and a snapshot of
    # the mutated index round-trips exactly — tombstones stay deleted.
    import tempfile
    db = VectorDB("ivf_pq", metric="cosine", m=8, ksub=64, nprobe=16)
    db.load_texts(passages, encoder)
    eng = QueryEngine(db, max_batch=32, max_wait_ms=0.0)
    probe = encoder([passages[3]])[0]
    new_ids = db.insert(encoder(["a freshly ingested passage about topic 1"]))
    r1 = eng.submit(probe, k=3)
    eng.submit_write("delete", ids=[3])     # tombstone the true match...
    r2 = eng.submit(probe, k=3)             # ...this read must not see it
    eng.drain()
    top_before = int(eng.result(r1)[1][0])
    top_after = int(eng.result(r2)[1][0])
    db.upsert(encoder([passages[3]]), new_ids)  # re-point the new id at it
    db.compact()
    st = eng.latency_stats()
    print(f"\nmutation loop: top1 before delete={top_before} "
          f"after={top_after} (id 3 tombstoned)")
    print(f"  write counters: inserts={st['write_inserts']} "
          f"deletes={st['write_deletes']} "
          f"compactions={db.mutation_stats['compactions']} "
          f"generation={db.generation}")
    with tempfile.TemporaryDirectory() as tmp:
        db.save_index(tmp)                  # generation-stamped snapshot
        db2 = VectorDB("ivf_pq", metric="cosine", m=8, ksub=64,
                       nprobe=16).restore_index(tmp)
        s_a, i_a = db.query(probe[None], k=3)
        s_b, i_b = db2.query(probe[None], k=3)
        same = bool(np.array_equal(np.asarray(i_a), np.asarray(i_b)))
        print(f"  snapshot round-trip: live={db2.n} "
              f"generation={db2.generation} results identical={same}")

    db = VectorDB("flat", metric="cosine").load_texts(passages, encoder)
    q = queries[7]
    scores, ids, hits = db.query_texts([q], encoder, k=3)
    print(f"\nquery: {q[:60]}...")
    for s, h in zip(np.asarray(scores)[0], hits[0]):
        print(f"  {s:.3f}  {h[:60]}...")
    print("\nfull-size engine baselines: see docs/BENCHMARKS.md "
          "(BENCH_pq_adc / BENCH_ivf_adc / BENCH_mutation / "
          "BENCH_serve_async)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
