#!/usr/bin/env python3
"""Smoke test of Thistle's IVF-PQ serving path on a TPU.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --chips 4      # the sharded index on four chips
    python chip_smoke.py --rehearse     # tiny CPU rehearsal (never default)

One chip: a clustered corpus of 1,000,000 x 768 f32 rows (the width of the
paper's passage embeddings) is generated on the device from ``--seed`` and
loaded into ``VectorDB("ivf_pq", m=96, ksub=256, nprobe=16, refine=32)``,
which keeps the normalized rows on the device (about 3.1 GB) for the exact
re-rank. A few hundred queries go through ``AsyncQueryEngine.submit`` and
every future's result is checked against an exact f32 scan (recall@10).
One batch then runs on each ``ivf_adc`` grid (per_query, blocked,
run_resident) with the Pallas kernel backend and is compared with the jnp
twin of the same grid: ids must agree and scores match to f32 rounding.

Four chips: a 4,000,000 x 768 corpus is generated row-sharded over a
four-chip mesh and served compressed-only (m=96) by ``DistributedIVFPQ``;
its answers are compared with a one-device ``VectorDB("ivf_pq", refine=0)``
built over the same rows. Only that path runs.

Earlier lines report sizes, timings, recall and grids. The last line of
stdout is ``{"ok": true, "device": {...}}``, and the exit code is 0, only
when every phase passed. Without a TPU the script exits 2, unless
``--rehearse`` asks for the tiny CPU run (interpret-mode kernels).
``JAX_COMPILATION_CACHE_DIR`` places the compile cache; unset, it lives in
``.jax_cache/`` inside the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

D = 768           # passage-embedding width (SBERT-base, the paper's encoder)
M, KSUB = 96, 256  # 8 dims per PQ subspace, one byte per code
NPROBE, REFINE, K = 16, 32, 10
PER_SUB = 20      # rows per sub-cluster: a query's top-10 lie inside one
NOISE = 0.15      # row noise, against 0.5 sub-centre and 1.0 centre spread
# recall@10 the served path must reach against the exact scan. The CPU
# rehearsal (16,384 rows, same generator and engine settings) measures
# 1.0; the floor leaves room for the larger corpus's coarser probe share
# (16 of ~1000 lists instead of 16 of 128).
RECALL_FLOOR = 0.95
SIZES = {  # (rows, served queries) per run kind
    "one": (1_000_000, 256), "four": (4_000_000, 256),
    "rehearse_one": (16_384, 128), "rehearse_four": (32_768, 64)}


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run with interpret-mode kernels")
    return ap.parse_args()


def main() -> int:
    args = parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       " --xla_force_host_platform_device_count=4")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (found {dev.platform}); "
              "--rehearse runs the tiny CPU version", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {len(jax.devices())}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock(jax)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" | jax {jax.__version__} | compile cache {cache}", flush=True)
    kind = ("rehearse_" if args.rehearse else "") + (
        "one" if args.chips == 1 else "four")
    n, n_queries = SIZES[kind]
    run = one_chip if args.chips == 1 else four_chips
    run(jax, n, n_queries, args.seed, args.rehearse, clock)
    devs = jax.devices()[:args.chips]
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    only its read) and counts cache hits and misses."""

    def __init__(self, jax):
        from jax import monitoring
        self.seconds, self.hits, self.misses = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compile {self.seconds:.1f}s (cache hits {self.hits}, "
                f"misses {self.misses})")


# ------------------------------------------------------------------ data
def make_corpus(jax, seed: int, n: int, devices):
    """Clustered rows, made on the devices that will hold them: n // 1000
    centres, n / PER_SUB sub-centres around them, PER_SUB noisy rows per
    sub-centre (row i belongs to sub-centre i mod n_sub). Row-sharded over
    ``devices`` when there are several. -> (corpus, sub-centres on
    devices[0])."""
    import jax.numpy as jnp
    import numpy as np

    n_sub = max(1, n // PER_SUB)
    n_top = max(8, n // 1000)
    key = jax.random.PRNGKey(seed)

    @jax.jit
    def centres(key):
        k1, k2 = jax.random.split(key)
        top = jax.random.normal(k1, (n_top, D), jnp.float32)
        return (jnp.take(top, jnp.arange(n_sub) % n_top, axis=0)
                + 0.5 * jax.random.normal(k2, (n_sub, D), jnp.float32))

    per_dev = n // len(devices)
    gen = min(62_500, per_dev)
    assert per_dev % gen == 0 and n % len(devices) == 0, (n, gen)

    @jax.jit
    def rows(key, sub, start):
        ids = start + jnp.arange(gen, dtype=jnp.int32)
        noise = jax.random.normal(jax.random.fold_in(key, start), (gen, D))
        return jnp.take(sub, ids % n_sub, axis=0) + NOISE * noise

    shards = []
    for i, d in enumerate(devices):
        sub = jax.device_put(centres(key), d)
        parts = [rows(jax.random.fold_in(key, 1), sub, s)
                 for s in range(i * per_dev, (i + 1) * per_dev, gen)]
        shards.append(jnp.concatenate(parts) if len(parts) > 1 else parts[0])
        del parts
    if len(devices) == 1:
        x = shards[0]
    else:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devices), ("shard",))
        x = jax.make_array_from_single_device_arrays(
            (n, D), NamedSharding(mesh, P("shard", None)), shards)
    x.block_until_ready()
    return x, jax.device_put(centres(key), devices[0])


def make_queries(jax, seed: int, sub, n_queries: int, same_sub: bool):
    """Fresh rows of the corpus distribution: around random sub-centres,
    or (``same_sub``) all around one — a batch whose probes share blocks."""
    import jax.numpy as jnp

    kq, kn = jax.random.split(jax.random.PRNGKey(seed + 1000))
    if same_sub:
        s = jnp.full((n_queries,), 7, jnp.int32)
    else:
        s = jax.random.randint(kq, (n_queries,), 0, sub.shape[0])
    noise = jax.random.normal(kn, (n_queries, D), jnp.float32)
    return jnp.take(sub, s, axis=0) + NOISE * noise


def exact_topk(jax, x, q, k: int):
    """The plain reference: cosine top-k by a full f32 scan at HIGHEST
    matmul precision, streamed over row chunks."""
    import jax.numpy as jnp

    @jax.jit
    def chunk_topk(rows, qn, base):
        rn = rows / jnp.linalg.norm(rows, axis=1, keepdims=True)
        s = jnp.dot(qn, rn.T, precision=jax.lax.Precision.HIGHEST)
        s, i = jax.lax.top_k(s, k)
        return s, i + base

    qn = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    step = min(62_500, x.shape[0])
    best_s = best_i = None
    for start in range(0, x.shape[0], step):
        s, i = chunk_topk(x[start:start + step], qn, start)
        if best_s is not None:
            s = jnp.concatenate([best_s, s], axis=1)
            i = jnp.concatenate([best_i, i], axis=1)
            s, pos = jax.lax.top_k(s, k)
            i = jnp.take_along_axis(i, pos, axis=1)
        best_s, best_i = s, i
    return best_s, best_i


# ---------------------------------------------------------------- checks
class PhaseFailed(RuntimeError):
    pass


def check(ok, *detail):
    """A phase check that holds under ``python -O`` too."""
    if not ok:
        raise PhaseFailed(repr(detail))


def recall_at_k(ids, ref_ids) -> float:
    import numpy as np
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    hits = [len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(ids, ref_ids)]
    return float(np.sum(hits)) / ref_ids.size


def agreement(s_a, i_a, s_b, i_b, tol: float):
    """(max |score difference|, id mismatches). Rank positions whose ids
    differ count as a mismatch unless both rows hold that id at a score
    within ``tol`` (an exact tie reordered) or it sits at the cut-off."""
    import numpy as np
    s_a, i_a, s_b, i_b = map(np.asarray, (s_a, i_a, s_b, i_b))
    fin = np.isfinite(s_a) & np.isfinite(s_b)
    diff = float(np.max(np.abs(s_a - s_b)[fin], initial=0.0))
    if (np.isfinite(s_a) != np.isfinite(s_b)).any():
        diff = float("inf")
    bad = 0
    for r in range(i_a.shape[0]):
        for j in np.flatnonzero(i_a[r] != i_b[r]):
            where = np.flatnonzero(i_b[r] == i_a[r, j])
            tied = (abs(s_b[r, where[0]] - s_a[r, j]) <= tol if where.size
                    else abs(s_a[r, j] - s_b[r, -1]) <= tol)
            bad += not tied
    return diff, bad


def check_results(results, n: int, k: int):
    import numpy as np
    for s, i in results:
        s, i = np.asarray(s), np.asarray(i)
        check(s.shape == (k,) and i.shape == (k,), s.shape, i.shape)
        check(np.isfinite(s).all(), s)
        check(((i >= 0) & (i < n)).all(), i)
        check((np.diff(s) <= 0).all(), s)  # ranked best first


def timed(jax, fn, *a, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*a, **kw))
    return out, time.perf_counter() - t0


# ------------------------------------------------------------ one chip
def one_chip(jax, n, n_queries, seed, rehearse, clock):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import VectorDB
    from repro.core.pq import ivf_pq_search
    from repro.kernels import ops as kops
    from repro.serve.async_engine import AsyncQueryEngine

    dev = jax.devices()[0]
    backend = kops.resolve_adc_backend()
    print(f"ADC backend: {backend}", flush=True)
    if not rehearse:
        check(backend == "kernel", backend)
    (x, sub), t_gen = timed(jax, make_corpus, jax, seed, n, [dev])
    print(f"corpus: {n} x {D} f32 ({x.nbytes / 1e9:.2f} GB), "
          f"{sub.shape[0]} sub-clusters, generated in {t_gen:.1f}s",
          flush=True)
    queries = make_queries(jax, seed, sub, n_queries, same_sub=False)
    (ref_s, ref_i), t_ref = timed(jax, exact_topk, jax, x, queries, K)
    print(f"exact f32 scan of {n_queries} queries: {t_ref:.1f}s", flush=True)

    db = VectorDB("ivf_pq", metric="cosine", m=M, ksub=KSUB, nprobe=NPROBE,
                  refine=REFINE)
    t0 = time.perf_counter()
    db.load(x)
    jax.block_until_ready((db.index.codes_bm, db.index.corpus))
    idx = db.index
    print(f"load: {time.perf_counter() - t0:.1f}s | {idx.centroids.shape[0]}"
          f" lists, blocks {idx.codes_bm.shape[0]} x {idx.codes_bm.shape[1]}"
          f", steps/probe {idx.spp} | index {idx.memory_bytes() / 1e6:.0f} MB"
          f" + re-rank rows {idx.corpus.nbytes / 1e9:.2f} GB on device"
          f" | {clock.line()}", flush=True)
    del x

    # serving: one warm-up batch (compiles the 64-query plan), then every
    # query through a fresh engine; each future must resolve to a result
    # (queued before the engine starts, so every batch is a full 64)
    qs = np.asarray(queries)
    with AsyncQueryEngine(db, max_batch=64, start=False) as eng:
        futs = [eng.submit(q, k=K) for q in qs[:64]]
        eng.start()
        for f in futs:
            f.result()
    print(f"serve warm-up: {clock.line()}", flush=True)
    t0 = time.perf_counter()
    with AsyncQueryEngine(db, max_batch=64, start=False) as eng:
        futs = [eng.submit(q, k=K) for q in qs]
        eng.start()
        results = [f.result() for f in futs]
        lat = eng.latency_stats()
    wall = time.perf_counter() - t0
    check_results(results, n, K)
    rec = recall_at_k(np.stack([i for _, i in results]), ref_i)
    print(f"served {len(results)} queries via AsyncQueryEngine.submit in "
          f"{wall:.2f}s (all submitted at once, batches of 64): "
          f"p50 {lat['p50_ms']:.1f} ms, p99 {lat['p99_ms']:.1f} ms | "
          f"recall@{K} {rec:.4f} (floor {RECALL_FLOOR}) | grids "
          f"{ {g: db.adc_stats[g] for g in kops.ADC_MODES[1:]} }",
          flush=True)
    check(rec >= RECALL_FLOOR, rec, RECALL_FLOOR)

    # one batch per grid, kernel vs jnp twin on the same visit table: a
    # batch whose queries share probes, so the grouped grids' panels fit
    qb = make_queries(jax, seed + 1, sub, 8, same_sub=True)
    qb = qb / jnp.linalg.norm(qb, axis=1, keepdims=True)
    use_kernel = True if rehearse else None
    outs = {}
    for grid in ("per_query", "blocked", "run_resident"):
        for be in ("kernel", "jnp"):
            st = {}
            t0 = time.perf_counter()
            outs[grid, be] = jax.block_until_ready(ivf_pq_search(
                idx.codebooks, None, idx.centroids, None, None, qb,
                metric="dot", k=K, nprobe=NPROBE,
                block_lists=(idx.codes_bm, idx.bucket_ids, idx.block_table),
                steps_per_probe=idx.spp, adc_mode=grid, adc_stats=st,
                use_kernel=use_kernel if be == "kernel" else False))
            check(st["mode"] == grid, st["mode"], grid)
            if be == "kernel":
                print(f"  {grid:12s} kernel: {time.perf_counter() - t0:.2f}s"
                      f" with compile | groups {st['groups']} qblk "
                      f"{st['qblk']} sharing {st['sharing']:.1f}", flush=True)
        diff, bad = agreement(*outs[grid, "kernel"], *outs[grid, "jnp"],
                              tol=1e-5)
        across = agreement(*outs[grid, "kernel"],
                           *outs["per_query", "kernel"], tol=1e-5)
        print(f"  {grid:12s} kernel vs jnp twin: max |score diff| {diff:.3g},"
              f" id mismatches {bad} | vs per_query kernel: {across[0]:.3g},"
              f" {across[1]}", flush=True)
        check(diff <= 1e-5 and bad == 0, grid, diff, bad)
        check(across[0] <= 1e-5 and across[1] == 0, grid, across)
    check_results(zip(*map(np.asarray, outs["per_query", "kernel"])), n, K)
    stats = dev.memory_stats() or {}
    grid_backend = "kernel (interpret mode)" if rehearse else backend
    print(f"grids per_query, blocked, run_resident ran on the {grid_backend}"
          f" backend | {clock.line()} | device peak "
          f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB", flush=True)


# ---------------------------------------------------------- four chips
def four_chips(jax, n, n_queries, seed, rehearse, clock):
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import VectorDB
    from repro.core.db import DistributedIVFPQ

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("shard",))
    (x, sub), t_gen = timed(jax, make_corpus, jax, seed, n, devs)
    print(f"corpus: {n} x {D} f32 ({x.nbytes / 1e9:.2f} GB) row-sharded "
          f"over {len(devs)} devices, generated in {t_gen:.1f}s", flush=True)
    queries = np.asarray(make_queries(jax, seed, sub, n_queries,
                                      same_sub=False))

    dist = DistributedIVFPQ(mesh, metric="cosine", m=M, ksub=KSUB,
                            nprobe=NPROBE)
    t0 = time.perf_counter()
    dist.load(x)
    jax.block_until_ready(dist.codes_bm)
    print(f"sharded load: {time.perf_counter() - t0:.1f}s | "
          f"{dist.centroids.shape[0]} lists, {dist.blocks_per_shard} blocks "
          f"per shard, {dist.per_device_bytes() / 1e6:.0f} MB per device | "
          f"{clock.line()}", flush=True)
    x_host = np.asarray(x)
    del x
    ref = VectorDB("ivf_pq", metric="cosine", m=M, ksub=KSUB, nprobe=NPROBE,
                   refine=0)
    t0 = time.perf_counter()
    ref.load(x_host)
    print(f"one-device reference load: {time.perf_counter() - t0:.1f}s | "
          f"{clock.line()}", flush=True)
    del x_host

    got, want = [], []
    for b in range(0, n_queries, 64):
        got.append(jax.device_get(dist.query(queries[b:b + 64], k=K)))
        want.append(jax.device_get(ref.query(queries[b:b + 64], k=K)))
    s_d, i_d = (np.concatenate([g[j] for g in got]) for j in (0, 1))
    s_r, i_r = (np.concatenate([w[j] for w in want]) for j in (0, 1))
    check_results(zip(s_d, i_d), n, K)
    diff, bad = agreement(s_d, i_d, s_r, i_r, tol=1e-5)
    print(f"sharded vs one-device ivf_pq on {n_queries} queries: max |score"
          f" diff| {diff:.3g}, id mismatches {bad} | {clock.line()}",
          flush=True)
    check(diff <= 1e-5 and bad == 0, diff, bad)


if __name__ == "__main__":
    sys.exit(main())
