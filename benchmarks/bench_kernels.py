"""Kernel-level benchmark: oracle-vs-kernel agreement + oracle walltimes.

The kernels resolve their mode by backend (``interpret=None``): compiled on
a TPU, interpret mode everywhere else. Interpret-mode Pallas timing is not
meaningful (Python per-block execution), so off the chip this suite checks
agreement with the oracles and times only the jnp oracles and twins, on the
device every row names. It is not a device benchmark.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import hamming, pq_adc, topk_distance
from repro.kernels import ref as R


def _timeit(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def topk_agreement():
    rows = []
    rng = np.random.default_rng(0)
    for (N, d, Q, k) in [(2048, 64, 8, 10), (8192, 128, 4, 10)]:
        c = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
        s, i = topk_distance(c, q, k=k, metric="dot", blk_n=512)
        rs, ri = R.topk_distance_ref(c, q, k=k, metric="dot")
        ok = bool((np.asarray(i) == np.asarray(ri)).all())
        oracle_t = _timeit(jax.jit(lambda c, q: R.topk_distance_ref(c, q, k=k)), c, q)
        rows.append({"N": N, "d": d, "match": ok, "oracle_s": oracle_t})
    return rows


def pq_adc_agreement():
    rng = np.random.default_rng(2)
    rows = []
    for (N, m, ksub, Q, k) in [(4096, 8, 256, 8, 10), (8192, 16, 256, 4, 10)]:
        codes = jnp.asarray(rng.integers(0, ksub, (N, m)).astype(np.int32))
        luts = jnp.asarray(rng.normal(size=(Q, m, ksub)).astype(np.float32))
        s, i = pq_adc(codes, luts, k=k, blk_n=512)
        rs, ri = R.pq_adc_ref(codes, luts, k=k)
        ok = bool((np.asarray(i) == np.asarray(ri)).all())
        oracle_t = _timeit(jax.jit(lambda c, l: R.pq_adc_ref(c, l, k=k)),
                           codes, luts)
        rows.append({"N": N, "m": m, "match": ok, "oracle_s": oracle_t})
    return rows


def ivf_adc_agreement():
    """Bucket-resident IVF-ADC: dispatcher (twin) parity vs the gather
    oracle, plus CPU walltimes of the three scoring strategies at the same
    probe geometry — ivf_adc (bucket-resident twin) vs pq_adc (all-codes
    fused twin) vs the materialize-everything jnp gather oracle."""
    from repro.core import build_block_lists
    from repro.kernels import adc_topk_jnp, ivf_adc_topk

    rng = np.random.default_rng(3)
    rows = []
    for (N, C, blk, m, ksub, Q, nprobe, k) in [
            (8192, 64, 32, 8, 256, 8, 8, 10),
            (16384, 128, 32, 8, 256, 8, 4, 10)]:
        assign = rng.integers(0, C, N)
        slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
        slots = jnp.asarray(slots)
        codes_flat = jnp.asarray(rng.integers(0, ksub, (N, m)).astype(np.int32))
        codes = jnp.take(codes_flat, jnp.clip(slots, 0), axis=0)
        luts = jnp.asarray(rng.normal(size=(Q, m, ksub)).astype(np.float32))
        probe = jnp.asarray(np.stack(
            [rng.choice(C, nprobe, replace=False) for _ in range(Q)]
        ).astype(np.int32))
        base = jnp.take(jnp.asarray(bstart), probe, axis=0)
        cnt = jnp.take(jnp.asarray(bcnt), probe, axis=0)
        r = jnp.arange(spp, dtype=jnp.int32)[None, None, :]
        visit = jnp.where(r < cnt[:, :, None], base[:, :, None] + r,
                          slots.shape[0] - 1).reshape(Q, nprobe * spp)

        s, i = ivf_adc_topk(codes, slots, visit, luts, k=k,
                            steps_per_probe=spp, use_kernel=False)
        rs, ri = R.ivf_adc_ref(codes, slots, visit, luts, k=k,
                               steps_per_probe=spp)
        ok = bool((np.asarray(i) == np.asarray(ri)).all())
        bucket_t = _timeit(
            lambda: ivf_adc_topk(codes, slots, visit, luts, k=k,
                                 steps_per_probe=spp, use_kernel=False))
        all_codes_t = _timeit(lambda: adc_topk_jnp(codes_flat, luts, k=k))
        gather_t = _timeit(
            lambda: R.ivf_adc_ref(codes, slots, visit, luts, k=k,
                                  steps_per_probe=spp))
        rows.append({"N": N, "nprobe": nprobe, "match": ok,
                     "bucket_s": bucket_t, "all_codes_s": all_codes_t,
                     "gather_s": gather_t})
    return rows


def ivf_adc_run_resident_agreement():
    """Run-resident grid: Pallas kernel vs jnp twin vs
    the gather oracle on the same visit table. Every grid scores with the
    same per-subspace contraction folded in the same order, so the grids
    are bit-exact (scores AND ids) on any geometry. Sizes stay small —
    interpret mode executes per-run Python."""
    from repro.core import build_block_lists
    from repro.kernels import ivf_adc_topk

    rng = np.random.default_rng(4)
    rows = []
    for (N, C, blk, m, ksub, Q, nprobe, k) in [
            (2048, 32, 8, 8, 64, 16, 4, 10)]:
        assign = rng.integers(0, C, N)
        slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
        slots = jnp.asarray(slots)
        codes_flat = jnp.asarray(rng.integers(0, ksub, (N, m)).astype(np.int32))
        codes = jnp.take(codes_flat, jnp.clip(slots, 0), axis=0)
        luts = jnp.asarray(rng.normal(size=(Q, m, ksub)).astype(np.float32))
        probe = jnp.asarray(np.stack(
            [rng.choice(C, nprobe, replace=False) for _ in range(Q)]
        ).astype(np.int32))
        base = jnp.take(jnp.asarray(bstart), probe, axis=0)
        cnt = jnp.take(jnp.asarray(bcnt), probe, axis=0)
        r = jnp.arange(spp, dtype=jnp.int32)[None, None, :]
        visit = jnp.where(r < cnt[:, :, None], base[:, :, None] + r,
                          slots.shape[0] - 1).reshape(Q, nprobe * spp)
        kw = dict(k=k, steps_per_probe=spp, pad_block=slots.shape[0] - 1)
        st, it = ivf_adc_topk(codes, slots, visit, luts, use_kernel=False,
                              mode="run_resident", **kw)
        sk, ik = ivf_adc_topk(codes, slots, visit, luts, use_kernel=True,
                              mode="run_resident", **kw)
        sp, ip = ivf_adc_topk(codes, slots, visit, luts, use_kernel=True,
                              mode="per_query", **kw)
        sb, ib = ivf_adc_topk(codes, slots, visit, luts, use_kernel=True,
                              mode="blocked", **kw)
        rs, ri = R.ivf_adc_ref(codes, slots, visit, luts, k=k,
                               steps_per_probe=spp)
        twin_vs_oracle = bool((np.asarray(it) == np.asarray(ri)).all())
        kernel_vs_blocked = bool(
            (np.asarray(ik) == np.asarray(ib)).all()
            and (np.asarray(sk) == np.asarray(sb)).all())
        kernel_ids_vs_per_query = bool(
            (np.asarray(ik) == np.asarray(ip)).all())
        kernel_vs_twin_ids = bool((np.asarray(ik) == np.asarray(it)).all())
        rows.append({"N": N, "nprobe": nprobe,
                     "match": (twin_vs_oracle and kernel_vs_blocked
                               and kernel_ids_vs_per_query
                               and kernel_vs_twin_ids),
                     "twin_vs_oracle": twin_vs_oracle,
                     "kernel_vs_blocked": kernel_vs_blocked,
                     "kernel_ids_vs_per_query": kernel_ids_vs_per_query,
                     "kernel_vs_twin_ids": kernel_vs_twin_ids})
    return rows


def hamming_agreement():
    rng = np.random.default_rng(1)
    rows = []
    for (T, Q, N, W) in [(4, 8, 4096, 4)]:
        qc = jnp.asarray(rng.integers(0, 2**32, (T, Q, W), dtype=np.uint64).astype(np.uint32))
        cc = jnp.asarray(rng.integers(0, 2**32, (T, N, W), dtype=np.uint64).astype(np.uint32))
        out = hamming(qc, cc, blk_n=512)
        ref = R.hamming_ref(qc, cc)
        ok = bool((np.asarray(out) == np.asarray(ref)).all())
        oracle_t = _timeit(jax.jit(R.hamming_ref), qc, cc)
        rows.append({"N": N, "match": ok, "oracle_s": oracle_t})
    return rows


def main(quick: bool = False):
    dev = jax.devices()[0]
    print(f"# kernels on {dev.platform} {dev.device_kind} "
          f"(interpret mode: {dev.platform != 'tpu'})")
    print("name,case,match,oracle_s")
    rows = {"topk": topk_agreement(), "pq_adc": pq_adc_agreement(),
            "ivf_adc": ivf_adc_agreement(),
            "ivf_adc_run_resident": ivf_adc_run_resident_agreement(),
            "hamming": hamming_agreement()}
    for r in rows["topk"]:
        print(f"kernels,topk_N{r['N']}d{r['d']},{r['match']},{r['oracle_s']:.4f}")
    for r in rows["pq_adc"]:
        print(f"kernels,pq_adc_N{r['N']}m{r['m']},{r['match']},{r['oracle_s']:.4f}")
    for r in rows["ivf_adc"]:
        print(f"kernels,ivf_adc_N{r['N']}np{r['nprobe']},{r['match']},"
              f"bucket={r['bucket_s']:.4f},all_codes={r['all_codes_s']:.4f},"
              f"gather={r['gather_s']:.4f}")
    for r in rows["ivf_adc_run_resident"]:
        print(f"kernels,ivf_adc_runres_N{r['N']}np{r['nprobe']},{r['match']},"
              f"twin_vs_oracle={r['twin_vs_oracle']},"
              f"kernel_vs_blocked={r['kernel_vs_blocked']},"
              f"kernel_vs_twin_ids={r['kernel_vs_twin_ids']}")
    for r in rows["hamming"]:
        print(f"kernels,hamming_N{r['N']},{r['match']},{r['oracle_s']:.4f}")
    return rows


if __name__ == "__main__":
    main()
