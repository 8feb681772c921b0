"""Bucket-resident IVF-ADC path: kernel/twin/oracle parity across all
metrics and LUT dtypes, the block-aligned inverted-list layout, int8 LUT
guards (quantization bound, table bytes, recall), ragged/empty bucket edge
cases, and the true-nprobe engine behavior (kernel path == jnp path, with
the all-codes scan demoted to an explicit scan_all hatch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import VectorDB, build_block_lists
from repro.core.ivf import build_buckets
from repro.kernels import ivf_adc_topk, ivf_adc_topk_jnp, quantize_lut_int8
from repro.kernels import ivf_adc as ivf_mod
from repro.kernels import ref as R
from repro.kernels.ivf_adc import ivf_adc
from repro.kernels.ops import mask_allowed_ids
from repro.kernels.topk_distance import NEG_INF


def _clustered(rng, n, d, n_clusters, scale=2.0):
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * scale
    return (centers[rng.integers(0, n_clusters, n)]
            + rng.normal(size=(n, d)).astype(np.float32))


def _random_layout(rng, N, C, blk=8):
    """Random cluster assignment -> block lists + per-row codes."""
    assign = rng.integers(0, C, N)
    slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
    return assign, jnp.asarray(slots), jnp.asarray(bstart), \
        jnp.asarray(bcnt), spp


def _expand_visit(probe, bstart, bcnt, spp, n_blocks):
    base = np.asarray(bstart)[np.asarray(probe)]
    cnt = np.asarray(bcnt)[np.asarray(probe)]
    r = np.arange(spp)[None, None, :]
    visit = np.where(r < cnt[:, :, None], base[:, :, None] + r, n_blocks - 1)
    return jnp.asarray(visit.reshape(probe.shape[0], -1).astype(np.int32))


# ------------------------------------------------------------ layout

def test_build_block_lists_properties(rng):
    N, C, blk = 1003, 37, 8
    assign = rng.integers(0, C, N)
    assign[assign == 5] = 6  # force an empty cluster
    slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
    counts = np.bincount(assign, minlength=C)
    np.testing.assert_array_equal(bcnt, -(-counts // blk))
    assert bcnt[5] == 0 and spp == int(bcnt.max())
    # every row appears exactly once; pad block is all -1
    seen = slots[:-1][slots[:-1] >= 0]
    np.testing.assert_array_equal(np.sort(seen), np.arange(N))
    assert (slots[-1] == -1).all()
    # each cluster's rows sit in its block range, slack < blk per cluster
    for c in range(C):
        rows = slots[bstart[c]:bstart[c] + bcnt[c]].reshape(-1)
        got = rows[rows >= 0]
        np.testing.assert_array_equal(np.sort(got),
                                      np.where(assign == c)[0])
        assert (rows >= 0).sum() > (bcnt[c] - 1) * blk or counts[c] == 0
    # total slack is bounded by blk-1 per non-empty cluster
    assert (slots[:-1] < 0).sum() <= (counts > 0).sum() * (blk - 1)


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("per_probe", [False, True])
@pytest.mark.parametrize("N,C,blk,Q,nprobe,k,ksub,m",
                         [(500, 20, 8, 4, 4, 8, 32, 4),
                          (1000, 10, 16, 3, 3, 10, 64, 8),
                          (200, 40, 8, 6, 12, 5, 16, 4)])
def test_ivf_adc_backends_vs_oracle(rng, per_probe, N, C, blk, Q, nprobe,
                                    k, ksub, m):
    _, slots, bstart, bcnt, spp = _random_layout(rng, N, C, blk=blk)
    codes = jnp.asarray(
        rng.integers(0, ksub, (slots.shape[0], blk, m)).astype(np.int32))
    probe = jnp.asarray(np.stack(
        [rng.choice(C, nprobe, replace=False) for _ in range(Q)]
    ).astype(np.int32))
    visit = _expand_visit(probe, bstart, bcnt, spp, slots.shape[0])
    lshape = (Q, nprobe, m, ksub) if per_probe else (Q, m, ksub)
    luts = jnp.asarray(rng.normal(size=lshape).astype(np.float32))
    coarse = jnp.asarray(rng.normal(size=(Q, nprobe)).astype(np.float32))
    rs, ri = R.ivf_adc_ref(codes, slots, visit, luts, coarse, k=k,
                           steps_per_probe=spp)
    for use_kernel in (False, True):  # jnp twin / Pallas kernel (interpret)
        s, i = ivf_adc_topk(codes, slots, visit, luts, k=k, coarse=coarse,
                            steps_per_probe=spp, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16", "int8"])
def test_ivf_adc_twin_matches_kernel(rng, lut_dtype):
    """The jnp twin and the Pallas kernel quantize and rank identically for
    every LUT dtype (continuous scores -> identical ids)."""
    _, slots, bstart, bcnt, spp = _random_layout(rng, 600, 15, blk=8)
    codes = jnp.asarray(
        rng.integers(0, 64, (slots.shape[0], 8, 8)).astype(np.int32))
    probe = jnp.asarray(np.stack(
        [rng.choice(15, 5, replace=False) for _ in range(4)]).astype(np.int32))
    visit = _expand_visit(probe, bstart, bcnt, spp, slots.shape[0])
    luts = jnp.asarray(rng.normal(size=(4, 5, 8, 64)).astype(np.float32))
    s0, i0 = ivf_adc_topk(codes, slots, visit, luts, k=9,
                          steps_per_probe=spp, use_kernel=False,
                          lut_dtype=lut_dtype)
    s1, i1 = ivf_adc_topk(codes, slots, visit, luts, k=9,
                          steps_per_probe=spp, use_kernel=True,
                          lut_dtype=lut_dtype)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-4,
                               rtol=1e-4)


# ------------------------------------------------------------ pad-step skip

PAD_SKIP_CASES = ("ragged", "all_pad", "adaptive", "allowed", "tombstoned",
                  "smem_chunks")


def _pad_skip_problem(rng, case, Q=5, C=12, blk=8, m=8, ksub=32, nprobe=4):
    """A per-query grid walk with many pad steps: one long list sets
    steps_per_probe, the other lists are short and ragged, one is empty.
    ``case`` layers one more way a step or slot knocks out on top."""
    n = 100
    assign = rng.integers(0, C, n)
    assign[:40] = 0
    assign[assign == 2] = 3
    slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
    pad = slots.shape[0] - 1
    codes = rng.integers(0, ksub, (slots.shape[0], blk, m)).astype(np.int32)
    probe = np.stack([rng.choice(C, nprobe, replace=False)
                      for _ in range(Q)]).astype(np.int32)
    probe[0, 1] = 2  # the empty list: a probe of pad steps only
    visit = np.array(_expand_visit(probe, bstart, bcnt, spp, pad + 1))
    luts = rng.normal(size=(Q, m, ksub)).astype(np.float32)
    coarse = rng.normal(size=(Q, nprobe)).astype(np.float32)
    if case == "all_pad":
        visit[:] = pad
    elif case == "adaptive":
        # as _ivf_probe_stage masks: steps to the pad block, coarse NEG_INF
        active = rng.random((Q, nprobe)) < 0.5
        active[:, 0] = True
        visit = np.where(np.repeat(active, spp, axis=1), visit, pad)
        coarse = np.where(active, coarse, NEG_INF).astype(np.float32)
    elif case == "allowed":
        slots = np.asarray(mask_allowed_ids(jnp.asarray(slots),
                                            jnp.asarray(rng.random(n) < 0.3)))
    elif case == "tombstoned":
        slots = np.where(rng.random(slots.shape) < 0.4, -1, slots)
        slots[bstart[0]] = -1  # a whole real block deleted, still visited
    return (jnp.asarray(codes), jnp.asarray(slots), jnp.asarray(visit),
            jnp.asarray(luts), jnp.asarray(coarse), spp, pad)


def _live_ids(s, i):
    """The ids the dispatcher serves: -1 under knocked-out scores."""
    s, i = np.asarray(s), np.asarray(i)
    return np.where(s > 0.5 * NEG_INF, i, -1)


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("case", PAD_SKIP_CASES + ("ragged_m192",))
def test_ivf_adc_pad_skip_bit_identical(rng, monkeypatch, case, lut_dtype):
    """The per-query kernel told the pad block skips its steps and returns
    bit-identical raw scores, and identical ids wherever a score is above
    the knockout, to the same kernel scoring every step and to the jnp
    twin; the served (normalized) answers are identical outright. k
    exceeds some queries' candidates, so knocked-out slots are compared
    too. ``smem_chunks`` shrinks the SMEM budget so the wrapper's
    query-chunk loop (a padded last chunk included) carries the skip.
    ``ragged_m192`` is the ragged walk at the 1536-d deployment's tables:
    192 subspaces of ksub 256, a 49,152-wide LUT row a step."""
    m, ksub = (192, 256) if case == "ragged_m192" else (8, 32)
    codes, slots, visit, luts, coarse, spp, pad = _pad_skip_problem(
        rng, case, m=m, ksub=ksub)
    Q, T = visit.shape
    if case == "smem_chunks":
        monkeypatch.setattr(ivf_mod, "SMEM_VISIT_BYTES", 4 * T * 2)
        ivf_adc.clear_cache()  # a cached unchunked trace would hide the loop
        assert ivf_mod.SMEM_VISIT_BYTES // (4 * T) < Q
    kw = dict(k=40, steps_per_probe=spp, lut_dtype=lut_dtype)
    s0, i0 = ivf_adc(codes, slots, visit, luts, coarse, interpret=True, **kw)
    s1, i1 = ivf_adc(codes, slots, visit, luts, coarse, interpret=True,
                     pad_block=pad, **kw)
    sj, ij = ivf_adc_topk_jnp(codes, slots, visit, luts, coarse, **kw)
    if case == "smem_chunks":
        ivf_adc.clear_cache()
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    np.testing.assert_array_equal(_live_ids(s1, i1), _live_ids(s0, i0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(sj))
    np.testing.assert_array_equal(_live_ids(s1, i1), _live_ids(sj, ij))
    if case == "all_pad":
        assert np.all(np.asarray(s1) == NEG_INF)
        assert np.all(np.asarray(i1) == -1)
    else:
        assert (_live_ids(s1, i1) >= 0).any()
        assert (_live_ids(s1, i1) == -1).any()  # some knockout fires
    served = [ivf_adc_topk(codes, slots, visit, luts, coarse=coarse,
                           mode="per_query", use_kernel=use_kernel,
                           pad_block=pb, **kw)
              for use_kernel, pb in ((True, pad), (True, None), (False, pad))]
    for s, i in served[1:]:
        np.testing.assert_array_equal(np.asarray(s), np.asarray(served[0][0]))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(served[0][1]))


def test_ivf_adc_pad_skip_keys_on_the_pad_block(rng):
    """Plant live ids and non-zero codes in the pad row: the kernel told
    the pad block, directly or through the dispatcher, returns exactly
    what the clean layout returns, and the kernel that scores every step
    serves the planted rows. So the skip covers every pad step and nothing
    else."""
    codes, slots, visit, luts, coarse, spp, pad = _pad_skip_problem(
        rng, "ragged")
    blk, m = codes.shape[1:]
    planted = 1000 + np.arange(blk, dtype=np.int32)
    dirty_slots = slots.at[pad].set(jnp.asarray(planted))
    dirty_codes = codes.at[pad].set(
        jnp.asarray(rng.integers(1, 32, (blk, m)).astype(np.int32)))
    kw = dict(k=64, steps_per_probe=spp, interpret=True)
    clean = ivf_adc(codes, slots, visit, luts, coarse, pad_block=pad, **kw)
    skip = ivf_adc(dirty_codes, dirty_slots, visit, luts, coarse,
                   pad_block=pad, **kw)
    every = ivf_adc(dirty_codes, dirty_slots, visit, luts, coarse, **kw)
    np.testing.assert_array_equal(np.asarray(skip[0]), np.asarray(clean[0]))
    np.testing.assert_array_equal(np.asarray(skip[1]), np.asarray(clean[1]))
    assert not np.isin(_live_ids(*skip), planted).any()
    assert np.isin(_live_ids(*every), planted).any(axis=1).all()
    # the dispatcher hands its pad_block on to the kernel
    _, served = ivf_adc_topk(dirty_codes, dirty_slots, visit, luts, k=64,
                             coarse=coarse, steps_per_probe=spp,
                             mode="per_query", use_kernel=True,
                             pad_block=pad)
    np.testing.assert_array_equal(np.asarray(served), _live_ids(*clean))


def test_int8_lut_quantization_bound(rng):
    """|score_int8 - score_f32| <= sum_j scale[q, j]/2 <= m * max|lut|/254
    (one absmax-quantization rounding per gathered subspace entry)."""
    m, ksub = 8, 64
    _, slots, bstart, bcnt, spp = _random_layout(rng, 800, 10, blk=8)
    codes = jnp.asarray(
        rng.integers(0, ksub, (slots.shape[0], 8, m)).astype(np.int32))
    probe = jnp.asarray(np.stack(
        [rng.choice(10, 4, replace=False) for _ in range(3)]).astype(np.int32))
    visit = _expand_visit(probe, bstart, bcnt, spp, slots.shape[0])
    luts = jnp.asarray(rng.normal(size=(3, m, ksub)).astype(np.float32))
    rs, _ = R.ivf_adc_ref(codes, slots, visit, luts, k=8,
                          steps_per_probe=spp)
    s, _ = ivf_adc_topk(codes, slots, visit, luts, k=8, steps_per_probe=spp,
                        use_kernel=False, lut_dtype="int8")
    bound = m * float(jnp.abs(luts).max()) / 254.0
    finite = np.isfinite(np.asarray(rs))
    err = np.abs(np.asarray(s) - np.asarray(rs))[finite]
    assert err.max() <= bound * 1.01, (err.max(), bound)


def test_int8_tables_half_the_bytes_of_bf16(rng):
    """The acceptance memory claim: int8 tables (values + per-(q, j) f32
    scales) are ~2x smaller than bf16 tables at the default ksub=256."""
    luts = jnp.asarray(rng.normal(size=(16, 8, 256)).astype(np.float32))
    lut_i8, scales = quantize_lut_int8(luts)
    int8_bytes = lut_i8.size + scales.size * 4
    bf16_bytes = luts.size * 2
    assert bf16_bytes / int8_bytes >= 1.9, (bf16_bytes, int8_bytes)
    # and the quantizer round-trips within half a step everywhere
    err = jnp.abs(lut_i8.astype(jnp.float32) * scales[..., None] - luts)
    assert float((err - scales[..., None] / 2).max()) <= 1e-6


# ------------------------------------------------------------ edge cases

def test_empty_and_ragged_buckets(rng):
    """Probing an empty cluster or a ragged tail block must surface only
    -inf/-1 padding, never a pad slot's id."""
    C, blk, m, ksub = 6, 8, 4, 16
    assign = rng.integers(0, C, 45)
    assign[assign == 2] = 3  # cluster 2 empty; counts ragged vs blk=8
    slots, bstart, bcnt, spp = build_block_lists(assign, C, blk=blk)
    slots = jnp.asarray(slots)
    codes = jnp.asarray(
        rng.integers(0, ksub, (slots.shape[0], blk, m)).astype(np.int32))
    luts = jnp.asarray(rng.normal(size=(2, m, ksub)).astype(np.float32))
    probe = jnp.asarray([[2, 3], [2, 2]], jnp.int32)  # heavy on the empty one
    visit = _expand_visit(probe, jnp.asarray(bstart), jnp.asarray(bcnt),
                          spp, slots.shape[0])
    k = 30  # more than any probed candidate set holds
    for use_kernel in (False, True):
        s, i = ivf_adc_topk(codes, slots, visit, luts, k=k,
                            steps_per_probe=spp, use_kernel=use_kernel)
        s, i = np.asarray(s), np.asarray(i)
        n3 = int((np.asarray(assign) == 3).sum())
        # query 0 sees exactly cluster 3's rows; query 1 sees nothing
        assert (i[0] >= 0).sum() == n3
        assert (i[1] == -1).all() and np.isinf(s[1]).all()
        valid = i[0] >= 0
        assert np.isfinite(s[0][valid]).all()
        assert (~np.isfinite(s[0][~valid])).all()
        assert set(i[0][valid]) <= set(np.where(np.asarray(assign) == 3)[0])


def test_in_graph_fallback_matches_prebuilt_layout(rng):
    """ivf_pq_search(block_lists=None) treats the fixed-cap bucket table as
    a one-block-per-cluster layout and must rank like the prebuilt path."""
    from repro.core.pq import ivf_pq_search, pq_encode, train_pq

    corpus = _clustered(rng, 400, 16, 8)
    x = jnp.asarray(corpus)
    from repro.core.ivf import assign_clusters, kmeans
    cent = kmeans(jax.random.PRNGKey(0), x, n_clusters=8)
    assign = np.asarray(assign_clusters(x, cent))
    residuals = x - jnp.take(cent, jnp.asarray(assign), axis=0)
    cb = train_pq(jax.random.PRNGKey(1), residuals, m=4, ksub=32)
    codes = pq_encode(cb, residuals)
    buckets, _cap = build_buckets(assign, 8)
    slots, bstart, bcnt, spp = build_block_lists(assign, 8, blk=8)
    slots = jnp.asarray(slots)
    codes_bm = jnp.take(codes, jnp.clip(slots, 0), axis=0)
    q = jnp.asarray(corpus[:5])
    for metric in ("dot", "l2"):
        s0, i0 = ivf_pq_search(cb, codes, cent, jnp.asarray(buckets), None,
                               q, metric=metric, k=7, nprobe=3)
        s1, i1 = ivf_pq_search(cb, None, cent, None, None, q, metric=metric,
                               k=7, nprobe=3,
                               block_lists=(codes_bm, slots,
                                            jnp.asarray(bstart),
                                            jnp.asarray(bcnt)),
                               steps_per_probe=spp)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-4)


# ------------------------------------------------------------ engine paths

def test_true_nprobe_kernel_equals_jnp_through_engine(rng):
    """The fix the issue demands: kernel-path ivf_pq no longer ignores
    nprobe — both backends probe the SAME buckets and rank identically,
    for every metric."""
    corpus = _clustered(rng, 600, 32, 12)
    q = corpus[:8] + 0.01 * rng.normal(size=(8, 32)).astype(np.float32)
    for metric in ("cosine", "l2", "dot"):
        ref = VectorDB("ivf_pq", metric=metric, nprobe=3,
                       use_kernel=False).load(corpus)
        ker = VectorDB("ivf_pq", metric=metric, nprobe=3,
                       use_kernel=True).load(corpus)
        s0, i0 = ref.query(q, k=5)
        s1, i1 = ker.query(q, k=5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-4)


def test_full_nprobe_equals_scan_all(rng):
    """nprobe=C covers every row, so the bucket path must return the same
    candidates the scan_all escape hatch scores over all codes (dot)."""
    corpus = _clustered(rng, 500, 16, 10)
    q = corpus[:6]
    bucket = VectorDB("ivf_pq", metric="cosine", n_clusters=10, nprobe=10,
                      refine=0).load(corpus)
    hatch = VectorDB("ivf_pq", metric="cosine", n_clusters=10, nprobe=10,
                     refine=0, scan_all=True).load(corpus)
    s0, i0 = bucket.query(q, k=8)
    s1, i1 = hatch.query(q, k=8)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-3)


def test_scan_all_keeps_row_major_codes_and_costs_memory(rng):
    corpus = _clustered(rng, 400, 16, 8)
    lean = VectorDB("ivf_pq", metric="cosine", refine=0).load(corpus)
    hatch = VectorDB("ivf_pq", metric="cosine", refine=0,
                     scan_all=True).load(corpus)
    assert lean.index.codes is None and lean.index.assign is None
    assert hatch.index.codes is not None and hatch.index.assign is not None
    assert hatch.index.memory_bytes() > lean.index.memory_bytes()
    # the hatch refuses l2 (the coarse term cannot fold into shared codes)
    with pytest.raises(AssertionError, match="dot"):
        VectorDB("ivf_pq", metric="l2", refine=0,
                 scan_all=True).load(corpus).query(corpus[:2], k=3)


def test_int8_recall_within_bf16_guard(rng):
    """Acceptance: serving ivf_pq with int8 LUTs costs <= 0.02 recall@10 vs
    bf16 tables (compressed-domain, refine=0 so the re-rank cannot hide
    quantization), and stays above the 0.8 floor with the exact re-rank."""
    N = 4000
    corpus = _clustered(rng, N, 64, n_clusters=40)
    q = _clustered(rng, 128, 64, n_clusters=40)
    exact = VectorDB("flat", metric="cosine").load(corpus)
    eids = np.asarray(exact.query(q, k=10)[1])

    def recall(db):
        ids = np.asarray(db.query(q, k=10)[1])
        return np.mean([len(set(ids[i]) & set(eids[i])) / 10
                        for i in range(len(q))])

    kw = dict(metric="cosine", nprobe=16, refine=0)
    r_bf16 = recall(VectorDB("ivf_pq", lut_dtype="bfloat16", **kw).load(corpus))
    r_int8 = recall(VectorDB("ivf_pq", lut_dtype="int8", **kw).load(corpus))
    assert r_bf16 - r_int8 <= 0.02, (r_bf16, r_int8)
    r_served = recall(VectorDB("ivf_pq", metric="cosine", nprobe=32,
                               refine=128, lut_dtype="int8").load(corpus))
    assert r_served >= 0.8, r_served


def test_int8_flat_pq_engine_recall_floor(rng):
    """int8 LUTs through the FLAT pq engine (the other query path) keep the
    0.8 recall@10 gate at the served refine=128 config (the CI gate's)."""
    corpus = _clustered(rng, 4000, 64, n_clusters=40)
    q = _clustered(rng, 128, 64, n_clusters=40)
    eids = np.asarray(VectorDB("flat", metric="cosine").load(corpus)
                      .query(q, k=10)[1])
    db = VectorDB("pq", metric="cosine", refine=128,
                  lut_dtype="int8").load(corpus)
    ids = np.asarray(db.query(q, k=10)[1])
    recall = np.mean([len(set(ids[i]) & set(eids[i])) / 10
                      for i in range(len(q))])
    assert recall >= 0.8, recall


def test_ivf_pq_l2_served_by_fused_path(rng, monkeypatch):
    """l2 must run the bucket-resident dispatcher (not a jnp gather
    special case): poison the dispatcher and assert the engine calls it."""
    from repro.kernels import ops as kops

    corpus = _clustered(rng, 300, 16, 6)
    db = VectorDB("ivf_pq", metric="l2", refine=0).load(corpus)
    calls = {"n": 0}
    real = kops.ivf_adc_topk

    def spy(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(kops, "ivf_adc_topk", spy)
    db.query(corpus[:4], k=5, bucketize=False)
    assert calls["n"] == 1
