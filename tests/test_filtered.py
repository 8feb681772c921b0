"""Filtered + hybrid search (PR 10): predicate engine, bitmap threading,
BM25 fusion.

Three oracle families:

* the predicate AST is fuzzed against a pure-python row-by-row evaluator
  (seeded generator always; hypothesis rides along when installed);
* filtered top-k must EXACTLY equal the engine's own unfiltered full
  ranking post-filtered on the host (invariant 6: a filter is a mask
  change, not a scoring change) — checked at ~1% / 10% / 50% selectivity
  across every filterable engine, metric, and ADC grid mode, with
  refine=0 and nprobe=C so the candidate set covers every live slot;
* an all-true bitmap must be BIT-identical to no filter at all.

Plus: metadata durability (snapshot round-trip and WAL crash recovery),
BM25 vs a brute-force oracle, hybrid fusion sanity, and the serve fronts'
(predicate, alpha) batch grouping.
"""
import os

import numpy as np
import pytest

from repro.core.db import VectorDB
from repro.search import (And, BM25Index, Eq, In, MetadataStore, Not, Or,
                          Range, filter_hash, hybrid_merge)

SEED = 1234
CATS = ["x", "y", "z", "w"]


# --------------------------------------------------------------- fuzz oracle
def _random_rows(rng, n):
    """Row dicts over a fixed schema with ~30% absent fields. Constants
    match their column kind so store-side dtype casts are exact."""
    rows = []
    for _ in range(n):
        r = {}
        if rng.random() < 0.7:
            r["i"] = int(rng.integers(0, 6))
        if rng.random() < 0.7:
            r["f"] = float(rng.integers(0, 12)) / 2.0
        if rng.random() < 0.7:
            r["b"] = bool(rng.integers(0, 2))
        if rng.random() < 0.7:
            r["c"] = CATS[rng.integers(0, len(CATS))]
        rows.append(r)
    return rows


def _random_pred(rng, depth=0):
    kind = rng.integers(0, 6 if depth < 3 else 3)
    if kind == 0:
        col = ["i", "f", "b", "c"][rng.integers(0, 4)]
        if col == "c":
            return Eq("c", CATS[rng.integers(0, len(CATS))])
        if col == "b":
            return Eq("b", bool(rng.integers(0, 2)))
        if col == "i":
            return Eq("i", int(rng.integers(0, 6)))
        return Eq("f", float(rng.integers(0, 12)) / 2.0)
    if kind == 1:
        col = ["i", "f"][rng.integers(0, 2)]
        lo = None if rng.random() < 0.3 else float(rng.integers(0, 6))
        hi = None if rng.random() < 0.3 else float(rng.integers(0, 6))
        return Range(col, lo, hi)
    if kind == 2:
        col = ["i", "c"][rng.integers(0, 2)]
        if col == "c":
            vals = [CATS[j] for j in rng.integers(0, len(CATS), size=2)]
        else:
            vals = [int(v) for v in rng.integers(0, 6, size=2)]
        return In(col, vals)
    if kind == 3:
        return Not(_random_pred(rng, depth + 1))
    sub = [_random_pred(rng, depth + 1) for _ in range(int(rng.integers(1, 4)))]
    return (And if kind == 4 else Or)(*sub)


def _oracle(pred, rows):
    """Independent row-by-row evaluation of the predicate semantics."""
    def ev(p, r):
        if isinstance(p, Eq):
            return p.column in r and r[p.column] == p.value
        if isinstance(p, Range):
            if p.column not in r:
                return False
            v = r[p.column]
            return ((p.lo is None or v >= p.lo)
                    and (p.hi is None or v <= p.hi))
        if isinstance(p, In):
            return p.column in r and r[p.column] in p.values
        if isinstance(p, Not):
            return not ev(p.child, r)
        if isinstance(p, And):
            return all(ev(c, r) for c in p.children)
        if isinstance(p, Or):
            return any(ev(c, r) for c in p.children)
        raise TypeError(p)
    return np.asarray([ev(pred, r) for r in rows], bool)


@pytest.mark.parametrize("seed", range(40))
def test_predicate_fuzz_vs_oracle(seed):
    rng = np.random.default_rng(SEED + seed)
    n = int(rng.integers(1, 80))
    rows = _random_rows(rng, n)
    store = MetadataStore()
    store.put(np.arange(n), rows)
    for _ in range(8):
        pred = _random_pred(rng)
        try:
            got = store.mask(pred, n)
        except TypeError:
            # Range over a non-numeric column refuses by contract
            assert isinstance(pred, Range)
            continue
        np.testing.assert_array_equal(got, _oracle(pred, rows),
                                      err_msg=repr(pred))


try:
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(0, 2 ** 16))
    @settings(max_examples=25, deadline=None)
    def test_predicate_fuzz_hypothesis(seed):
        test_predicate_fuzz_vs_oracle(seed)
except ImportError:  # the seeded fuzzer above always runs
    pass


def test_predicate_semantics_edges():
    store = MetadataStore()
    store.put([0, 1, 2], [{"t": "a"}, {}, {"t": "b"}])
    # absent rows match nothing on Eq/In; Not flips the whole mask
    np.testing.assert_array_equal(store.mask(Eq("t", "a"), 3),
                                  [True, False, False])
    np.testing.assert_array_equal(store.mask(~Eq("t", "a"), 3),
                                  [False, True, True])
    # unknown column / unseen category -> empty, not an error
    assert not store.mask(Eq("missing", 1), 3).any()
    assert not store.mask(In("t", ["zzz"]), 3).any()
    # operator sugar builds the same AST
    p = Eq("t", "a") | (Eq("t", "b") & ~In("t", ["c"]))
    assert store.mask(p, 3).tolist() == [True, False, True]
    # filter_hash: stable, None -> 0, distinct predicates differ
    assert filter_hash(None) == 0
    assert filter_hash(p) == filter_hash(
        Eq("t", "a") | (Eq("t", "b") & ~In("t", ["c"])))
    assert filter_hash(p) != filter_hash(Eq("t", "a"))


def test_range_on_categorical_refuses():
    store = MetadataStore()
    store.put([0], [{"t": "a"}])
    with pytest.raises(TypeError):
        store.mask(Range("t", 0, 1), 1)


# --------------------------------------------------- filtered top-k parity
# every engine here ranks ALL live slots when configured with refine=0 and
# nprobe = n_clusters, so its own unfiltered full ranking is the oracle
ENGINE_CONFIGS = [
    ("flat", "cosine", {}),
    ("flat", "l2", {}),
    ("flat", "dot", {}),
    ("int8", "cosine", {}),
    ("pq", "cosine", {"refine": 0}),
    ("pq", "l2", {"refine": 0}),
    ("ivf", "cosine", {"n_clusters": 8, "nprobe": 8}),
    ("ivf", "l2", {"n_clusters": 8, "nprobe": 8}),
    ("ivf_pq", "cosine", {"n_clusters": 8, "nprobe": 8, "refine": 0,
                          "adc_mode": "per_query"}),
    ("ivf_pq", "cosine", {"n_clusters": 8, "nprobe": 8, "refine": 0,
                          "adc_mode": "blocked"}),
    ("ivf_pq", "l2", {"n_clusters": 8, "nprobe": 8, "refine": 0,
                      "adc_mode": "run_resident"}),
]

N, D_, Q, K = 400, 16, 4, 10


def _corpus(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D_)).astype(np.float32)
    meta = {"tag": (np.arange(N) % 100).tolist()}
    return X, meta


def _predicates():
    # tag is uniform over 0..99: Eq ~1%, Range(hi=9) ~10%, Range(hi=49) ~50%
    return [("1%", Eq("tag", 7)), ("10%", Range("tag", hi=9)),
            ("50%", Range("tag", hi=49))]


def _post_filter(scores, ids, allowed, kk):
    """The oracle: host-filter the engine's own full ranking. Stable —
    lax.top_k ties break by position, which filtering preserves."""
    out_s = np.full((ids.shape[0], kk), -np.inf, np.float32)
    out_i = np.full((ids.shape[0], kk), -1, np.int32)
    for r in range(ids.shape[0]):
        keep = [(s, i) for s, i in zip(scores[r], ids[r])
                if i >= 0 and allowed[i]][:kk]
        for c, (s, i) in enumerate(keep):
            out_s[r, c] = s
            out_i[r, c] = i
    return out_s, out_i


@pytest.mark.parametrize("engine,metric,kwargs", ENGINE_CONFIGS)
def test_filtered_topk_exact_parity(engine, metric, kwargs):
    X, meta = _corpus()
    db = VectorDB(engine=engine, metric=metric, **kwargs)
    db.load(X, meta=meta)
    q = X[:Q] + 0.01
    full_s, full_i = map(np.asarray, db.query(q, k=N))
    for label, pred in _predicates():
        allowed = db.metastore.mask(pred, N)
        want_s, want_i = _post_filter(full_s, full_i, allowed, K)
        got_s, got_i = map(np.asarray, db.query(q, k=K, where=pred))
        np.testing.assert_array_equal(got_i, want_i,
                                      err_msg=f"{engine}/{metric}/{label}")
        np.testing.assert_allclose(got_s, want_s, rtol=0, atol=0,
                                   err_msg=f"{engine}/{metric}/{label}")
        # every surfaced id satisfies the predicate
        alive = got_i[got_i >= 0]
        assert allowed[alive].all()


@pytest.mark.parametrize("engine,metric,kwargs", ENGINE_CONFIGS)
def test_all_true_bitmap_bit_identical(engine, metric, kwargs):
    X, meta = _corpus()
    db = VectorDB(engine=engine, metric=metric, **kwargs)
    db.load(X, meta=meta)
    q = X[:Q]
    s0, i0 = map(np.asarray, db.query(q, k=K))
    s1, i1 = map(np.asarray, db.query(q, k=K, where=Range("tag", lo=0)))
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)


def test_unfilterable_engines_refuse():
    X, meta = _corpus()
    for engine in ("lsh", "graph"):
        db = VectorDB(engine=engine, metric="cosine")
        db.load(X, meta=meta)
        with pytest.raises(NotImplementedError):
            db.query(X[:2], k=4, where=Eq("tag", 1))


def test_filtered_after_mutation():
    """The bitmap covers the GROWN id space: inserts/upserts/deletes keep
    metadata and filters aligned with the engines' stable ids."""
    X, meta = _corpus()
    db = VectorDB(engine="flat", metric="l2")
    db.load(X, meta=meta)
    rng = np.random.default_rng(3)
    new_ids = db.insert(rng.normal(size=(20, D_)).astype(np.float32),
                        meta={"tag": [1000] * 20})
    db.delete(new_ids[:5])
    db.upsert(rng.normal(size=(2, D_)).astype(np.float32), new_ids[5:7],
              meta={"tag": [2000, 2000]})
    s, i = map(np.asarray, db.query(X[:3], k=30, where=Eq("tag", 1000)))
    alive = i[i >= 0]
    assert set(alive) == set(int(x) for x in new_ids[7:])
    s, i = map(np.asarray, db.query(X[:3], k=5, where=Eq("tag", 2000)))
    assert set(i[i >= 0]) == set(int(x) for x in new_ids[5:7])


def test_selectivity_nprobe_boost_and_stats():
    X, meta = _corpus()
    db = VectorDB(engine="ivf_pq", metric="cosine", n_clusters=16,
                  nprobe=2, refine=0)
    db.load(X, meta=meta)
    assert db.filter_stats is None
    db.query(X[:2], k=5, where=Eq("tag", 7))       # ~1% -> boost (clamped 4)
    db.query(X[:2], k=5, where=Range("tag", lo=0))  # all-true -> no boost
    st = db.filter_stats
    assert st["filtered_batches"] == 2
    assert st["nprobe_boosts"] == 1
    assert st["selectivity_hist"]["<=1%"] == 1
    assert st["selectivity_hist"][">50%"] == 1
    assert st["bitmap_build_ms"] > 0


# ----------------------------------------------------------- durability
def test_metadata_snapshot_roundtrip(tmp_path):
    X, meta = _corpus()
    db = VectorDB(engine="ivf_pq", metric="l2", n_clusters=8, nprobe=8,
                  refine=0)
    db.load(X, meta=dict(meta, name=[CATS[i % 4] for i in range(N)]))
    db.save_index(str(tmp_path), 0)
    db2 = VectorDB(engine="ivf_pq", metric="l2", n_clusters=8, nprobe=8,
                   refine=0)
    db2.restore_index(str(tmp_path))
    q = X[:3]
    for pred in (Eq("name", "y"), Range("tag", hi=9) & ~Eq("name", "x")):
        w_s, w_i = map(np.asarray, db.query(q, k=K, where=pred))
        g_s, g_i = map(np.asarray, db2.query(q, k=K, where=pred))
        np.testing.assert_array_equal(w_i, g_i)
        np.testing.assert_array_equal(w_s, g_s)


def test_metadata_wal_recovery(tmp_path):
    rng = np.random.default_rng(5)
    X, meta = _corpus()
    db = VectorDB(engine="ivf_pq", metric="l2", n_clusters=8, nprobe=8,
                  refine=0)
    db.load(X, meta=meta)
    db.save_index(str(tmp_path), 0, durable=True)
    ins = db.insert(rng.normal(size=(12, D_)).astype(np.float32),
                    meta=[{"tag": 777, "src": "wal"}] * 12)
    db.delete(ins[:4])
    db.upsert(rng.normal(size=(3, D_)).astype(np.float32), ins[4:7],
              meta={"tag": [888] * 3, "src": ["up"] * 3})
    db.compact()
    # recover from snapshot + WAL tail only
    db2 = VectorDB(engine="ivf_pq", metric="l2", n_clusters=8, nprobe=8,
                   refine=0)
    db2.restore_index(str(tmp_path), durable=True)
    q = X[:3]
    for pred in (Eq("tag", 777), Eq("src", "up"),
                 Range("tag", hi=49) | Eq("tag", 888)):
        w_s, w_i = map(np.asarray, db.query(q, k=K, where=pred))
        g_s, g_i = map(np.asarray, db2.query(q, k=K, where=pred))
        np.testing.assert_array_equal(w_i, g_i, err_msg=repr(pred))
        np.testing.assert_array_equal(w_s, g_s, err_msg=repr(pred))


def test_wal_meta_record_roundtrip(tmp_path):
    """The optional meta segment decodes exactly and survives the
    truncate_through re-encode; records without it stay byte-identical
    to the pre-metadata framing."""
    from repro.core.wal import WriteAheadLog, decode_payload, encode_record
    meta = {"tag": [1, 2], "name": ["a", None]}
    rec = encode_record(7, "insert", vectors=np.zeros((2, 3), np.float32),
                        ids=np.asarray([5, 6]), meta=meta)
    got = decode_payload(rec[8:])
    assert got.meta == meta and got.lsn == 7
    bare = encode_record(7, "insert", vectors=np.zeros((2, 3), np.float32),
                         ids=np.asarray([5, 6]))
    assert b'"meta"' not in bare and decode_payload(bare[8:]).meta is None
    wal, _ = WriteAheadLog.open(str(tmp_path / "wal.log"))
    wal.append("insert", vectors=np.zeros((1, 2), np.float32),
               ids=np.asarray([0]), meta={"k": ["v"]})
    wal.append("delete", ids=np.asarray([0]))
    wal.truncate_through(0)  # rewrite every surviving record
    wal.close()
    wal2, records = WriteAheadLog.open(str(tmp_path / "wal.log"))
    wal2.close()
    assert [r.meta for r in records] == [{"k": ["v"]}, None]


# ------------------------------------------------------------- BM25 + hybrid
def _bm25_oracle(docs, q_terms, k1=1.5, b=0.75):
    """Textbook BM25 over token-id docs, one query."""
    N_ = len(docs)
    dl = np.asarray([len(d) for d in docs], float)
    avg = dl.mean()
    scores = np.zeros(N_)
    for t in set(q_terms):
        df = sum(1 for d in docs if t in d)
        if df == 0:
            continue
        idf = np.log(1.0 + (N_ - df + 0.5) / (df + 0.5))
        for r, d in enumerate(docs):
            tf = d.count(t)
            if tf:
                scores[r] += idf * tf * (k1 + 1) / (
                    tf + k1 * (1 - b + b * dl[r] / avg))
    return scores


def test_bm25_matches_oracle():
    rng = np.random.default_rng(11)
    docs = [list(rng.integers(2, 30, size=rng.integers(3, 20)))
            for _ in range(40)]
    idx = BM25Index.from_tokens(docs)
    q = [4, 4, 9, 17]
    s, i = idx.score([q], k=40)
    want = _bm25_oracle(docs, q)
    hit = i[0] >= 0
    got = dict(zip(i[0][hit].tolist(), s[0][hit].tolist()))
    for r, w in enumerate(want):
        if w > 0:
            assert abs(got[r] - w) < 1e-9
        else:
            assert r not in got
    # allowed bitmap composes
    allowed = np.zeros(40, bool)
    allowed[::2] = True
    s2, i2 = idx.score([q], k=40, allowed=allowed)
    assert all(r % 2 == 0 for r in i2[0][i2[0] >= 0])


def test_hybrid_merge_alpha_extremes():
    dense_s = np.asarray([[3.0, 2.0, 1.0]])
    dense_i = np.asarray([[10, 11, 12]])
    lex_s = np.asarray([[9.0, 4.0, 1.0]])
    lex_i = np.asarray([[20, 11, 21]])
    # alpha=1: dense ranking wins; lexical-only candidates contribute 0
    s, i = map(np.asarray, hybrid_merge(dense_s, dense_i, lex_s, lex_i,
                                        alpha=1.0, k=3))
    assert i[0].tolist()[:3] == [10, 11, 12]
    # alpha=0: lexical ranking wins (20 then 11); dense-only rows score 0
    s, i = map(np.asarray, hybrid_merge(dense_s, dense_i, lex_s, lex_i,
                                        alpha=0.0, k=2))
    assert i[0].tolist() == [20, 11]
    # duplicates surface once
    s, i = map(np.asarray, hybrid_merge(dense_s, dense_i, lex_s, lex_i,
                                        alpha=0.5, k=6))
    ids = i[0][i[0] >= 0].tolist()
    assert len(ids) == len(set(ids)) == 5


def test_hybrid_beats_noisy_dense():
    """On MarcoLike with noisy queries, a mid-alpha hybrid must reach at
    least the dense-only MRR — lexical evidence can only help here."""
    from repro.data.marco import MarcoLike, simple_tokenizer
    m = MarcoLike(n_passages=80, seed=2)
    rng = np.random.default_rng(7)
    proj = rng.normal(size=(m.vocab_size, 24)).astype(np.float32) / 5.0
    noise = rng.normal(size=(80, 24)).astype(np.float32) * 2.0

    def enc(texts, jitter=None):
        out = np.zeros((len(texts), 24), np.float32)
        for r, t in enumerate(texts):
            toks = simple_tokenizer(t, m.vocab_size, 64)
            out[r] = proj[toks[toks >= 2]].sum(0)
        if jitter is not None:
            out += jitter
        return out

    db = VectorDB(engine="flat", metric="cosine")
    texts = m.passage_texts()
    db.load(enc(texts), meta=None)
    db._texts = texts
    db.enable_lexical()
    qt = m.query_texts(noise=0.5)
    qv = enc(qt, jitter=noise)  # deliberately degraded dense queries

    def mrr(ids):
        out = 0.0
        for r, row in enumerate(np.asarray(ids)):
            where = np.where(row == r)[0]
            if where.size:
                out += 1.0 / (where[0] + 1)
        return out / len(ids)

    _, di = db.query(qv, k=10)
    _, hi = db.query(qv, k=10, hybrid=0.5, hybrid_texts=qt)
    assert mrr(hi) >= mrr(di)


# ----------------------------------------------------------------- serving
def test_serve_fronts_group_and_match_direct():
    from repro.serve.async_engine import AsyncQueryEngine
    from repro.serve.engine import QueryEngine
    X, meta = _corpus()
    pred = Range("tag", hi=9)

    def build():
        db = VectorDB(engine="flat", metric="cosine")
        db.load(X, meta=meta)
        return db

    oracle = build()
    want = [np.asarray(a) for a in oracle.query(X[:6], k=5, where=pred)]
    plain = [np.asarray(a) for a in oracle.query(X[:6], k=5)]

    eng = QueryEngine(build(), max_batch=16)
    rids = [eng.submit(X[i], k=5, where=pred) for i in range(6)]
    rids += [eng.submit(X[i], k=5) for i in range(6)]
    eng.drain()
    for r, rid in enumerate(rids[:6]):
        s, i = eng.result(rid)
        np.testing.assert_array_equal(np.asarray(i), want[1][r])
    for r, rid in enumerate(rids[6:]):
        s, i = eng.result(rid)
        np.testing.assert_array_equal(np.asarray(i), plain[1][r])
    st = eng.latency_stats()
    assert st["filtered_batches"] >= 1 and "filter_sel_<=10%" in st

    with AsyncQueryEngine(build(), max_batch=16, max_wait_ms=1.0) as a:
        futs = [a.submit(X[i], k=5, where=pred) for i in range(6)]
        futs += [a.submit(X[i], k=5) for i in range(6)]
        got = [f.result(30) for f in futs]
    for r in range(6):
        np.testing.assert_array_equal(np.asarray(got[r][1]), want[1][r])
        np.testing.assert_array_equal(np.asarray(got[6 + r][1]), plain[1][r])


def test_filter_salts_plan_ledger():
    X, meta = _corpus()
    db = VectorDB(engine="flat", metric="cosine")
    db.load(X, meta=meta)
    db.query(X[:4], k=5)
    m0 = db.plan_stats["misses"]
    db.query(X[:4], k=5, where=Eq("tag", 1))   # new filter ctx -> new key
    assert db.plan_stats["misses"] == m0 + 1
    db.query(X[:4], k=5, where=Eq("tag", 1))   # same ctx -> hit
    assert db.plan_stats["misses"] == m0 + 1
    db.query(X[:4], k=5)                        # unfiltered key still cached
    assert db.plan_stats["misses"] == m0 + 1
