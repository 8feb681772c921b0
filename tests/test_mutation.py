"""Mutation lifecycle: insert/delete/upsert/compact through every layer.

The load-bearing test is the mutation FUZZ: a random interleaving of
insert / delete / upsert / query / compact on ``ivf_pq`` checked against a
brute-force dict oracle after every step, with nprobe = C and an exhaustive
exact re-rank so the engine's answer must EXACTLY equal brute force over
the live rows — any slot the layout mishandles (stale tombstone, lost
spill block, wrong id after compaction) shows up as a wrong id, not a
recall wiggle. A snapshot/restore round-trip of the mutated index must
then preserve results bit-for-bit.

A deterministic seeded version always runs (the CI container may lack
hypothesis); the hypothesis property test widens the interleaving space.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import VectorDB
from repro.core.ivf import BlockListLayout
from repro.serve import QueryEngine


def _oracle_topk(vecs: dict, q: np.ndarray, k: int, metric: str):
    """Brute-force top-k over a {id: vector} dict, engine score convention."""
    ids = np.asarray(sorted(vecs))
    M = np.stack([vecs[i] for i in ids]).astype(np.float64)
    qq = q.astype(np.float64)
    if metric == "cosine":
        M = M / np.linalg.norm(M, axis=-1, keepdims=True)
        qq = qq / np.linalg.norm(qq, axis=-1, keepdims=True)
        s = qq @ M.T
    elif metric == "dot":
        s = qq @ M.T
    else:
        s = -(np.sum(qq**2, -1)[:, None] - 2 * qq @ M.T + np.sum(M**2, -1)[None])
    order = np.argsort(-s, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(s, order, axis=-1), ids[order]


def _check_exact(db, vecs: dict, q: np.ndarray, k: int, metric: str, ctx=""):
    """Engine top-k must exactly agree with the oracle: same live ids, same
    scores. Ties (and f32-vs-f64 near-ties) are tolerated as swaps WITHIN
    score tolerance, never as a wrong member."""
    s, ids = db.query(q, k=k)
    s, ids = np.asarray(s), np.asarray(ids)
    kk = min(k, len(vecs))
    assert s.shape[1] in (k, kk) or kk == 0, (s.shape, k, kk, ctx)
    if kk == 0:
        assert s.shape[1] == 0
        return
    ref_s, ref_ids = _oracle_topk(vecs, q, kk, metric)
    tol = 1e-3 * max(1.0, float(np.abs(ref_s).max()))
    for r in range(q.shape[0]):
        got = ids[r, :kk]
        assert len(set(got.tolist())) == kk, (ctx, r, got)
        for j, i in enumerate(got):
            assert int(i) in vecs, (ctx, r, j, i)  # never a dead/pad id
            # returned score must be the true score of that id
            one_s, _ = _oracle_topk({int(i): vecs[int(i)]}, q[r:r + 1], 1,
                                    metric)
            assert abs(s[r, j] - one_s[0, 0]) <= tol, (ctx, r, j)
        # and the set must be a true top-k up to score ties at the boundary
        boundary = ref_s[r, kk - 1]
        assert s[r, :kk].min() >= boundary - tol, (ctx, r)
        clear = ref_s[r] > boundary + tol  # members strictly above the tie
        assert set(ref_ids[r][clear].tolist()) <= set(got.tolist()), (ctx, r)
    # tail of a shorter-than-k result is well-formed padding
    if s.shape[1] > kk:
        assert np.all(np.isneginf(s[:, kk:])) and np.all(ids[:, kk:] == -1)


def _run_fuzz(seed: int, metric: str, n_steps: int = 30, check_every: int = 1):
    rng = np.random.default_rng(seed)
    d, n0 = 12, 60
    corpus = rng.normal(size=(n0, d)).astype(np.float32)
    # nprobe covers every cluster and refine covers every candidate, so the
    # engine must return EXACT brute force over live rows
    db = VectorDB("ivf_pq", metric=metric, n_clusters=5, nprobe=5, m=4,
                  ksub=32, refine=4096, block_size=8,
                  compact_threshold=0.5).load(corpus)
    vecs = {i: corpus[i] for i in range(n0)}
    q = rng.normal(size=(3, d)).astype(np.float32)
    _check_exact(db, vecs, q, 8, metric, "after load")
    for step in range(n_steps):
        op = rng.choice(["insert", "delete", "upsert", "compact"],
                        p=[0.45, 0.25, 0.2, 0.1])
        if op == "insert":
            rows = rng.normal(size=(int(rng.integers(1, 6)), d)).astype(np.float32)
            ids = db.insert(rows)
            vecs.update({int(i): r for i, r in zip(ids, rows)})
        elif op == "delete" and vecs:
            take = rng.choice(sorted(vecs), size=min(len(vecs),
                                                     int(rng.integers(1, 5))),
                              replace=False)
            db.delete(take)
            for i in take:
                vecs.pop(int(i))
        elif op == "upsert":
            ids = rng.integers(0, db.index.next_id, size=2)
            ids = np.unique(ids)
            rows = rng.normal(size=(ids.size, d)).astype(np.float32)
            db.upsert(rows, ids)
            vecs.update({int(i): r for i, r in zip(ids, rows)})
        else:
            db.compact()
        if step % check_every == 0:
            _check_exact(db, vecs, q, 8, metric, f"step {step} ({op})")
    assert db.n == len(vecs)
    return db, vecs, q


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_mutation_fuzz_matches_oracle(metric):
    """Acceptance: any interleaving of insert/delete/upsert/compact keeps
    ivf_pq top-k exactly equal to the brute-force dict oracle."""
    _run_fuzz(seed=0, metric=metric)


def test_mutated_snapshot_roundtrip_bit_for_bit(tmp_path):
    """Acceptance: a snapshot of a mutated index restores to bit-identical
    query results — tombstone state persists (dead ids stay retired) and
    the generation stamp survives."""
    db, vecs, q = _run_fuzz(seed=3, metric="l2", n_steps=20, check_every=5)
    s0, i0 = db.query(q, k=8)
    dead = next(i for i in range(db.index.next_id) if i not in vecs)
    db.save_index(str(tmp_path), step=1)
    db2 = VectorDB("ivf_pq", metric="l2", nprobe=5,
                   block_size=8).restore_index(str(tmp_path))
    s1, i1 = db2.query(q, k=8)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    assert db2.generation == db.generation > 0
    assert db2.n == len(vecs)
    assert not db2.index.layout.contains(dead)  # tombstones persisted
    # the restored index keeps mutating correctly
    _check_exact(db2, vecs, q, 8, "l2", "restored")
    db2.delete([sorted(vecs)[0]])
    vecs.pop(sorted(vecs)[0])
    _check_exact(db2, vecs, q, 8, "l2", "restored+delete")
    # the manifest meta stamp is readable without loading leaves
    meta = ckpt.load_meta(str(tmp_path))
    assert meta["engine"] == "ivf_pq" and meta["generation"] == db.generation


def test_mutation_fuzz_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(0, 2**16),
           metric=st.sampled_from(["l2", "cosine"]))
    @settings(max_examples=8, deadline=None)
    def run(seed, metric):
        _run_fuzz(seed=seed, metric=metric, n_steps=12, check_every=3)

    run()


# --------------------------------------------------------- other engines

@pytest.mark.parametrize("engine", ["flat", "pq", "ivf"])
@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_engines_share_mutation_protocol(rng, engine, metric):
    """flat / pq / ivf implement the same MutableIndex protocol, and in an
    exhaustive configuration (probe-all nprobe, rerank-all refine) each is
    EXACT — so the dict-oracle check applies to all of them."""
    d = 16
    corpus = rng.normal(size=(20, d)).astype(np.float32)
    kwargs = {"pq": dict(m=4, ksub=16, refine=4096),
              "ivf": dict(n_clusters=4, nprobe=4)}.get(engine, {})
    db = VectorDB(engine, metric=metric, **kwargs).load(corpus)
    vecs = {i: corpus[i] for i in range(20)}
    new = rng.normal(size=(6, d)).astype(np.float32)
    ids = db.insert(new)
    vecs.update({int(i): r for i, r in zip(ids, new)})
    db.delete([0, 3, 21])
    for i in (0, 3, 21):
        vecs.pop(i)
    up = rng.normal(size=(2, d)).astype(np.float32)
    db.upsert(up, np.array([5, 0]))  # id 0 resurrects
    vecs.update({5: up[0], 0: up[1]})
    db.compact()
    assert db.n == len(vecs) == db.index.size
    q = np.stack([vecs[7], vecs[22]]).astype(np.float32)
    _check_exact(db, vecs, q, 8, metric, engine)
    # dead ids never come back at any k
    s, ids = db.query(q, k=len(vecs))
    assert 3 not in set(np.asarray(ids).reshape(-1).tolist())


def test_insert_and_upsert_id_validation(rng):
    db = VectorDB("flat").load(rng.normal(size=(10, 4)).astype(np.float32))
    with pytest.raises(ValueError, match="fresh"):
        db.insert(np.ones((1, 4), np.float32), ids=[5])
    with pytest.raises(ValueError, match="existing"):
        db.upsert(np.ones((1, 4), np.float32), ids=[99])
    with pytest.raises(ValueError, match="duplicate"):
        db.insert(np.ones((2, 4), np.float32), ids=[12, 12])
    ids = db.insert(np.ones((1, 4), np.float32), ids=[17])  # fresh, gap ok
    assert ids.tolist() == [17] and db.index.next_id == 18
    assert db.n == 11  # the gap ids 10..16 never existed


def test_pq_staleness_counter_flags_retrain(rng):
    corpus = rng.normal(size=(40, 8)).astype(np.float32)
    db = VectorDB("pq", m=4, ksub=16, retrain_threshold=0.25).load(corpus)
    assert db.index.stale_fraction == 0.0 and not db.index.needs_retrain
    db.insert(rng.normal(size=(5, 8)).astype(np.float32))
    assert not db.index.needs_retrain  # 5/45 stale
    db.insert(rng.normal(size=(10, 8)).astype(np.float32))
    assert db.index.needs_retrain  # 15/55 > 0.25
    db.load(np.asarray(db.index._corpus.data[: db.index.next_id]))
    assert db.index.stale_fraction == 0.0  # retrain resets the counter


# ---------------------------------------------------- empty / deleted-out

def test_query_empty_and_fully_deleted_index(rng):
    """Satellite: an empty or fully-deleted index returns a well-formed
    (Q, 0) result instead of a reshape error; never-loaded still raises."""
    with pytest.raises(RuntimeError):
        VectorDB("flat").query(np.zeros(4), k=1)
    db = VectorDB("flat").load(np.zeros((0, 8), np.float32))
    s, i = db.query(np.zeros((3, 8), np.float32), k=5)
    assert s.shape == (3, 0) and i.shape == (3, 0)
    ids = db.insert(rng.normal(size=(4, 8)).astype(np.float32))
    s, i = db.query(np.zeros((1, 8), np.float32), k=2)
    assert s.shape == (1, 2)
    db.delete(ids)
    s, i = db.query(np.zeros((2, 8), np.float32), k=5)
    assert s.shape == (2, 0) and i.shape == (2, 0)
    # the quantized engine fully deleted behaves too
    db = VectorDB("ivf_pq", m=4, ksub=8, block_size=8).load(
        rng.normal(size=(20, 8)).astype(np.float32))
    db.delete(np.arange(20))
    s, i = db.query(np.zeros((2, 8), np.float32), k=3)
    assert s.shape == (2, 0)


# --------------------------------------------------- plans stay compiled

def test_steady_state_inserts_do_not_recompile(rng):
    """Acceptance: plan-ledger miss count is FLAT across >= 100 insert
    batches inside one pre-reserved capacity bucket — mutation changes
    array contents, not compiled shapes."""
    corpus = rng.normal(size=(256, 16)).astype(np.float32)
    db = VectorDB("ivf_pq", n_clusters=8, nprobe=4, m=4, ksub=16, refine=0,
                  block_size=8).load(corpus)
    db.reserve(256, 8)  # headroom: rows AND per-cluster spill blocks
    eng = QueryEngine(db, max_batch=4, max_wait_ms=0.0)
    eng.submit(corpus[0], k=4)
    eng.pump(force=True)
    misses0 = eng.latency_stats()["plan_misses"]
    key0 = db.index.shape_key
    for i in range(110):
        eng.submit_write("insert",
                         rng.normal(size=(2, 16)).astype(np.float32))
        eng.submit(corpus[i % 256], k=4)
        eng.pump(force=True)
    st = eng.latency_stats()
    assert db.index.shape_key == key0  # stayed inside the bucket
    assert st["plan_misses"] == misses0, st  # NOT one per insert batch
    assert st["plan_hits"] >= 110
    assert st["write_inserts"] == 220


def test_bucket_overflow_is_counted_as_plan_miss(rng):
    """When an insert DOES overflow a capacity bucket, the next query is a
    genuine retrace and the ledger must say miss, not lie hit."""
    corpus = rng.normal(size=(32, 8)).astype(np.float32)
    db = VectorDB("flat").load(corpus)
    db.query(corpus[:4], k=3)
    assert db.plan_stats == {"hits": 0, "misses": 1}
    db.query(corpus[:4], k=3)
    assert db.plan_stats == {"hits": 1, "misses": 1}
    gen0 = db.plan_generation
    db.insert(rng.normal(size=(64, 8)).astype(np.float32))  # 32 -> 96 rows
    assert db.plan_generation == gen0 + 1
    db.query(corpus[:4], k=3)
    assert db.plan_stats == {"hits": 1, "misses": 2}


# ----------------------------------------------------------- serve layer

def test_serve_read_your_writes_within_pump(rng):
    corpus = rng.normal(size=(16, 8)).astype(np.float32)
    target = np.full((8,), 2.0, np.float32)
    db = VectorDB("flat", metric="l2").load(corpus)
    eng = QueryEngine(db, max_batch=64, max_wait_ms=0.0)
    r_before = eng.submit(target, k=1)
    w = eng.submit_write("insert", target[None])
    r_after = eng.submit(target, k=1)
    # one pump: the read batch must stop at the write, not leap over it
    assert eng.pump(force=True) == 1
    eng.drain()
    _, before_ids = eng.result(r_before)
    _, after_ids = eng.result(r_after)
    kind, new_ids = eng.result(w)
    assert kind == "insert" and new_ids.tolist() == [16]
    assert before_ids[0] != 16  # submitted before the write: can't see it
    assert after_ids[0] == 16   # submitted after: must see it
    st = eng.latency_stats()
    assert st["write_inserts"] == 1
    eng.submit_write("delete", ids=new_ids)
    eng.submit_write("compact")
    eng.drain()
    st = eng.latency_stats()
    assert st["write_deletes"] == 1 and st["write_compactions"] == 1


# ------------------------------------------------------------ mesh front

def test_distributed_ivf_pq_mutates_like_single_host(rng):
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    from repro.core import DistributedIVFPQ

    corpus = rng.normal(size=(128, 16)).astype(np.float32)
    kw = dict(n_clusters=6, nprobe=6, m=4, ksub=16, block_size=8, seed=0)
    dd = DistributedIVFPQ(mesh, metric="cosine", **kw).load(corpus)
    ref = VectorDB("ivf_pq", metric="cosine", refine=0, **kw).load(corpus)
    new = rng.normal(size=(20, 16)).astype(np.float32)
    for db in (dd, ref):
        db.insert(new)
        db.delete(np.arange(0, 40, 4))
        db.upsert(new[:3] * 2.0, np.array([130, 7, 141]))
    q = rng.normal(size=(5, 16)).astype(np.float32)
    s0, i0 = ref.query(q, k=8, bucketize=False)
    s1, i1 = dd.query(q, k=8, bucketize=False)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)
    assert dd.size == ref.index.size
    for db in (dd, ref):
        db.compact()
    s2, i2 = dd.query(q, k=8, bucketize=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# --------------------------------------------------------- layout layer

def test_block_layout_append_spill_and_slack(rng):
    lay = BlockListLayout.from_assign(np.zeros(5, np.int64), 3, blk=8,
                                      payload=rng.integers(
                                          0, 255, (5, 4)).astype(np.uint8))
    assert lay.bcnt[0] == 1 and lay.tail_fill[0] == 5
    lay.insert_rows(np.arange(5, 8), np.zeros(3, np.int64),
                    np.zeros((3, 4), np.uint8))
    assert lay.bcnt[0] == 1 and lay.tail_fill[0] == 8  # filled, no spill
    lay.insert_rows(np.array([8]), np.array([0]), np.zeros((1, 4), np.uint8))
    assert lay.bcnt[0] == 2 and lay.tail_fill[0] == 1  # spilled
    # tail slack invariant: every cluster wastes <= blk-1 slots
    for c in range(3):
        rows = lay.block_table[c, : lay.bcnt[c]]
        used = (lay.slots[rows] >= 0).sum()
        assert lay.bcnt[c] * lay.blk - used <= lay.blk - 1


def test_block_layout_compact_keeps_shapes(rng):
    assign = rng.integers(0, 4, size=50)
    lay = BlockListLayout.from_assign(assign, 4, blk=8,
                                      payload=rng.integers(
                                          0, 255, (50, 4)).astype(np.uint8))
    key = lay.shape_key
    lay.delete_rows(np.arange(0, 50, 2))
    assert lay.tombstone_fraction == pytest.approx(0.5)
    stats = lay.compact()
    assert stats["dropped_tombstones"] == 25
    assert lay.shape_key == key  # compaction never changes device shapes
    assert lay.tombstone_fraction == 0.0 and lay.live == 25
    # every live id still findable, payload intact
    for i in range(1, 50, 2):
        assert lay.contains(i)


@pytest.mark.parametrize("longest,spp", [(1, 1), (3, 4), (8, 8), (9, 10),
                                         (116, 128), (128, 128), (129, 160),
                                         (168, 192)])
def test_block_layout_steps_per_probe_ladder(longest, spp):
    """steps_per_probe doubles to 8 blocks, then climbs quarter octaves: a
    longest list one block past a power of two (129) widens the visit row
    to 160 steps, not 256."""
    blk = 8
    assign = np.r_[np.zeros(longest * blk - 3), np.ones(5)].astype(np.int64)
    lay = BlockListLayout.from_assign(assign, 2, blk=blk)
    assert lay.bcnt.max() == longest and lay.steps_per_probe == spp
    assert lay.block_table.shape == (2, spp)
    assert (lay.block_table[0, :longest] >= 0).all()
    assert (lay.block_table[0, longest:] == -1).all()


def test_block_layout_steps_per_probe_grows_one_rung_at_a_time():
    """Appends that lengthen a list one block at a time move
    steps_per_probe up one rung when the list outgrows it, never past 25%
    above the longest list, through few distinct widths."""
    lay = BlockListLayout.from_assign(np.zeros(8, np.int64), 1, blk=8)
    seen = []
    for i in range(1, 300):
        lay.insert_rows(np.arange(8 * i, 8 * i + 8), np.zeros(8, np.int64))
        longest, spp = int(lay.bcnt[0]), lay.steps_per_probe
        assert longest <= spp <= max(8, 1.25 * longest)
        if not seen or seen[-1] != spp:
            seen.append(spp)
    assert seen == [2, 4, 8, 10, 12, 14, 16, 20, 24, 28, 32, 40, 48, 56, 64,
                    80, 96, 112, 128, 160, 192, 224, 256, 320]


def test_steps_per_probe_width_leaves_answers_unchanged(rng):
    """A wider visit row only adds steps on the all-pad block: the served
    scores and ids are the same at the ladder's width and a rung up."""
    corpus = rng.normal(size=(640, 16)).astype(np.float32)
    q = rng.normal(size=(6, 16)).astype(np.float32)
    db = VectorDB("ivf_pq", n_clusters=4, nprobe=3, m=4, ksub=16, refine=8,
                  block_size=8).load(corpus)
    longest = int(db.index.layout.bcnt.max())
    s0, i0 = db.query(q, k=5)
    spp0 = db.index.spp
    assert 8 < longest <= spp0 < 2 * longest
    db.reserve(0, spp0 - longest + 1)
    s1, i1 = db.query(q, k=5)
    assert db.index.spp > spp0
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


def test_sharded_alloc_policy_prefers_home_shard():
    """DistributedIVFPQ routes a cluster's spilled blocks onto the shard
    already owning its slab; a full home shard falls back gracefully and a
    blockless cluster takes the densest free row."""
    from repro.core import DistributedIVFPQ

    dd = DistributedIVFPQ.__new__(DistributedIVFPQ)  # policy needs no mesh
    dd.n_shards = 4
    lay = BlockListLayout(2, blk=8, row_multiple=4)
    lay._reserve_rows(32)  # capacity 32 -> 8 storage rows per shard
    lay.block_table[0, 0] = 9  # cluster 0's last block lives on shard 1
    lay.bcnt[0] = 1
    dd.layout = lay
    assert dd._alloc_policy(0, {3, 12, 20, 30}) == 12  # shard 1's free row
    assert dd._alloc_policy(1, {3, 12, 20, 30}) == 3   # no home yet
    assert dd._alloc_policy(0, {3, 20}) == 3           # home full: fallback
