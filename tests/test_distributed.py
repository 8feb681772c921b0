"""Multi-device SPMD tests. jax fixes the device count at first init, so each
test runs a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count.
"""
import os
import subprocess
import sys
import textwrap

import pytest


def run_spmd(code: str, n_dev: int = 8, timeout: int = 600):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_distributed_db_matches_single_device():
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedVectorDB, VectorDB
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        rng = np.random.default_rng(0)
        corpus = rng.normal(size=(1000, 32)).astype(np.float32)
        q = corpus[:7] + 0.01 * rng.normal(size=(7, 32)).astype(np.float32)
        for metric in ['cosine', 'l2', 'dot']:
            dd = DistributedVectorDB(mesh, metric=metric).load(corpus)
            s, ids = dd.query(q, k=5)
            ref = VectorDB('flat', metric=metric).load(corpus)
            rs, rids = ref.query(q, k=5)
            assert (np.asarray(ids) == np.asarray(rids)).all(), metric
            assert np.allclose(np.asarray(s), np.asarray(rs), atol=1e-4), metric
        print('OK')
    """)


def test_distributed_pq_matches_single_host():
    """Sharded codes + replicated LUTs give the single-host pq ranking, at
    <= 1/4 the per-device bytes of the replicated f32 corpus (4 shards)."""
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedPQ, VectorDB
        mesh = jax.make_mesh((4,), ('data',))
        rng = np.random.default_rng(0)
        corpus = rng.normal(size=(2000, 32)).astype(np.float32)
        q = corpus[:16] + 0.01 * rng.normal(size=(16, 32)).astype(np.float32)
        dpq = DistributedPQ(mesh, metric='cosine', m=8).load(corpus)
        s, ids = dpq.query(q, k=10)
        ref = VectorDB('pq', metric='cosine', refine=0).load(corpus)
        rs, rids = ref.query(q, k=10, bucketize=False)
        ids, rids = np.asarray(ids), np.asarray(rids)
        recall = np.mean([len(set(ids[i]) & set(rids[i])) / 10
                          for i in range(16)])
        assert recall >= 0.95, recall
        assert np.allclose(np.sort(np.asarray(s)), np.sort(np.asarray(rs)),
                           atol=1e-4)
        assert dpq.per_device_bytes() <= corpus.nbytes / 4, (
            dpq.per_device_bytes(), corpus.nbytes)
        print('OK', recall)
    """, n_dev=4)


def test_distributed_pq_bf16_luts():
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedPQ
        mesh = jax.make_mesh((2,), ('data',))
        rng = np.random.default_rng(1)
        corpus = rng.normal(size=(512, 16)).astype(np.float32)
        q = corpus[:8]
        f32 = DistributedPQ(mesh, metric='l2').load(corpus)
        bf16 = DistributedPQ(mesh, metric='l2', lut_dtype='bfloat16').load(corpus)
        i0 = np.asarray(f32.query(q, k=5)[1])
        i1 = np.asarray(bf16.query(q, k=5)[1])
        overlap = np.mean([len(set(i0[r]) & set(i1[r])) / 5 for r in range(8)])
        assert overlap >= 0.9, overlap
        print('OK', overlap)
    """, n_dev=2)


def test_distributed_ivf_pq_matches_single_host():
    """Bucket-range-sharded IVF-PQ: 4 shards must rank exactly like the
    single-host bucket path (same seed -> same clustering -> same probes),
    for both metrics, with per-device code bytes ~1/4 of the total."""
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedIVFPQ, VectorDB
        mesh = jax.make_mesh((4,), ('data',))
        rng = np.random.default_rng(0)
        centers = rng.normal(size=(30, 32)).astype(np.float32) * 2.0
        corpus = (centers[rng.integers(0, 30, 2000)]
                  + rng.normal(size=(2000, 32)).astype(np.float32))
        q = corpus[:16] + 0.01 * rng.normal(size=(16, 32)).astype(np.float32)
        for metric in ['cosine', 'l2']:
            dd = DistributedIVFPQ(mesh, metric=metric, nprobe=8).load(corpus)
            s, ids = dd.query(q, k=10)
            ref = VectorDB('ivf_pq', metric=metric, nprobe=8,
                           refine=0).load(corpus)
            rs, rids = ref.query(q, k=10, bucketize=False)
            ids, rids = np.asarray(ids), np.asarray(rids)
            recall = np.mean([len(set(ids[i]) & set(rids[i])) / 10
                              for i in range(16)])
            assert recall >= 0.99, (metric, recall)
            assert np.allclose(np.sort(np.asarray(s)), np.sort(np.asarray(rs)),
                               atol=1e-4), metric
            # codes really are range-sharded: each device holds ~1/4 slab
            shard = dd.codes_bm.addressable_shards[0].data
            assert shard.size <= dd.codes_bm.size / 3.5, (
                shard.size, dd.codes_bm.size)
        print('OK')
    """, n_dev=4)


def test_distributed_ivf_pq_int8_luts():
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedIVFPQ
        mesh = jax.make_mesh((2,), ('data',))
        rng = np.random.default_rng(1)
        centers = rng.normal(size=(10, 16)).astype(np.float32) * 2.0
        corpus = (centers[rng.integers(0, 10, 512)]
                  + rng.normal(size=(512, 16)).astype(np.float32))
        q = corpus[:8]
        f32 = DistributedIVFPQ(mesh, metric='l2', nprobe=4).load(corpus)
        i8 = DistributedIVFPQ(mesh, metric='l2', nprobe=4,
                              lut_dtype='int8').load(corpus)
        i0 = np.asarray(f32.query(q, k=5)[1])
        i1 = np.asarray(i8.query(q, k=5)[1])
        overlap = np.mean([len(set(i0[r]) & set(i1[r])) / 5 for r in range(8)])
        assert overlap >= 0.9, overlap
        print('OK', overlap)
    """, n_dev=2)


def test_distributed_ivf_pq_pad_skip_identical():
    """Each shard names its local pad row to the per-query kernel, which
    skips the off-shard and tail steps retargeted there: the answers equal
    the kernel's that scores every step, and the jnp twin's."""
    run_spmd("""
        import jax, numpy as np
        from repro.core import DistributedIVFPQ
        from repro.kernels import ops as kops
        mesh = jax.make_mesh((2,), ('data',))
        rng = np.random.default_rng(2)
        centers = rng.normal(size=(10, 16)).astype(np.float32) * 2.0
        corpus = (centers[rng.integers(0, 10, 400)]
                  + rng.normal(size=(400, 16)).astype(np.float32))
        q = corpus[:4] + 0.01 * rng.normal(size=(4, 16)).astype(np.float32)
        seen = []
        real = kops.ivf_adc_topk

        def every_step(*a, **kw):
            seen.append(kw.pop('pad_block'))
            return real(*a, **kw)

        for metric in ['cosine', 'l2']:
            kw = dict(metric=metric, nprobe=4, n_clusters=10)
            ker = DistributedIVFPQ(mesh, use_kernel=True, **kw).load(corpus)
            twin = DistributedIVFPQ(mesh, use_kernel=False, **kw).load(corpus)
            s0, i0 = map(np.asarray, ker.query(q, k=5))
            s2, i2 = map(np.asarray, twin.query(q, k=5))
            kops.ivf_adc_topk = every_step
            jax.clear_caches()
            s1, i1 = map(np.asarray, ker.query(q, k=5))
            kops.ivf_adc_topk = real
            jax.clear_caches()
            assert seen[-1] == ker.blocks_per_shard, (seen, metric)
            assert (i0 == i1).all() and (s0 == s1).all(), metric
            assert (i0 == i2).all() and (s0 == s2).all(), metric
            assert (i0 >= 0).all(), metric
        print('OK')
    """, n_dev=2)


def test_two_level_search_matches_flat():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import two_level_search
        from repro.core.flat import flat_search
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        rng = np.random.default_rng(1)
        corpus = jnp.asarray(rng.normal(size=(512, 16)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
        s, i = two_level_search(corpus, q, mesh=mesh, k=9, q_axes=('data',),
                                c_axes=('model',), tile=64, n_valid=500)
        rs, ri = flat_search(corpus, q, metric='dot', k=9,
                             valid=jnp.arange(512) < 500)
        assert (np.asarray(i) == np.asarray(ri)).all()
        assert np.allclose(np.asarray(s), np.asarray(rs), atol=1e-4)
        print('OK')
    """)


def test_sharded_lm_train_step_runs_and_matches():
    """A real sharded train step must run AND match the single-device step."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.launch.shapes import CellSpec
        from repro.launch import steps as S
        from repro.models import transformer
        from repro.train import adamw_init

        # the train step places activations with sharding constraints,
        # which only Auto mesh axes accept (make_mesh defaults to Explicit)
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_arch('stablelm-1.6b').smoke
        inputs = {'tokens': jax.ShapeDtypeStruct((8, 32), jnp.int32),
                  'labels': jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        built = S.make_lm_train(cfg, mesh, 'stablelm-1.6b', inputs,
                                opts={'n_micro': 2, 'int8_opt': False,
                                      'remat': True})
        params = transformer.init(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
        batch = {'tokens': toks, 'labels': toks}

        # single-device reference FIRST (the sharded step donates its state)
        from repro.train import gradient_accumulation
        transformer.ACT_SHARDING = None
        import repro.models.moe as moe_mod
        moe_mod.EP_SHARDING = None
        grads, loss, m = gradient_accumulation(
            lambda p, b: transformer.loss_fn(p, cfg, b, remat=True),
            params, batch, 2)
        loss_ref = float(m['loss'])

        state = {'params': params, 'opt': adamw_init(params)}
        with mesh:
            new_state, metrics = built.jitted()(state, batch)
        loss_sharded = float(metrics['loss'])
        assert abs(loss_sharded - loss_ref) < 5e-2, (loss_sharded, loss_ref)
        print('OK', loss_sharded)
    """)


def test_compressed_allreduce_8way():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train import make_compressed_allreduce
        from repro.train.compress import init_error_feedback
        mesh = jax.make_mesh((8,), ('dp',))
        allreduce = make_compressed_allreduce('dp')
        g = jnp.asarray(np.random.default_rng(0).normal(size=(8, 256)).astype(np.float32))
        e = jnp.zeros((8, 256), jnp.float32)
        def f(g, e):
            out, err = allreduce({'w': g}, {'w': e})
            return out['w'], err['w']
        out, err = jax.shard_map(f, mesh=mesh, in_specs=(P('dp'), P('dp')),
                             out_specs=(P('dp'), P('dp')),
                             check_vma=False)(g, e)
        # each shard's output approximates the mean over shards
        mean = np.mean(np.asarray(g), axis=0)
        got = np.asarray(out)[0]
        scale = np.abs(np.asarray(g)).max()
        assert np.abs(got - mean).max() < 0.02 * scale
        print('OK')
    """)


def test_elastic_remesh_checkpoint_restore():
    """Save sharded on 8 devices, restore resharded onto 4 (elastic)."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointStore

        devs = jax.devices()
        mesh8 = jax.sharding.Mesh(np.array(devs).reshape(8), ('data',))
        mesh4 = jax.sharding.Mesh(np.array(devs[:4]).reshape(4), ('data',))
        tree = {'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        sharded = jax.device_put(tree['w'], NamedSharding(mesh8, P('data', None)))
        with tempfile.TemporaryDirectory() as d:
            store = CheckpointStore(d)
            store.save({'w': sharded}, 1, pspecs={'w': P('data', None)})
            restored, step = store.restore_resharded(
                {'w': jax.ShapeDtypeStruct((8, 8), jnp.float32)}, mesh4,
                lambda key, leaf: NamedSharding(mesh4, P('data', None)))
            assert step == 1
            w = restored['w']
            assert len(w.sharding.device_set) == 4
            np.testing.assert_array_equal(np.asarray(w), np.asarray(tree['w']))
        print('OK')
    """)


def test_gnn_sharded_full_graph_step():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_arch
        from repro.launch.shapes import get_cell
        from repro.launch.steps import build_cell_program
        from repro.models import gnn
        from repro.data import sbm_graph
        from repro.train import adamw_init

        # reduced full-graph cell on a (2, 4) mesh with real data
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        cfg = dataclasses.replace(get_arch('graphsage-reddit').smoke,
                                  d_in=8, n_classes=4)
        g = sbm_graph(64, 4, 8, seed=0)  # 64 nodes divisible by data axis
        E = g['edges'].shape[1]
        pad = (-E) % 2
        edges = np.pad(g['edges'], ((0, 0), (0, pad)))
        params = gnn.init(cfg, jax.random.PRNGKey(0))
        state = {'params': params, 'opt': adamw_init(params)}
        batch = {'feats': jnp.asarray(g['feats']), 'edges': jnp.asarray(edges),
                 'labels': jnp.asarray(g['labels']),
                 'label_mask': jnp.asarray(g['label_mask'])}
        def step(state, batch):
            (loss, m), grads = jax.value_and_grad(
                lambda p: gnn.node_loss(p, cfg, batch), has_aux=True)(state['params'])
            return loss
        with mesh:
            loss = jax.jit(step)(state, batch)
        assert np.isfinite(float(loss))
        print('OK', float(loss))
    """)


def test_partitioned_gnn_matches_baseline():
    """Owner-computes shard_map GraphSAGE == replicated-math baseline."""
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_arch
        from repro.models import gnn
        from repro.models.gnn_partitioned import make_partitioned_loss, partition_edges
        from repro.data import sbm_graph

        mesh = jax.make_mesh((4, 2), ('data', 'model'))
        cfg = dataclasses.replace(get_arch('graphsage-reddit').smoke,
                                  d_in=8, n_classes=4)
        N = 64
        g = sbm_graph(N, 4, 8, seed=0)
        params = gnn.init(cfg, jax.random.PRNGKey(0))

        # baseline (single-logical-device math)
        batch0 = {'feats': jnp.asarray(g['feats']), 'edges': jnp.asarray(g['edges']),
                  'labels': jnp.asarray(g['labels']),
                  'label_mask': jnp.asarray(g['label_mask'])}
        loss0, m0 = gnn.node_loss(params, cfg, batch0)

        # partitioned owner-computes
        edges_p, valid, cap = partition_edges(g['edges'], N, 4)
        loss_fn = make_partitioned_loss(cfg, mesh, ('data',), N)
        batch = {'feats': batch0['feats'], 'edges': jnp.asarray(edges_p),
                 'edge_valid': jnp.asarray(valid),
                 'labels': batch0['labels'], 'label_mask': batch0['label_mask']}
        with mesh:
            (loss1, m1), grads = jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True))(params, batch)
        assert abs(float(loss0) - float(loss1)) < 1e-4, (float(loss0), float(loss1))
        assert abs(float(m0['acc']) - float(m1['acc'])) < 1e-6
        assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(grads))
        print('OK', float(loss0), float(loss1))
    """)
