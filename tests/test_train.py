"""Optimizer / accumulation / compression substrate tests."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_schedule, dequantize_blockwise, global_norm,
                         gradient_accumulation, quantize_blockwise)
from repro.train.compress import compressed_bytes, init_error_feedback
from repro.train.optim import adam_state_bytes


def _quadratic_problem():
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (6, 4))
    X = jax.random.normal(jax.random.PRNGKey(1), (128, 6))
    Y = X @ W

    def loss_fn(p, b):
        l = jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
        return l, {"loss": l}

    return {"w": jnp.zeros((6, 4))}, {"x": X, "y": Y}, loss_fn


@pytest.mark.parametrize("int8", [False, True])
def test_adamw_converges(int8):
    params, batch, loss_fn = _quadratic_problem()
    state = adamw_init(params, int8_state=int8)
    loss = None
    for _ in range(250):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        params, state = adamw_update(grads, state, params, lr=3e-2,
                                     weight_decay=0.0, int8_state=int8)
    assert float(loss) < 1e-3, float(loss)


def test_int8_state_matches_f32_early():
    """First steps of int8-state Adam track f32 Adam closely."""
    params, batch, loss_fn = _quadratic_problem()
    p8, pf = params, params
    s8 = adamw_init(params, int8_state=True)
    sf = adamw_init(params, int8_state=False)
    for _ in range(5):
        (_, _), g8 = jax.value_and_grad(loss_fn, has_aux=True)(p8, batch)
        (_, _), gf = jax.value_and_grad(loss_fn, has_aux=True)(pf, batch)
        p8, s8 = adamw_update(g8, s8, p8, lr=1e-2, weight_decay=0.0, int8_state=True)
        pf, sf = adamw_update(gf, sf, pf, lr=1e-2, weight_decay=0.0, int8_state=False)
    np.testing.assert_allclose(np.asarray(p8["w"]), np.asarray(pf["w"]),
                               atol=5e-3, rtol=5e-2)


def test_adam_state_bytes_planning():
    n = 671_000_000_000
    assert adam_state_bytes(n, int8=False) == n * 8
    assert adam_state_bytes(n, int8=True) < n * 2.1  # ~4x smaller


def test_grad_accum_matches_full_batch():
    params, batch, loss_fn = _quadratic_problem()
    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (6, 4))}
    g1, l1, _ = gradient_accumulation(loss_fn, params, batch, 1)
    g4, l4, _ = gradient_accumulation(loss_fn, params, batch, 4)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g4["w"]),
                               rtol=1e-4, atol=1e-5)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones((10,)) * 3.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(np.sqrt(90.0), rel=1e-5)


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.asarray(s), base_lr=1.0, warmup=10, total=100))
           for s in [0, 5, 10, 55, 100, 200]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0)
    assert lrs[3] < 1.0
    assert lrs[4] == pytest.approx(0.1, abs=1e-6)  # min_ratio floor
    assert lrs[5] == pytest.approx(0.1, abs=1e-6)


def test_blockwise_quant_roundtrip(rng):
    tree = {"a": jnp.asarray(rng.normal(size=(1000,)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(37, 5)).astype(np.float32) * 100)}
    q = quantize_blockwise(tree)
    back = dequantize_blockwise(q)
    for k in tree:
        err = np.abs(np.asarray(back[k]) - np.asarray(tree[k]))
        scale = np.abs(np.asarray(tree[k])).max()
        assert err.max() <= scale / 127.0 + 1e-6
    assert compressed_bytes(tree) < tree["a"].size * 4  # < f32 wire size


def test_compressed_allreduce_error_feedback():
    """Error feedback keeps the long-run mean of compressed psums unbiased."""
    from repro.train import make_compressed_allreduce
    # single-device 'mesh': pmean over a size-1 axis via vmap-style shard_map
    # -> exercise quantize/err logic directly
    allreduce = make_compressed_allreduce("i")

    grads = {"w": jnp.asarray(np.linspace(-1, 1, 256), jnp.float32)}
    err = init_error_feedback(grads)

    def one(g, e):
        return jax.shard_map(lambda gg, ee: allreduce(gg, ee),
                         mesh=jax.make_mesh((1,), ("i",)),
                         in_specs=(jax.sharding.PartitionSpec(),) * 2,
                         out_specs=(jax.sharding.PartitionSpec(),) * 2,
                         check_vma=False)(g, e)

    acc = jnp.zeros_like(grads["w"])
    for _ in range(20):
        out, err = one(grads, err)
        acc = acc + out["w"]
    mean = np.asarray(acc / 20)
    np.testing.assert_allclose(mean, np.asarray(grads["w"]), atol=2e-3)
