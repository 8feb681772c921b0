"""Recsys retrieval through the vector DB: train FM on click logs, decompose
its score into exact MIPS vectors, and serve 1-vs-many retrieval — the
``retrieval_cand`` path (1 query against the full item corpus).

    PYTHONPATH=src python examples/recsys_retrieval.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.core import VectorDB
from repro.data import ClickLogs
from repro.models import recsys
from repro.train import adamw_init, adamw_update


def main():
    cfg = get_arch("fm").smoke
    logs = ClickLogs(cfg)
    params = recsys.init(cfg, jax.random.PRNGKey(0))
    state = adamw_init(params)

    @jax.jit
    def step(params, state, batch):
        (loss, m), grads = jax.value_and_grad(
            lambda p: recsys.bce_loss(p, cfg, batch), has_aux=True)(params)
        params, state = adamw_update(grads, state, params, lr=3e-3,
                                     weight_decay=1e-5)
        return params, state, m

    for i in range(150):
        batch = {k: jnp.asarray(v) for k, v in logs.batch(512, step=i).items()}
        params, state, m = step(params, state, batch)
        if i % 50 == 0:
            print(f"  step {i:3d}  bce {float(m['loss']):.4f} "
                  f"acc {float(m['acc']):.3f}")

    # --- decompose: item tower -> MIPS corpus; user tower -> query
    item_field = 0
    n_items = cfg.field_vocab_sizes()[item_field]
    item_vecs = recsys.fm_item_vectors(params, cfg,
                                       jnp.arange(n_items), item_field)
    db = VectorDB("flat", metric="dot").load(np.asarray(item_vecs))
    print(f"item corpus: {item_vecs.shape} (exact FM dot decomposition)")

    batch = {k: jnp.asarray(v) for k, v in logs.batch(4, step=999).items()}
    user_vecs = recsys.fm_user_vector(params, cfg, batch, item_field)
    scores, ids = db.query(np.asarray(user_vecs), k=5)
    for u in range(4):
        print(f"  user {u}: top items {np.asarray(ids[u]).tolist()} "
              f"scores {np.round(np.asarray(scores[u]), 3).tolist()}")

    # verify MIPS ranking == exact full-model ranking for user 0
    full_scores = []
    offs = recsys.field_offsets(cfg)
    for item in range(n_items):
        b2 = {k: v[:1] for k, v in batch.items()}
        b2["sparse_idx"] = b2["sparse_idx"].at[:, item_field].set(
            item + int(offs[item_field]))
        full_scores.append(float(recsys.fm_forward(params, cfg, b2)[0]))
    exact_top = int(np.argmax(full_scores))
    print(f"exact re-scored top item for user 0: {exact_top} "
          f"(MIPS said {int(ids[0, 0])})")
    assert exact_top == int(ids[0, 0]), "FM MIPS decomposition must be exact"


if __name__ == "__main__":
    enable_compile_cache()
    main()
