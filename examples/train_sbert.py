"""End-to-end driver: train the paper's SBERT-style encoder with the siamese
contrastive objective, under the full production machinery — mesh, sharded
batches, AdamW + clipping + cosine schedule, checkpointing, fault-tolerant
supervisor — then rebuild the vector DB with the trained tower and measure
the retrieval gain.

    PYTHONPATH=src python examples/train_sbert.py              # small, ~2 min CPU
    PYTHONPATH=src python examples/train_sbert.py --preset full --steps 300
        # the ~100M thistle-sbert config (needs real accelerators for speed)
"""
import argparse
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.checkpoint import CheckpointStore
from repro.configs import get_arch
from repro.core import VectorDB
from repro.data import MarcoLike
from repro.ft import FailureInjector, Supervisor, TrainJob
from repro.launch.mesh import make_host_mesh
from repro.models import encoder as enc_lib
from repro.train import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_schedule)


class SbertJob(TrainJob):
    def __init__(self, cfg, data, batch: int, injector=None, lr: float = 3e-4,
                 total_steps: int = 200):
        self.cfg, self.data, self.batch = cfg, data, batch
        self.injector = injector or FailureInjector()
        self.lr, self.total_steps = lr, total_steps
        params = enc_lib.init(cfg, jax.random.PRNGKey(0))
        self.state = {"params": params, "opt": adamw_init(params)}
        self.metrics = []

        @jax.jit
        def train_step(state, batch):
            (loss, m), grads = jax.value_and_grad(
                lambda p: enc_lib.contrastive_loss(p, cfg, batch), has_aux=True)(
                    state["params"])
            grads, gn = clip_by_global_norm(grads, 1.0)
            lr_t = cosine_schedule(state["opt"]["step"], base_lr=lr,
                                   warmup=20, total=total_steps)
            params, opt = adamw_update(grads, state["opt"], state["params"],
                                       lr=lr_t, weight_decay=0.01)
            return {"params": params, "opt": opt}, m

        self._step = train_step

    def _batch(self, step: int):
        gen = self.data.contrastive_batches(self.batch, 1, seq_len=24)
        # deterministic per-step batch (replayable on restart)
        rng = np.random.default_rng(step)
        idx = rng.integers(0, self.data.n_passages, size=self.batch)
        qs = self.data.queries()
        q = np.zeros((self.batch, 24), np.int32)
        q[:, : self.data.query_len] = qs[idx]
        p = self.data.passages[idx][:, :24]
        return {"q_tokens": jnp.asarray(q % self.cfg.vocab_size),
                "q_mask": jnp.asarray(q != 0),
                "p_tokens": jnp.asarray(p % self.cfg.vocab_size),
                "p_mask": jnp.asarray(p != 0)}

    def run_step(self, step: int):
        self.injector.check(step)
        self.state, m = self._step(self.state, self._batch(step))
        m = {k: float(v) for k, v in m.items()}
        self.metrics.append(m)
        if step % 20 == 0:
            print(f"  step {step:4d}  loss {m['loss']:.4f}  "
                  f"in-batch acc {m['in_batch_acc']:.3f}")
        return m

    def save_state(self, store: CheckpointStore, step: int):
        store.save_async(self.state, step)

    def load_state(self, store: CheckpointStore):
        step = store.latest_step()
        if step is None:
            return None
        self.state, _ = store.restore(self.state)
        return step

    def remesh(self, scale):
        return self  # single host example: re-mesh is a no-op


def retrieval_accuracy(params, cfg, data, n_eval: int = 300):
    enc = jax.jit(lambda t, m: enc_lib.encode(params, cfg, t, m))

    def embed(tok_rows):
        out = []
        for i in range(0, len(tok_rows), 128):
            chunk = np.asarray(tok_rows[i:i + 128])[:, :24] % cfg.vocab_size
            pad = 128 - len(chunk)
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            out.append(np.asarray(enc(jnp.asarray(chunk), jnp.asarray(chunk != 0))))
        return np.concatenate(out)[: len(tok_rows)]

    p_emb = embed(data.passages)
    qs = np.zeros((data.n_passages, 24), np.int32)
    qs[:, : data.query_len] = data.queries()
    q_emb = embed(qs)[:n_eval]
    db = VectorDB("flat", metric="cosine").load(p_emb)
    _, ids = db.query(q_emb, k=1)
    return float((np.asarray(ids)[:, 0] == np.arange(n_eval)).mean())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("small", "full"), default="small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/thistle_sbert_ckpt")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject node failures at these steps (FT demo)")
    args = ap.parse_args()

    cfg = (get_arch("thistle-sbert").full if args.preset == "full"
           else get_arch("thistle-sbert").smoke)
    data = MarcoLike(n_passages=2000, vocab_size=cfg.vocab_size, noise=0.25,
                     passage_len=24, seed=0)
    job = SbertJob(cfg, data, args.batch,
                   injector=FailureInjector(fail_at=args.fail_at),
                   total_steps=args.steps)

    acc0 = retrieval_accuracy(job.state["params"], cfg, data)
    print(f"retrieval top-1 accuracy BEFORE training: {acc0:.3f}")

    store = CheckpointStore(args.ckpt_dir, keep=2)
    sup = Supervisor(job, store, total_steps=args.steps, checkpoint_every=50,
                     on_event=lambda k, i: print(f"  [supervisor] {k}: {i}"))
    out = sup.run()
    store.wait()
    print(f"trained {out['final_step']} steps ({out['n_retries']} restarts)")

    acc1 = retrieval_accuracy(job.state["params"], cfg, data)
    print(f"retrieval top-1 accuracy AFTER training:  {acc1:.3f}")
    assert acc1 > acc0, "training must improve retrieval"


if __name__ == "__main__":
    enable_compile_cache()
    main()
