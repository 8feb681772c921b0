"""Faults planted in the timed path, to show that ``correct`` fails.

Each fault breaks a whole run underneath: the front's batches (a proxy in
place of the database the window drives) or the ADC stage below the exact
re-rank (a wrapper around ``kernels.ops.ivf_adc_topk``, the dispatch that
``VectorDB.query`` calls for its candidates). ``plant(name)`` returns the
proxy class the run should use, with the ADC wrapper in force until the
context closes. ``tests/test_faults.py`` runs each at a tiny size on the
CPU; ``calibrate.py --fault`` reads them at the cell's own size.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from harness.bench import DBProxy


class HalfBatch(DBProxy):
    """Half of each batch left out: its rows are answered with the other
    half's queries."""

    def query(self, q, k=10, **kw):
        n = q.shape[0]
        h = (n + 1) // 2
        return super().query(jnp.concatenate([q[:h], q[:n - h]]), k=k, **kw)


class AlteredAnswer(DBProxy):
    """One id of every answer altered where it is produced."""

    def query(self, q, k=10, **kw):
        s, i = super().query(q, k=k, **kw)
        return s, i.at[:, -1].set((i[:, -1] + 1) % self._db.n)


class Dropped(DBProxy):
    """Every third batch never answered."""

    seen = 0

    def query(self, q, k=10, **kw):
        self.seen += 1
        if self.seen % 3 == 0:
            raise RuntimeError("batch dropped")
        return super().query(q, k=k, **kw)


def _quarter_lists(visit, luts, kw):
    """Candidates drawn from only the nearest quarter of each query's
    probed lists: the rest of its visit table is pad."""
    spp = kw["steps_per_probe"]
    keep = max(1, visit.shape[1] // spp // 4) * spp
    return jnp.asarray(visit).at[:, keep:].set(kw["pad_block"]), luts


def _rolled_lut(visit, luts, kw):
    """Each subspace's codes scored with the next subspace's table."""
    return visit, jnp.roll(luts, 1, axis=-2)


ADC_FAULTS = {"quarter_lists": _quarter_lists, "rolled_lut": _rolled_lut}
PROXY_FAULTS = {"half_batch": HalfBatch, "altered_answer": AlteredAnswer,
                "dropped": Dropped}
NAMES = tuple(PROXY_FAULTS) + tuple(ADC_FAULTS)


@contextlib.contextmanager
def plant(name: str | None):
    """The proxy class for a run with fault ``name`` (None: no fault)."""
    if name is None or name in PROXY_FAULTS:
        yield PROXY_FAULTS.get(name, DBProxy)
        return
    from repro.kernels import ops
    fault, sound = ADC_FAULTS[name], ops.ivf_adc_topk

    def broken(codes, ids, visit, luts, **kw):
        visit, luts = fault(visit, luts, kw)
        return sound(codes, ids, visit, luts, **kw)

    ops.ivf_adc_topk = broken
    try:
        yield DBProxy
    finally:
        ops.ivf_adc_topk = sound
