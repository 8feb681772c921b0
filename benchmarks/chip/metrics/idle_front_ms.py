"""The serving front's share of ``pending_idle_ms``, in ms per query sent:
device idle while a query waits and the completer is syncing, scattering
and resolving (``serve.complete``), or else the batcher waits for an
inflight slot, fills its batch or assembles it (``serve.slot_wait``,
``serve.batch_wait``, ``serve.dispatch`` outside ``db.query``)."""
from harness.spans import idle_ms


def read(run):
    return idle_ms(run, "front")
