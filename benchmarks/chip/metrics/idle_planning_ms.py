"""Query planning's share of ``pending_idle_ms``, in ms per query sent:
device idle while a query waits, the completer is not running, and the
batcher is inside ``db.query`` (probe launch, the visit table's round
trip, the sharing probe, grid dispatch, re-rank launch, eager ops)."""
from harness.spans import idle_ms


def read(run):
    return idle_ms(run, "planning")
