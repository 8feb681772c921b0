"""The rest of ``pending_idle_ms``, in ms per query sent: device idle while
a query waits and neither the completer nor any front or planning span of
the batcher is open. The batcher is between spans, blocked for a query
the generator has not sent yet, or in a collection outside ``db.query``
(``host.gc``). The three ``idle_*`` metrics sum to ``pending_idle_ms``."""
from harness.spans import idle_ms


def read(run):
    return idle_ms(run, "unattributed")
