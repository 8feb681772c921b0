"""Device idle time while a query waits, in ms per query sent: the part of
the traced window in which some query was due and not yet answered, yet
no operation ran on the device. It is the host holding the chip back
(batching wait, planning, the visit table's round trip, the completer);
a faster kernel shortens the waits but leaves this as it is."""


def read(run):
    t = run.trace
    if t is None or not t.devices or not run.window.sent:
        return None
    return 1e-6 * t.idle_within(run.pending_ns()) / run.window.sent
