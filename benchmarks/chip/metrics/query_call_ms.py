"""Mean host milliseconds inside one ``VectorDB.query`` call: planning,
the probe stage's visit-table round trip, grid dispatch and the launch of
the re-rank (the device work after the round trip runs asynchronously)."""


def read(run):
    if not run.calls:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in run.calls) / len(run.calls)
