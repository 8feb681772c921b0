"""95th percentile (nearest rank) over the window's queries of how long
each waited in the serving front before its batch was dispatched, in ms:
from its ``serve.request`` start (``submit``) to the start of its batch's
``serve.dispatch`` (slot wait and batch fill included)."""
from harness.bench import nearest_rank
from harness.spans import window_records


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    dispatch = {r.attrs["batch"]: r.t0 for r in recs
                if r.name == "serve.dispatch"}
    waits = [dispatch[r.attrs["batch"]] - r.t0 for r in recs
             if r.name == "serve.request"]
    if not waits:
        raise ValueError("no serve.request span in the window")
    return 1e-6 * nearest_rank(waits, 0.95)
