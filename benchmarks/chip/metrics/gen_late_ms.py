"""95th percentile of how late the load generator sent, in ms, by its own
clock: a starved generator is not a fast server."""
from harness.bench import nearest_rank


def read(run):
    late = run.window.late
    return 1e3 * nearest_rank(late, 0.95) if late else None
