"""95th percentile (nearest rank) of the latency of every query due in the
window, in ms, from the instant it was due to its answer; a query never
answered counts as infinitely late."""
from harness.bench import nearest_rank


def read(run):
    return nearest_rank(run.latencies_ms(), 0.95)
