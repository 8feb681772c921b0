"""Recall@k of the window's answers against the exact scan: the share of
each query's exact top-k that its answer holds, over every well-formed
answer (the reference's ``recall_miss``, the other way up)."""


def read(run):
    return 1.0 - run.checks["recall_miss"]
