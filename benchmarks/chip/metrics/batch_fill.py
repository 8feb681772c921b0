"""Real queries over the plan-bucket rows the serving front dispatched, in
%: the rest are pad rows that the front adds to reach a compiled bucket."""


def read(run):
    rows = sum(c[2] for c in run.calls)
    return 100.0 * run.window.sent / rows if rows else None
