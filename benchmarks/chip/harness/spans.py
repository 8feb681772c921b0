"""Read the program's own spans (``repro.obs``) for a run's window.

The program keeps its span records in memory, on the host clock
(``time.perf_counter_ns``); ``run.window`` holds stamps of the same clock
in seconds. A device-trace reader maps a record onto the trace's clock
with the anchor ``Run.pending_ns`` uses: ``bench.window`` opens at
``trace.t0`` on the trace's clock and at ``run.t_window`` on the host's.

A program without ``repro.obs`` has nothing to read: every reader then
returns None.
"""
from __future__ import annotations

from harness import trace as tr

# the batcher's spans whose own time is the serving front's
FRONT = ("serve.slot_wait", "serve.batch_wait", "serve.dispatch")


def window_records(run):
    """The records that end inside or after the window, or None where the
    program keeps none. Raises where the ring has already dropped records
    that may fall in the window."""
    try:
        import repro.obs as obs
    except ModuleNotFoundError as e:
        if e.name != "repro.obs":
            raise
        return None
    recs = obs.spans()
    t0 = run.window.t0 * 1e9
    if not recs:
        raise ValueError("the program recorded no spans")
    if recs[0].t1 > t0:
        raise ValueError(
            f"span ring no longer covers the window: its oldest record "
            f"ends {(recs[0].t1 - t0) * 1e-9:.3f} s after the window opens")
    return [r for r in recs if r.t1 >= t0]


def _layer(rec, by_id):
    """The layer that owns a batcher span's own time: the front's waits
    and dispatch, planning for ``db.query`` and anything inside it, else
    None (unattributed: e.g. a collection outside both)."""
    if rec.name in FRONT:
        return "front"
    while rec is not None:
        if rec.name == "db.query":
            return "planning"
        rec = by_id.get(rec.parent)
    return None


def innermost(spans):
    """Properly nested (start, end, label) spans of one thread -> disjoint
    (start, end, label) pieces, each labelled by the innermost span open
    over it; time under no span is left out."""
    out, stack, t = [], [], None
    for s, e, lab in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, lab))
        t = s
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def intersect(a, b):
    """Two sorted lists of disjoint [start, end] -> their intersection."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append([lo, hi])
            k += 1
    return out


def subtract(a, b):
    """Two sorted lists of disjoint [start, end] -> a less b."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def length(a) -> float:
    return float(sum(e - s for s, e in a))


def idle_split(run):
    """The nanoseconds of ``pending_idle_ms``'s intervals (some query due
    and unanswered, no operation on the first device), split by what the
    host was doing: ``front`` where ``serve.complete`` was open, else by
    the batcher thread's innermost span (``_layer``), else
    ``unattributed``. None where there is no trace or no record."""
    t = run.trace
    if t is None or not t.devices or not run.window.sent:
        return None
    recs = window_records(run)
    if recs is None:
        return None
    off = t.t0 - run.t_window * 1e9

    def on_trace(rs):
        return tr.merge(t._clip((None, r.t0 + off, r.t1 + off) for r in rs))

    want = tr.merge(t._clip((None, s, e) for s, e in run.pending_ns()))
    busy = tr.merge(t._clip(next(iter(t.ops.values()))))
    idle = subtract(want, busy)
    complete = on_trace(r for r in recs if r.name == "serve.complete")
    batcher = {r.thread for r in recs if r.name == "serve.dispatch"}
    by_id = {r.id: r for r in recs}
    pieces = innermost((r.t0 + off, r.t1 + off, _layer(r, by_id))
                       for r in recs if r.thread in batcher)
    rest = subtract(idle, complete)

    def held(layer):
        return length(intersect(rest, tr.merge(
            (lab, s, e) for s, e, lab in pieces if lab == layer)))

    front, planning = held("front"), held("planning")
    return {"front": length(intersect(idle, complete)) + front,
            "planning": planning,
            "unattributed": length(rest) - front - planning}


def idle_ms(run, layer: str):
    """One layer's share of ``pending_idle_ms``, in ms per query sent."""
    split = idle_split(run)
    if split is None:
        return None
    return 1e-6 * split[layer] / run.window.sent
