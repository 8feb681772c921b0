"""The load generator, one for every traffic mix: open loop.

Each query is sent at its own due time (Poisson arrivals at the cell's
fixed rate), whatever the server is doing, and timed from that due time.
"""
from __future__ import annotations

import time
from concurrent.futures import wait

import numpy as np

# seconds a window waits past its close for answers still due
ANSWER_GRACE_S = 60.0


class Window:
    """What one measured window sent and got back, query by query."""

    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.late = []  # seconds each send left after its due time
        self.futures = [None] * n
        self.t0 = self.t1 = 0.0
        self.sent = 0

    def answers(self):
        """(scores, ids) per sent query, None where it never came."""
        out = []
        for f in self.futures[:self.sent]:
            if f is None or not f.done() or f.cancelled() or f.exception():
                out.append(None)
            else:
                s, i = f.result()
                out.append((np.asarray(s), np.asarray(i)))
        return out

    def _track(self, j, fut):
        self.futures[j] = fut

        def on_done(_f, j=j):
            self.done[j] = time.perf_counter()
        fut.add_done_callback(on_done)


def open_loop(eng, queries: np.ndarray, arrivals: np.ndarray, k: int,
              lead_s: float = 0.05) -> Window:
    """Send query j at ``t0 + arrivals[j]``; wait for every answer."""
    win = Window(len(queries))
    win.t0 = time.perf_counter() + lead_s
    for j, (q, a) in enumerate(zip(queries, arrivals)):
        due = win.t0 + a
        gap = due - time.perf_counter()
        if gap > 0:
            time.sleep(gap)
        win.late.append(max(0.0, time.perf_counter() - due))
        win.due[j] = due
        win._track(j, eng.submit(q, k=k))
        win.sent = j + 1
    win.t1 = win.t0 + (arrivals[-1] if len(arrivals) else 0.0)
    wait([f for f in win.futures[:win.sent]], timeout=ANSWER_GRACE_S)
    return win
