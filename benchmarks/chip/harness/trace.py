"""Reduce a profiler trace to device busy time, stage times and idle gaps.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
JAX alone. Device planes are named ``/device:TPU:<n>``; their
``XLA Modules`` line holds one event per executed program (``jit_<fn>``)
and their ``XLA Ops`` line one per operation. The benchmark's own host
spans (``bench.*``, written with ``TraceAnnotation``) share the clock:
``bench.window`` bounds the measured window, and ``bench.db_query`` marks
the host inside ``VectorDB.query``.
"""
from __future__ import annotations

import glob
import os
import re

import jax

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def start(log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events in the window
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def module_name(event_name: str) -> str:
    return MODULE_SUFFIX.sub("", event_name).strip()


def union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def merge(events):
    """(name, start, end) events -> their union as sorted disjoint
    [start, end] intervals."""
    out = []
    for s, e in sorted((s, e) for _, s, e in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """Device events and benchmark host spans of one traced window."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.ops, self.modules, self.spans = {}, {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events]
                    if line.name == "XLA Ops":
                        self.ops[plane.name] = evs
                    elif line.name == "XLA Modules":
                        self.modules[plane.name] = evs
            else:
                for line in plane.lines:
                    self.spans += [(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events
                                   if ev.name.startswith("bench.")]
        win = [s for s in self.spans if s[0] == "bench.window"]
        if not win:
            raise ValueError(f"trace {path} has no bench.window span")
        self.t0, self.t1 = win[0][1], win[0][2]

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise ValueError(f"want one .xplane.pb under {log_dir}: {paths}")
        return cls(paths[0])

    @property
    def devices(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, evs):
        return [(n, max(s, self.t0), min(e, self.t1)) for n, s, e in evs
                if e > self.t0 and s < self.t1]

    def busy_s(self) -> float:
        """Seconds in the window in which an operation ran, averaged over
        the devices traced."""
        if not self.ops:
            return 0.0
        return sum(union_ns([(s, e) for _, s, e in self._clip(evs)])
                   for evs in self.ops.values()) * 1e-9 / len(self.ops)

    def idle_within(self, intervals) -> float:
        """Nanoseconds of the given (start, end) intervals, clipped to the
        window and merged, in which no operation ran on the first device."""
        if not self.ops:
            return 0.0
        want = merge(self._clip((None, s, e) for s, e in intervals))
        busy = merge(self._clip(next(iter(self.ops.values()))))
        overlap, b = 0.0, 0
        for s, e in want:
            while b < len(busy) and busy[b][1] <= s:
                b += 1
            j = b
            while j < len(busy) and busy[j][0] < e:
                overlap += min(e, busy[j][1]) - max(s, busy[j][0])
                j += 1
        return sum(e - s for s, e in want) - overlap

    def module_s(self, names) -> float:
        """Device seconds in the window of the programs named, summed over
        devices (a program's name is ``jit_<function>``)."""
        names = set(names)
        return sum(e - s for evs in self.modules.values()
                   for n, s, e in self._clip(evs)
                   if module_name(n) in names) * 1e-9

    def top_modules(self, n: int = 10):
        tot = {}
        for evs in self.modules.values():
            for name, s, e in self._clip(evs):
                key = module_name(name)
                tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10):
        """The longest gaps between device operations (first device), each
        named by the benchmark host span that covers most of it."""
        if not self.ops:
            return []
        evs = sorted((s, e) for _, s, e in
                     self._clip(next(iter(self.ops.values()))))
        gaps, end = [], self.t0
        for s, e in evs:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if self.t1 > end:
            gaps.append((end, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = [sp for sp in self.spans if sp[0] != "bench.window"]
        out = []
        for s, e in gaps[:n]:
            cover = {}
            for name, a, b in spans:
                o = min(b, e) - max(a, s)
                if o > 0:
                    cover[name] = cover.get(name, 0) + o
            what = (max(cover, key=cover.get) if cover
                    and max(cover.values()) > (e - s) / 2
                    else "outside db.query")
            out.append([what, (e - s) * 1e-9])
        return out
