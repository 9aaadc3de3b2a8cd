"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps, kernel time and the harness's own spans.

Read with ``jax.profiler.ProfileData`` alone.  Device operations are the
events of the op line (``XLA Ops``) of each ``/device:TPU:<n>`` plane; the
harness's spans are the ``chipbench.*`` events that
``jax.profiler.TraceAnnotation`` writes on the host's Python thread.  Both
are on the profiler's clock, in nanoseconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Callable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]  # device plane name -> its op events
    spans: list[Event]  # the harness's spans, in start order

    @property
    def window(self) -> tuple[float, float]:
        """From the first harness span's start to the last one's end."""
        if not self.spans:
            raise ValueError("the trace holds no chipbench.* span")
        return self.spans[0].start, max(s.end for s in self.spans)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy(self, plane: str) -> list[tuple[float, float]]:
        lo, hi = self.window
        return clip(union([(e.start, e.end) for e in self.ops[plane]]), lo, hi)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy(p)) / 1e9 for p in self.ops]
        return sum(per) / len(per)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def gaps(self, plane: Optional[str] = None) -> list[tuple[float, float]]:
        """Idle intervals of ``plane`` (the first device's by default)."""
        lo, hi = self.window
        if not self.ops:
            return [(lo, hi)]
        out, at = [], lo
        for s, e in self.busy(plane or sorted(self.ops)[0]):
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if hi > at:
            out.append((at, hi))
        return out

    def span_at(self, s: float, e: float) -> str:
        """The harness span that overlaps [s, e] the most ("none" if none)."""
        best, best_ov = "none", 0.0
        for sp in self.spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        return best

    def longest_gaps(self, k: int = 10) -> list[list]:
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:k]
        return [[self.span_at(s, e), (e - s) / 1e9] for s, e in gaps]

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, float] = collections.Counter()
        lo, hi = self.window
        for evs in self.ops.values():
            for ev in evs:
                if ev.end > lo and ev.start < hi:
                    tot[ev.name] += ev.dur / 1e9
        n = max(len(self.ops), 1)
        return [[name, s / n] for name, s in tot.most_common(k)]

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the ops whose name ``match`` accepts, summed
        within the window and averaged over the chips."""
        lo, hi = self.window
        n = max(len(self.ops), 1)
        return sum(
            ev.dur for evs in self.ops.values() for ev in evs
            if match(ev.name) and ev.end > lo and ev.start < hi
        ) / 1e9 / n

    def span_seconds(self, name: str) -> float:
        return sum(sp.dur for sp in self.spans if sp.name == name) / 1e9

    def count_spans(self, name: str) -> int:
        return sum(1 for sp in self.spans if sp.name == name)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    """Read the device op events and the harness spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    Event(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans)
