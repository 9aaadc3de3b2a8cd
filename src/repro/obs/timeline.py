"""Extract per-step wall times from a captured profiler trace.

``jax.profiler.start_trace`` writes a TensorBoard-layout directory::

    <trace_dir>/plugins/profile/<timestamp>/<host>.trace.json.gz

whose payload is Chrome-trace JSON (``traceEvents``: complete events with
``ph="X"``, ``ts``/``dur`` in microseconds).  This module reads those files
with the stdlib only (no jax, no tensorboard) and pulls out the *device
execution* events — the spans the step-time gate should compare, as opposed
to bench medians which time the host loop around them (ROADMAP item 5
follow-on: "gate on step markers from real profiles rather than bench
medians").

Steps come first from the program itself: ``launch.train`` wraps each
logical step in a ``jax.profiler.StepTraceAnnotation`` named
``dp.train_step`` whose ``step_num`` is the step, on every backend.  One
step's wall time is its annotation's length.

Traces without those annotations fall back to a regex over event names,
with a default covering the backends we run:

* CPU: each compiled program execution is one ``PjRtCpuExecutable::Execute``
  event (an accumulation run has ``accum+1`` executions per logical step);
* GPU: module execution lands as ``XlaModule:``-prefixed events;
* TPU: XLA step markers are expected as ``StepMarker`` events.  A TPU v5e
  trace (jax 0.9) has none, and names no execution the pattern matches:
  each one is an event named after its module, ``jit_<fn>(<fingerprint>)``,
  on the ``/device:TPU:0`` plane, which the Chrome-trace export does not
  carry as such.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import re
from typing import Iterable, Optional

DEFAULT_STEP_PATTERN = r"StepMarker|XlaModule|PjRtCpuExecutable::Execute"
STEP_ANNOTATION = "dp.train_step"


def trace_files(trace_dir) -> list[pathlib.Path]:
    """Every ``*.trace.json[.gz]`` under ``trace_dir``, sorted for determinism."""
    root = pathlib.Path(trace_dir)
    if not root.exists():
        return []
    return sorted(
        p for p in root.rglob("*")
        if p.is_file() and (
            p.name.endswith(".trace.json.gz") or p.name.endswith(".trace.json")
        )
    )


def load_trace_events(trace_dir) -> list[dict]:
    """All Chrome-trace ``traceEvents`` from every trace file, ``ts``-ordered."""
    events: list[dict] = []
    for path in trace_files(trace_dir):
        raw = path.read_bytes()
        if path.name.endswith(".gz"):
            raw = gzip.decompress(raw)
        payload = json.loads(raw)
        evs = payload.get("traceEvents", payload if isinstance(payload, list) else [])
        events.extend(e for e in evs if isinstance(e, dict))
    events.sort(key=lambda e: float(e.get("ts", 0.0)))
    return events


def annotated_steps(trace_dir) -> list[dict]:
    """The program's ``dp.train_step`` annotations, one per logical step.

    Returns ``[{"step", "ts_us", "dur_us"}, ...]`` in step order; the events
    of one step number (one per host) merge into first start to last end.
    """
    return _annotated(load_trace_events(trace_dir))


def _annotated(events: list[dict]) -> list[dict]:
    bounds: dict[int, tuple[float, float]] = {}
    for e in events:
        step = (e.get("args") or {}).get("step_num")
        if e.get("ph") != "X" or e.get("name") != STEP_ANNOTATION or step is None:
            continue
        ts = float(e.get("ts", 0.0))
        end = ts + float(e.get("dur", 0.0))
        lo, hi = bounds.get(int(step), (ts, end))
        bounds[int(step)] = (min(lo, ts), max(hi, end))
    return [
        {"step": k, "ts_us": lo, "dur_us": hi - lo}
        for k, (lo, hi) in sorted(bounds.items())
    ]


def execution_spans(
    trace_dir, pattern: str = DEFAULT_STEP_PATTERN
) -> list[dict]:
    """Complete (``ph="X"``) events whose name matches ``pattern``.

    Returns ``[{"name", "ts_us", "dur_us"}, ...]`` in timestamp order —
    the raw material for per-step wall times.
    """
    return _matching(load_trace_events(trace_dir), pattern)


def _matching(events: list[dict], pattern: str) -> list[dict]:
    rx = re.compile(pattern)
    out = []
    for e in events:
        name = str(e.get("name", ""))
        if e.get("ph") == "X" and rx.search(name):
            out.append({
                "name": name,
                "ts_us": float(e.get("ts", 0.0)),
                "dur_us": float(e.get("dur", 0.0)),
            })
    return out


def step_wall_times_ms(
    trace_dir,
    pattern: Optional[str] = None,
    group_us: Optional[float] = None,
) -> list[float]:
    """Per-step wall times (ms): see ``step_timeline``."""
    return step_timeline(trace_dir, pattern, group_us)[0]


def step_timeline(
    trace_dir,
    pattern: Optional[str] = None,
    group_us: Optional[float] = None,
) -> tuple[list[float], str]:
    """Per-step wall times (ms), and what they were read from.

    With no ``pattern``, a trace holding ``dp.train_step`` annotations gives
    one time per annotated step (``"annotations"``).  Otherwise the times
    come from the execution spans ``pattern`` matches (default
    ``DEFAULT_STEP_PATTERN``; ``"spans"``):
    consecutive spans separated by less than ``group_us`` of idle gap are
    folded into one step (an accumulation loop is several executions per
    logical batch); ``group_us=None`` derives the threshold as half the
    median inter-span gap, which cleanly splits back-to-back microsteps
    from the between-step host work in practice.  Each step's wall time is
    last-span-end minus first-span-start.
    """
    events = load_trace_events(trace_dir)
    if pattern is None:
        annotated = _annotated(events)
        if annotated:
            return [a["dur_us"] / 1e3 for a in annotated], "annotations"
    spans = _matching(events, pattern or DEFAULT_STEP_PATTERN)
    if not spans:
        return [], "spans"
    if len(spans) == 1:
        return [spans[0]["dur_us"] / 1e3], "spans"
    gaps = [
        max(0.0, b["ts_us"] - (a["ts_us"] + a["dur_us"]))
        for a, b in zip(spans, spans[1:])
    ]
    if group_us is None:
        ordered = sorted(gaps)
        group_us = ordered[len(ordered) // 2] / 2.0
    steps: list[list[dict]] = [[spans[0]]]
    for gap, span in zip(gaps, spans[1:]):
        if gap <= group_us:
            steps[-1].append(span)
        else:
            steps.append([span])
    out = []
    for group in steps:
        start = group[0]["ts_us"]
        end = max(s["ts_us"] + s["dur_us"] for s in group)
        out.append((end - start) / 1e3)
    return out, "spans"


def percentile(xs: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (mirrors serving.engine's aggregation)."""
    s = sorted(xs)
    if not s:
        return 0.0
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]
