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

What counts as a step span is backend-dependent, so the matcher is a
regex over event names with a default covering the backends we run:

* CPU: each compiled program execution is one ``PjRtCpuExecutable::Execute``
  event (an accumulation run has ``accum+1`` executions per logical step);
* GPU: module execution lands as ``XlaModule:``-prefixed events;
* TPU: XLA step markers (``--xla_step_marker_location=1`` via
  ``launch/env.py``) are expected as ``StepMarker`` events.  Without them a
  TPU v5e trace (jax 0.9) names no execution the pattern matches: each one
  is an event named after its module, ``jit_<fn>(<fingerprint>)``, on the
  ``/device:TPU:0`` plane, plus a host launch event
  ``TpuLoadedExecutable::ExecuteLaunch`` that ends before the device does.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import re
from typing import Iterable, Optional

DEFAULT_STEP_PATTERN = r"StepMarker|XlaModule|PjRtCpuExecutable::Execute"


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


def execution_spans(
    trace_dir, pattern: str = DEFAULT_STEP_PATTERN
) -> list[dict]:
    """Complete (``ph="X"``) events whose name matches ``pattern``.

    Returns ``[{"name", "ts_us", "dur_us"}, ...]`` in timestamp order —
    the raw material for per-step wall times.
    """
    rx = re.compile(pattern)
    out = []
    for e in load_trace_events(trace_dir):
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
    pattern: str = DEFAULT_STEP_PATTERN,
    group_us: Optional[float] = None,
) -> list[float]:
    """Per-step wall times (ms) from the trace's execution spans.

    Consecutive spans separated by less than ``group_us`` of idle gap are
    folded into one step (an accumulation loop is several executions per
    logical batch); ``group_us=None`` derives the threshold as half the
    median inter-span gap, which cleanly splits back-to-back microsteps
    from the between-step host work in practice.  Each step's wall time is
    last-span-end minus first-span-start.
    """
    spans = execution_spans(trace_dir, pattern)
    if not spans:
        return []
    if len(spans) == 1:
        return [spans[0]["dur_us"] / 1e3]
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
    return out


def percentile(xs: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (mirrors serving.engine's aggregation)."""
    s = sorted(xs)
    if not s:
        return 0.0
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]
