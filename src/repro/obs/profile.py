"""Profiler trace capture around a step window (``--profile-steps N:M``).

``jax.profiler.start_trace`` / ``stop_trace`` bracket the inclusive step
range ``[N, M]``: the trace opens before step N begins and closes after
step M's work is done, so the captured window contains exactly M-N+1
logical batches.  The train loop marks each of them with a
``dp.train_step`` step annotation carrying its step number, on every
backend; ``repro.obs.timeline`` reads those first, and falls back to the
backend's execution events (``PjRtCpuExecutable::Execute`` on CPU, module
events on GPU) for traces without them.

The window degrades gracefully: a backend whose profiler cannot start
(sandboxed CI, missing permissions) logs a warning and the run proceeds
untraced — profiling is observability, never a correctness dependency.
"""
from __future__ import annotations

import pathlib
from typing import Optional

from repro.obs.events import emit_event
from repro.utils.logging import get_logger

log = get_logger("obs.profile")


def parse_window(spec: str) -> tuple[int, int]:
    """``"N:M"`` -> inclusive (first, last) step; ``"N"`` means one step."""
    lo_s, _, hi_s = spec.partition(":")
    try:
        lo = int(lo_s)
        hi = int(hi_s) if hi_s else lo
    except ValueError as e:
        raise ValueError(
            f"bad --profile-steps spec {spec!r}: expected N or N:M"
        ) from e
    if lo < 0 or hi < lo:
        raise ValueError(
            f"bad --profile-steps window {spec!r}: need 0 <= N <= M"
        )
    return lo, hi


class ProfileWindow:
    """Drives one start_trace/stop_trace pair from the train loop.

    The loop calls ``before_step(step)`` ahead of the step's annotation
    and ``after_step(step)`` once the annotation has closed; ``stop()``
    (idempotent) runs in the loop's ``finally`` so a crash inside the
    window still flushes a usable partial trace.
    """

    def __init__(self, first: int, last: int, trace_dir):
        self.first = first
        self.last = last
        self.trace_dir = pathlib.Path(trace_dir)
        self.active = False
        self.done = False

    @classmethod
    def from_spec(cls, spec: str, run_dir) -> "ProfileWindow":
        first, last = parse_window(spec)
        return cls(first, last, pathlib.Path(run_dir) / "profile")

    def before_step(self, step: int) -> None:
        if self.done or self.active or not (self.first <= step <= self.last):
            return
        import jax

        try:
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
        except Exception as e:  # pragma: no cover - backend-dependent
            log.warning("profiler could not start (%s: %s); continuing "
                        "untraced", type(e).__name__, e)
            self.done = True
            return
        self.active = True
        log.info("profiler trace open: steps [%d, %d] -> %s",
                 self.first, self.last, self.trace_dir)
        emit_event("profile_started", step=step, first=self.first,
                   last=self.last, trace_dir=str(self.trace_dir))

    def after_step(self, step: int) -> None:
        if self.active and step >= self.last:
            self.stop(step=step)

    def stop(self, step: Optional[int] = None) -> None:
        if not self.active:
            return
        import jax

        self.active = False
        self.done = True
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            log.warning("profiler stop failed (%s: %s)", type(e).__name__, e)
            return
        log.info("profiler trace written: %s", self.trace_dir)
        emit_event("profile_stopped", step=step,
                   trace_dir=str(self.trace_dir))
