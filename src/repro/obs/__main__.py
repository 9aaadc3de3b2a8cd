"""CLI: render a run directory's obs streams into a human summary.

    python -m repro.obs RUN_DIR [--json] [--require-epsilon]
                        [--timeline] [--step-pattern REGEX]

``RUN_DIR`` is the directory ``launch.train``/``launch.serve`` wrote
``events.jsonl``/``metrics.jsonl`` into (the ``--ckpt-dir``/``--obs-dir``).
``--json`` emits the machine summary instead of text; ``--require-epsilon``
exits non-zero when no epsilon trajectory was recorded (the tier-1 smoke
gate's assertion); ``--timeline`` additionally extracts per-step wall
times from a captured profiler trace under ``RUN_DIR/profile`` (the train
loop's ``dp.train_step`` annotations, else ``--step-pattern`` spans).

Deliberately jax-free: reading a run's telemetry must work on a laptop
that cannot even initialize the run's backend.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from repro.obs.report import render_text, summarize_run
from repro.obs.timeline import (
    DEFAULT_STEP_PATTERN,
    STEP_ANNOTATION,
    percentile,
    step_timeline,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs")
    ap.add_argument("run_dir", help="directory holding events.jsonl/metrics.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable summary")
    ap.add_argument("--require-epsilon", action="store_true",
                    help="exit 1 unless a non-empty epsilon trajectory was "
                         "recorded (CI smoke assertion)")
    ap.add_argument("--timeline", action="store_true",
                    help="extract per-step wall times from the profiler "
                         "trace under RUN_DIR/profile")
    ap.add_argument("--step-pattern", default=None,
                    help="regex over trace event names that count as "
                         "step/execution spans (default: the program's "
                         f"{STEP_ANNOTATION} annotations, else "
                         f"{DEFAULT_STEP_PATTERN!r})")
    args = ap.parse_args(argv)

    summary = summarize_run(args.run_dir)
    if args.timeline:
        times, source = step_timeline(
            pathlib.Path(args.run_dir) / "profile", pattern=args.step_pattern
        )
        summary["profile_step_times_ms"] = times
        summary["profile_step_source"] = source
        summary["profile_step_p50_ms"] = (
            percentile(times, 0.50) if times else None
        )

    if args.json:
        print(json.dumps(summary, sort_keys=True))
    else:
        print(render_text(summary))
        if args.timeline:
            times = summary["profile_step_times_ms"]
            if times:
                what = (f"{STEP_ANNOTATION} annotation(s)"
                        if summary["profile_step_source"] == "annotations"
                        else "span group(s)")
                print(
                    f"  profiled steps: {len(times)} {what}, "
                    f"p50 {percentile(times, 0.5):.1f}ms "
                    f"p95 {percentile(times, 0.95):.1f}ms"
                )
            else:
                print("  profiled steps: no trace found")

    if args.require_epsilon and not summary["epsilon_trajectory"]:
        print("ERROR: no epsilon trajectory in the metrics stream",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
