# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--only table4,table7] [--fast]

Each benchmark also writes a machine-readable ``BENCH_<name>.json`` (list of
{name, us_per_call, derived} rows) under --out-dir, so the perf trajectory
can accumulate across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of benchmark names")
    ap.add_argument("--fast", action="store_true", help="smaller sweeps")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_<name>.json artifacts")
    args = ap.parse_args()

    # pin the launch env (allocator, XLA step markers, preallocate-off)
    # before the benchmark imports below pull in jax — timings archived to
    # benchmarks/history/ are only comparable under the same harness
    from repro.launch.env import apply_env, host_fingerprint

    apply_env()

    from benchmarks import (
        decode,
        fig3_memory_curve,
        kernels,
        modes,
        policies,
        roofline,
        table1_complexity,
        table3_decision,
        table4_time_memory,
        table5_accuracy,
        table7_max_batch,
    )

    benches = {
        "table1": lambda: table1_complexity.run(),
        "table3": lambda: table3_decision.run(),
        "kernels": lambda: kernels.run(fast=args.fast),
        "decode": lambda: decode.run(fast=args.fast),
        "table4": lambda: table4_time_memory.run(batch=32 if args.fast else 64),
        "table5": lambda: table5_accuracy.run(steps=10 if args.fast else 30),
        "table7": lambda: table7_max_batch.run(),
        "fig3": lambda: fig3_memory_curve.run(fast=args.fast),
        "modes": lambda: modes.run(batch=32 if args.fast else 64),
        "policies": lambda: policies.run(batch=32 if args.fast else 64),
        "roofline": lambda: roofline.run("single") + roofline.run("multi"),
    }
    host = host_fingerprint()  # after the imports above: jax names the backend
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    os.makedirs(args.out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches.items():
        t0 = time.time()
        rows = []
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
                # "host" tags the row's host class so the step-time gate
                # only ever compares same-host rows (render ignores it)
                rows.append(
                    {"name": row_name, "us_per_call": us,
                     "derived": str(derived), "host": host}
                )
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,FAILED", file=sys.stderr)
            traceback.print_exc()
            # a stale artifact from an earlier healthy run would mask the
            # regression — remove it so the trajectory shows the gap
            stale = os.path.join(args.out_dir, f"BENCH_{name}.json")
            if os.path.exists(stale):
                os.remove(stale)
        else:
            path = os.path.join(args.out_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(rows, f, indent=2)
            print(f"# wrote {path}", file=sys.stderr)
        print(f"# {name} finished in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
