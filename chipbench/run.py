"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run makes
its weights and data from ``--seed``, compiles and warms up (set-up), runs
the measured window for ``--seconds``, reads the peak device memory, then
checks what the timed path produced against the plain reference.  The last
line of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device`` and, traced, ``breakdown``; then ``checks``, each compared
number beside its limit, which also close stderr.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int) -> str | None:
    """Why this machine cannot run the cell, or None."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return f"JAX found no devices: {e}"
    d0 = devices[0]
    print(f"chipbench: platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}", file=sys.stderr)
    if d0.platform != "tpu":
        return f"needs a TPU, found platform {d0.platform!r}"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips, found {len(devices)}"
    return None


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import bench

    entry = next((w for w in bench.benchmark()["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        print(f"chipbench: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    why_not = check_devices(entry["chips"])
    if why_not:
        print(f"chipbench: {why_not}", file=sys.stderr)
        return 3

    from chipbench import harness

    harness.use_checkout_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    harness.report_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
