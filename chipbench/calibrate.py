"""Readings from which a cell's limits are set (not part of a benchmark run).

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --variants program,control,half_batch [--out readings.jsonl]

For every seed the plain reference runs once; then each variant of the
configuration's loop (``program``; the lower-precision ``control``; the
faults ``half_batch``, ``sum_dropped`` and ``unchanged``) takes the cell's
first steps at the cell's own size, and the compared numbers are printed,
one JSON line per variant and seed, with both sides' readings (per-leaf
norms, per-sample norms, losses) so that other numbers can be worked out
from them later.  A limit lies above every ``program`` reading and below
the smallest reading that fails, as ``PERF.md`` sets out.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def _listed(readings: dict) -> dict:
    return {k: (v if k == "names" else [float(x) for x in np.ravel(v)])
            for k, v in readings.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--variants", default="program,control,half_batch")
    ap.add_argument("--variant-seeds", type=int, default=None,
                    help="run variants other than program on only the first N seeds")
    ap.add_argument("--out", default=None, help="also append the lines to this file")
    args = ap.parse_args(argv)

    from chipbench import bench, compare, harness

    harness.use_checkout_cache()
    wl = bench.workload(args.workload)
    cfg = bench.config(wl["config"])
    mod = bench.config_module(wl["config"])
    variants = args.variants.split(",")
    # a configuration without a lower-precision path of its own puts its
    # reference, computed lower, in the program's place
    ref_control = "control" in variants and hasattr(mod, "reference_control")
    trainers = {}
    for v in variants:
        if v == "control" and ref_control:
            trainers[v] = None
            continue
        t = time.perf_counter()
        trainers[v] = mod.Trainer(cfg, wl, v)
        print(f"calibrate: built {v} in {time.perf_counter() - t:.1f}s", file=sys.stderr)
    out = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            ref = mod.reference(cfg, wl, seed, harness.FIRST_STEPS)
            ref_s = time.perf_counter() - t
            for v, trainer in trainers.items():
                if v != "program" and args.variant_seeds is not None and i >= args.variant_seeds:
                    continue
                if trainer is None:
                    prog = mod.reference_control(cfg, wl, seed, harness.FIRST_STEPS)
                else:
                    state, prog = harness.first_steps(trainer, seed)
                    harness.free(state)
                numbers, where = compare.training_numbers(prog, ref)
                line = json.dumps({
                    "workload": args.workload, "variant": v, "seed": seed, "numbers": numbers,
                    "where": where, "reference_s": ref_s,
                    "program_readings": _listed(prog), "reference_readings": _listed(ref),
                })
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    print(f"calibrate: {time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
