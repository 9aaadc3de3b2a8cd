"""Tuner CLI: profile a model's taps and write the ClipPlan artifact.

    PYTHONPATH=src python -m repro.tuner --arch xlstm-350m --reduced

Steps: build the arch (registry), discover its taps, time the three-way
branch decision per matmul tap on this device — {ghost norm, instantiated
norm} for the second-backward modes and {ghost-bank, psg-bank} for
book-keeping, plus each tap's share of the second backward — binary-search
the max physical microbatch under the memory budget, re-measure at the tuned
physical batch, and write the plan JSON (cache path or --plan).  The printed
table shows where the measured winner disagrees with the analytic Eq-(4.1)
rule — the entire reason this subsystem exists — and which tuned mode
(mixed_ghost vs bk_mixed) the measurements recommend.

Fleet workflows (repro.tuner.consensus):

- ``--consensus``: run the multi-host agreement after measuring — one
  leader per device kind measures, every rank adopts the byte-identical
  agreed plan (single process: stamps consensus provenance on the plan).
- ``--export-plan out.json``: write the adopted plan for offline fleets
  whose ranks cannot gather at tune time.
- ``--import-plan in.json``: skip measuring; load + strictly verify a plan
  against this host's model/device (exit non-zero on any mismatch — a
  fleet rank must never silently fall back to the analytic rule).
"""
from __future__ import annotations

import argparse
import sys

import jax

from repro.configs.registry import build_model, get_arch
from repro.core.clipping import MODES, ClipConfig, discover_meta, dp_value_and_clipped_grad
from repro.core.decision import decide
from repro.data.synthetic import synthetic_arch_batch
from repro.tuner import max_batch as mb
from repro.tuner.measure import (
    MeasureConfig,
    build_plan,
    close_physical_batch_loop,
)
from repro.tuner.plan import default_plan_path
from repro.utils.logging import get_logger

log = get_logger("tuner")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="repro.tuner")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="physical microbatch used for profiling")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--logical-batch", type=int, default=None,
                    help="derive accumulation_steps for this logical batch "
                         "(default: --batch)")
    ap.add_argument("--plan", default=None,
                    help="output path (default: ~/.cache/repro-tuner/)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--max-rows", type=int, default=64,
                    help="clamp profiled rows N (0 = unclamped, use --batch as-is)")
    ap.add_argument("--budget-gb", type=float, default=16.0,
                    help="memory budget for the max-batch search")
    ap.add_argument("--hi-cap", type=int, default=4096)
    ap.add_argument("--skip-max-batch", action="store_true")
    ap.add_argument("--skip-remeasure", action="store_true",
                    help="do not re-time branches at the tuned physical batch")
    ap.add_argument("--mode", default="mixed_ghost", choices=MODES,
                    help="clipping mode the max-batch search compiles")
    ap.add_argument("--consensus", action="store_true",
                    help="fleet agreement after measuring: adopt the "
                         "byte-identical plan on every rank")
    ap.add_argument("--export-plan", default=None,
                    help="also write the adopted plan here (offline fleets)")
    ap.add_argument("--import-plan", default=None,
                    help="skip measuring: load + strictly verify this plan "
                         "against the local model/device (non-zero exit on "
                         "mismatch)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = synthetic_arch_batch(cfg, batch=args.batch, seq=args.seq)

    metas = discover_meta(model.loss_with_ctx, params, batch)
    log.info("discovered %d taps (%d matmul) on %s", len(metas),
             sum(1 for m in metas.values() if m.kind == "matmul"),
             jax.devices()[0].device_kind)

    if args.import_plan:
        # offline-fleet rank: adopt a plan exported elsewhere, or die loudly
        from repro.tuner.consensus import PlanConsensusError, verify_adopted
        from repro.tuner.plan import ClipPlan

        try:
            plan = ClipPlan.load(args.import_plan)
            verify_adopted(plan, metas)
        except (PlanConsensusError, ValueError, OSError) as e:
            log.error("cannot adopt %s: %s", args.import_plan, e)
            return 1
        for out in {args.plan, args.export_plan} - {None}:
            plan.save(out)  # re-export the canonicalized (v3) artifact
        print(f"adopted ClipPlan {args.import_plan} for {cfg.name} on "
              f"{plan.device} (hash {plan.consensus_hash()}"
              f"{f', agreed by {plan.agreed_ranks} rank(s)' if plan.agreed_ranks else ''})")
        print(f"recommended mode: {plan.recommended_mode()}  "
              f"max physical batch: {plan.physical_batch}")
        return 0

    if args.consensus:
        # one measurement per device kind: a non-leader rank measures
        # nothing and adopts the fleet plan (measuring anyway would submit
        # a noise-divergent duplicate the agreement rightly rejects)
        from repro.tuner.consensus import fleet_agree, fleet_roles

        roles = fleet_roles()
        if not roles.is_leader:
            plan = fleet_agree(None, metas)
            plan.save(args.plan or default_plan_path(cfg.name, plan.fingerprint))
            if args.export_plan:
                plan.save(args.export_plan)
            print(f"process {roles.process_index} ({roles.device}): adopted "
                  f"the fleet plan measured by process {plan.leader_process} "
                  f"(hash {plan.agreed_hash}, {plan.agreed_ranks} ranks)")
            return 0

    measure = MeasureConfig(
        repeats=args.repeats, warmup=args.warmup,
        max_rows=args.max_rows or None,
    )
    plan = build_plan(metas, measure=measure, arch=cfg.name)

    if not args.skip_max_batch:
        grad_fn = dp_value_and_clipped_grad(
            model.loss_with_ctx, ClipConfig(mode=args.mode, plan=plan)
        )
        budget = int(args.budget_gb * 1024**3)
        max_physical = mb.max_batch_by_memory(
            grad_fn, params, batch, budget_bytes=budget, hi_cap=args.hi_cap,
            reserved_bytes=mb.resident_state_bytes(params),
        )
        if max_physical <= 0:
            log.warning("no batch fits the %.1fGB budget; plan has no "
                        "physical_batch", args.budget_gb)
        else:
            logical = args.logical_batch or args.batch
            physical, steps = mb.derive_accumulation(logical, max_physical)
            plan = plan.replace_batch(
                physical_batch=max_physical,
                logical_batch=logical,
                accumulation_steps=steps,
                budget_bytes=budget,
            )
            log.info("max physical batch %d under %.1fGB; logical %d -> "
                     "%d x %d microsteps", max_physical, args.budget_gb,
                     logical, physical, steps)
            if not args.skip_remeasure:
                # the step runs at the tuned batch: measure the decision
                # there, re-certifying the batch if any branch flips
                def _search(p):
                    fn = dp_value_and_clipped_grad(
                        model.loss_with_ctx, ClipConfig(mode=args.mode, plan=p)
                    )
                    return mb.max_batch_by_memory(
                        fn, params, batch, budget_bytes=budget,
                        hi_cap=args.hi_cap,
                        reserved_bytes=mb.resident_state_bytes(params),
                    )

                plan = close_physical_batch_loop(
                    plan, metas, _search, logical, budget, measure
                )

    if args.consensus:
        from repro.tuner.consensus import fleet_agree

        plan = fleet_agree(plan, metas)

    path = args.plan or default_plan_path(cfg.name, plan.fingerprint)
    plan.save(path)
    if args.export_plan:
        plan.save(args.export_plan)

    branch_map = plan.branch_map()
    bk_map = plan.branch_map("bk_mixed")
    timing = plan.tap_timings()
    print(f"\nClipPlan for {cfg.name} on {plan.device}  ->  {path}")
    print(f"{'tap':<40s} {'T':>5s} {'D':>6s} {'p':>6s} "
          f"{'ghost_us':>9s} {'inst_us':>9s} {'bk_g_us':>9s} {'bk_i_us':>9s} "
          f"{'2bwd_us':>8s} {'analytic':>11s} {'measured':>11s} {'bk':>11s}")
    flips = 0
    for name in sorted(branch_map):
        m = metas[name]
        analytic = decide(m, mode="mixed_ghost")
        measured = branch_map[name]
        t = timing[name]
        flag = "  <- flip" if analytic != measured else ""
        flips += analytic != measured
        print(f"{name:<40s} {m.T:>5d} {m.D:>6d} {m.p:>6d} "
              f"{t.ghost_us:>9.1f} {t.instantiate_us:>9.1f} "
              f"{t.bk_ghost_us:>9.1f} {t.bk_instantiate_us:>9.1f} "
              f"{t.second_bwd_us:>8.1f} {analytic:>11s} {measured:>11s} "
              f"{bk_map.get(name, '-'):>11s}{flag}")
    print(f"\n{flips}/{len(branch_map)} taps flip vs the analytic rule")
    kmap = plan.kernel_map()
    if kmap:
        # describe the PLAN's map, not the local backend: the plan may have
        # been imported from another device kind (its map then only applies
        # there — ClipPlan.kernels_for)
        if any(i != "xla" for ks in kmap.values() for i in ks.values()):
            for name in sorted(kmap):
                print(f"kernel impls {name}: " + "  ".join(
                    f"{op}={impl}" for op, impl in sorted(kmap[name].items())))
        elif plan.device.startswith("tpu:"):
            # both impls were raced on the measuring TPU and xla swept —
            # the signal that the Pallas kernels are underperforming there
            print("kernel impls: xla everywhere (pallas raced and lost "
                  "every op)")
        else:
            print("kernel impls: xla everywhere (single-impl device, "
                  "nothing raced)")
    print(f"measured per-step clipping cost: mixed_ghost="
          f"{plan.mode_cost_us('mixed_ghost'):.1f}us  "
          f"bk_mixed={plan.mode_cost_us('bk_mixed'):.1f}us  "
          f"-> recommended mode: {plan.recommended_mode()}")
    if plan.physical_batch:
        at = " (branches re-measured there)" if plan.measured_at_physical else ""
        print(f"max physical batch: {plan.physical_batch} "
              f"(logical {plan.logical_batch} = "
              f"{plan.accumulation_steps} microsteps){at}")
    if plan.agreed_ranks:
        print(f"fleet agreement: {plan.agreed_ranks} rank(s) on "
              f"{list(plan.devices)}, hash {plan.agreed_hash}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
