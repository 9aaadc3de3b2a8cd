"""DP training driver.

Full production loop: data pipeline -> mixed-ghost clipped grads (with
gradient accumulation / virtual steps) -> Gaussian noise -> optimizer ->
checkpoint manager -> privacy accountant, with straggler watchdog,
preemption-to-checkpoint, and an ``--auto-restart`` supervision loop that
resumes from the latest checkpoint after a crash (fault injection for tests
via ``--fail-at-step``).

CPU quickstart (reduced config):
    python -m repro.launch.train --arch qwen2-72b --reduced --steps 20 \
        --batch 4 --seq 64 --ckpt-dir /tmp/ckpt

Measured-cost autotuning (repro.tuner): ``--tune`` profiles the three-way
branch decision per tap on this device — ghost / instantiate norms for the
second-backward modes and the book-keeping banks for ``bk_mixed`` — and
binary-searches the max physical microbatch; ``--plan plan.json`` reuses a
cached ClipPlan.  ``--mode auto`` adopts the plan's measured
``recommended_mode`` (mixed_ghost vs bk_mixed).  When the tuned physical
batch is smaller than ``--batch`` (the logical batch), the loop
automatically switches to gradient accumulation with the derived number of
microsteps (the paper's virtual-step pattern).

Multi-host fleets add ``--consensus`` (repro.tuner.consensus): tuning
elects one leader per device kind, every rank adopts the byte-identical
fleet-agreed plan (GSPMD requires all ranks to trace the same branch per
tap), memory certificates compile at the per-host batch share, and a stale
``--plan`` import fails loudly instead of silently falling back to the
analytic rule on one rank while its peers trace the plan.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import build_model, get_arch
from repro.core.clipping import MODES
from repro.core.engine import PrivacyEngine
from repro.data.pipeline import DataPipeline
from repro.data.poisson import poisson_sample_mask
from repro.data.synthetic import synthetic_arch_batch
from repro.checkpoint.manager import CheckpointManager
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    DPTrainConfig,
    make_accum_finalize,
    make_accum_init,
    make_accum_microstep,
    make_clipped_microstep,
    make_train_state,
    make_train_step,
)
from repro.obs import events as obs
from repro.obs.profile import ProfileWindow
from repro.obs.timeline import STEP_ANNOTATION
from repro.optim import adam, warmup_cosine
from repro.parallel.reshard import use_reshard_rules
from repro.parallel.sharding import batch_shardings, state_shardings
from repro.runtime.elastic import current_data_shards, elastic_plan
from repro.runtime.fault import PreemptionHandler, StepWatchdog
from repro.runtime.inject import InjectionPlan
from repro.utils.logging import get_logger, reconfigure

log = get_logger("train")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mode", default="mixed_ghost", choices=MODES + ("auto",),
                    help="clipping mode, or 'auto' to adopt the tuned plan's "
                         "recommended_mode")
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--clip-policy", default="fixed",
                    choices=["fixed", "automatic", "quantile", "per_layer"],
                    help="clipping policy (repro.policies): fixed flat R, "
                         "automatic AUTO-S normalization (no R), quantile "
                         "DP-adaptive R, or per_layer group thresholds")
    ap.add_argument("--clip-quantile", type=float, default=0.5,
                    help="quantile policy: target norm quantile for R")
    ap.add_argument("--quantile-lr", type=float, default=0.2,
                    help="quantile policy: geometric update rate for R")
    ap.add_argument("--quantile-sigma", type=float, default=1.0,
                    help="quantile policy: noise multiplier of the "
                         "indicator release (composed into the accountant; "
                         "0 disables the release and its DP guarantee)")
    ap.add_argument("--auto-gamma", type=float, default=0.01,
                    help="automatic policy: stability constant (0 = AUTO-V)")
    ap.add_argument("--layer-groups", default="",
                    help="per_layer policy: comma-separated param-path "
                         "prefixes, one threshold per group (a catch-all "
                         "group is added automatically)")
    ap.add_argument("--target-epsilon", type=float, default=None)
    ap.add_argument("--epsilon-alarm-frac", type=float, default=0.9,
                    help="emit a one-shot epsilon_budget_crossed event when "
                         "the accountant passes this fraction of "
                         "--target-epsilon (<=0 disables)")
    ap.add_argument("--noise-multiplier", type=float, default=1.0)
    ap.add_argument("--sample-size", type=int, default=50000)
    ap.add_argument("--poisson", action="store_true",
                    help="Poisson subsampling masks (DP accounting assumption)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--auto-restart", type=int, default=0,
                    help="supervise and restart up to N times on failure")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="fault injection: raise at this step (tests); "
                         "shorthand for --inject crash@STEP")
    ap.add_argument("--inject", default=None,
                    help="deterministic fault injection spec "
                         "(runtime.inject), e.g. 'crash@5,torn@4' or "
                         "'shrink@5:1'; merged with $REPRO_FAULT_INJECT")
    ap.add_argument("--data-shards", type=int, default=0,
                    help="data-parallel degree of the fleet (0 = "
                         "$REPRO_ELASTIC_SHARDS, else 1); the elastic "
                         "replan keeps the logical batch across resizes")
    ap.add_argument("--elastic-max-per-shard", type=int, default=0,
                    help="per-shard microbatch cap for the elastic replan "
                         "(0 = the tuned/physical microbatch)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--obs-dir", default=None,
                    help="directory for the observability streams "
                         "(events.jsonl/metrics.jsonl; default: --ckpt-dir). "
                         "Read back with `python -m repro.obs DIR`")
    ap.add_argument("--profile-steps", default=None, metavar="N[:M]",
                    help="capture a jax.profiler trace around the inclusive "
                         "step window [N, M] into <obs-dir>/profile; each step "
                         "is a dp.train_step annotation there, whose wall times "
                         "`python -m repro.obs DIR --timeline` prints")
    ap.add_argument("--tune", action="store_true",
                    help="profile ghost-vs-instantiate per tap and search the "
                         "max physical microbatch before training")
    ap.add_argument("--consensus", action="store_true",
                    help="fleet-safe tuning/plan adoption: one measurement "
                         "per device kind, every rank adopts the "
                         "byte-identical agreed ClipPlan; with --plan, a "
                         "stale import fails loudly instead of silently "
                         "falling back (which would diverge across ranks)")
    ap.add_argument("--plan", default=None,
                    help="ClipPlan JSON to load (or, with --tune, to write)")
    ap.add_argument("--tune-budget-gb", type=float, default=16.0,
                    help="memory budget for the --tune max-batch search")
    ap.add_argument("--tune-hi-cap", type=int, default=4096)
    return ap.parse_args(argv)


def _injection_for(args) -> InjectionPlan:
    """One InjectionPlan per process: ``--inject`` + env, with the legacy
    ``--fail-at-step N`` folded in as a ``crash@N`` injector.  Injectors are
    one-shot, so in-process ``--auto-restart`` attempts share the plan and a
    fault that already fired does not re-fire after the restart."""
    plan = InjectionPlan.from_spec(args.inject)
    if args.fail_at_step is not None:
        plan.add_crash(args.fail_at_step)
    return plan


def _write_summary(ckpt_dir: str, **fields) -> None:
    """Machine-readable run outcome next to the checkpoints (tests compare
    the privacy spend of interrupted vs uninterrupted runs through this)."""
    path = pathlib.Path(ckpt_dir) / "summary.json"
    tmp = path.with_name(".tmp_summary.json")
    tmp.write_text(json.dumps(fields, sort_keys=True))
    tmp.replace(path)


def run_once(args, injection: Optional[InjectionPlan] = None) -> int:
    if injection is None:
        injection = _injection_for(args)
    # observability streams live next to the checkpoints unless redirected;
    # configure_run(None) resets any sinks a previous in-process run left
    # installed, and re-configuring the SAME dir keeps appending (so every
    # --auto-restart attempt lands in one events.jsonl timeline)
    run_dir = args.obs_dir or args.ckpt_dir
    obs.configure_run(run_dir)
    obs.emit_event(
        "run_started", arch=args.arch, reduced=bool(args.reduced),
        steps=args.steps, logical_batch=args.batch, seq_len=args.seq,
        mode=args.mode, policy=args.clip_policy, resume=bool(args.resume),
        ckpt_dir=args.ckpt_dir,
    )
    profile = None
    if args.profile_steps:
        if run_dir is None:
            log.warning("--profile-steps needs --obs-dir or --ckpt-dir for "
                        "the trace output; skipping profiling")
        else:
            profile = ProfileWindow.from_spec(args.profile_steps, run_dir)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()

    # clipping policy (repro.policies): make_policy filters the kwarg union
    # down to what the chosen policy's __init__ actually takes
    from repro.policies import make_policy

    policy = make_policy(
        args.clip_policy,
        clip_norm=args.clip_norm,
        init_clip_norm=args.clip_norm,
        gamma=args.auto_gamma,
        target_quantile=args.clip_quantile,
        lr=args.quantile_lr,
        release_sigma=args.quantile_sigma,
        groups=tuple(g for g in args.layer_groups.split(",") if g),
    )
    if args.clip_policy != "fixed":
        log.info("clipping policy: %s", policy.fingerprint())

    # privacy engine: sigma from target epsilon (or given), accountant
    # attached.  With --target-epsilon the bisection composes the policy's
    # per-step release (quantile indicator) so the TOTAL spend hits the
    # target — no hand-picked sigma, no silent under-accounting.
    def make_engine(batch_size: int, mode: str) -> PrivacyEngine:
        return PrivacyEngine(
            loss_with_ctx=model.loss_with_ctx,
            batch_size=batch_size,
            sample_size=args.sample_size,
            steps=args.steps,
            max_grad_norm=args.clip_norm,
            target_epsilon=args.target_epsilon,
            noise_multiplier=None if args.target_epsilon else args.noise_multiplier,
            mode=mode,
            clip_policy=policy,
        )

    # '--mode auto' is resolved from the tuned plan below; tune/search under
    # the paper default in the meantime
    clip_mode = "mixed_ghost" if args.mode == "auto" else args.mode
    engine = make_engine(args.batch, clip_mode)
    log.info("noise multiplier sigma=%.4f (q=%.5f)", engine.noise_multiplier,
             engine.sampling_rate)

    optimizer = adam(state_dtype=jnp.dtype(cfg.opt_state_dtype))
    schedule = warmup_cosine(args.lr, max(args.steps // 20, 1), args.steps)

    state = make_train_state(model, jax.random.PRNGKey(0), optimizer, policy)

    # measured-cost autotuning: load a cached ClipPlan or profile one now.
    # Memory certificates (max-batch search / re-certification) compile at
    # the PER-HOST share of the batch: on a fleet, one host's HBM never
    # holds the global batch.  Single host: probe_batch == args.batch.
    from repro.parallel.sharding import per_host_batch

    seq = args.seq if args.reduced else 4096
    probe_batch = per_host_batch(args.batch, mesh, cfg)
    if probe_batch != args.batch:
        log.info("multi-host fleet: memory certificates compile at the "
                 "per-host batch share %d (global %d)", probe_batch, args.batch)
    plan = None
    if args.plan and not args.tune:
        from repro.core.clipping import discover_meta
        from repro.tuner import ClipPlan

        probe = synthetic_arch_batch(cfg, batch=probe_batch, seq=seq)
        metas = discover_meta(model.loss_with_ctx, state["params"], probe)
        if args.consensus:
            # fleet import: a stale plan on one rank means that rank would
            # trace different branches than its peers — abort, loudly,
            # before anything is traced.  verify_adopted is rank-local
            # (fingerprint/ratification/hash integrity); the certify phase
            # then cross-checks that every rank imported the SAME bytes
            # (e.g. one host left holding yesterday's re-exported artifact)
            from repro.tuner.consensus import certify_fleet_hash, verify_adopted

            plan = ClipPlan.load(args.plan)
            verify_adopted(
                plan, metas, policy_fingerprint=policy.fingerprint()
            )
            certify_fleet_hash(plan)
        else:
            try:
                plan = ClipPlan.load(args.plan)
            except (ValueError, KeyError) as e:
                # e.g. a pre-three-way (v1) artifact: unreadable == stale
                log.warning("unreadable ClipPlan %s (%s); falling back to the "
                            "analytic decision", args.plan, e)
                plan = None
            if plan is not None and not plan.matches(metas):
                # a stale plan must not drive anything — neither the branch
                # overrides nor the microbatch geometry it measured elsewhere
                log.warning("ClipPlan %s is stale for this arch/device; "
                            "falling back to the analytic decision", args.plan)
                plan = None
        if plan is not None:
            engine.use_plan(plan)
            log.info("loaded ClipPlan %s (device %s, %d branch overrides%s)",
                     args.plan, plan.device, len(plan.branches),
                     f", agreed by {plan.agreed_ranks} rank(s)"
                     if plan.agreed_ranks else "")
    elif args.tune:
        probe = synthetic_arch_batch(cfg, batch=probe_batch, seq=seq)
        plan = engine.tune(
            state["params"], probe, arch=cfg.name,
            budget_bytes=int(args.tune_budget_gb * 1024**3),
            hi_cap=args.tune_hi_cap,
            plan_path=args.plan if args.plan else "auto",
            consensus=args.consensus,
        )
        log.info("tuned %d taps; max physical batch=%s", len(plan.branches),
                 plan.physical_batch)

    if args.mode == "auto":
        if plan is not None:
            clip_mode = plan.recommended_mode()
            log.info("--mode auto: measured recommendation is %s "
                     "(mixed_ghost=%.1fus bk_mixed=%.1fus per step)",
                     clip_mode, plan.mode_cost_us("mixed_ghost"),
                     plan.mode_cost_us("bk_mixed"))
        else:
            log.warning("--mode auto without a usable plan; staying on %s "
                        "(pass --tune or a valid --plan)", clip_mode)
        if clip_mode != engine.mode:
            # the max-batch certificate was compiled under the tuning mode;
            # book-keeping banks residuals the searched graph never
            # allocated, so re-certify under the adopted mode before
            # committing to it
            candidate = make_engine(args.batch, clip_mode)
            if plan is not None:
                candidate.use_plan(plan)
                if plan.physical_batch and plan.budget_bytes:
                    replan = candidate.recertify_max_batch(
                        state["params"], probe, hi_cap=args.tune_hi_cap
                    )
                    if args.consensus:
                        # the re-certification compiled on THIS rank's kind;
                        # the fleet adopts the mode only if every rank fits
                        # it, at the minimum batch any rank certified
                        from repro.tuner.consensus import (
                            reconcile_recertification,
                        )

                        fits, fleet_mb = reconcile_recertification(
                            replan is not None,
                            replan.physical_batch if replan is not None
                            else None,
                        )
                        if not fits:
                            replan = None
                        elif fleet_mb and fleet_mb != replan.physical_batch:
                            log.info("fleet minimum re-certified batch %d "
                                     "(this rank fit %d)", fleet_mb,
                                     replan.physical_batch)
                            replan = replan.replace_batch(
                                physical_batch=fleet_mb,
                                logical_batch=replan.logical_batch,
                                accumulation_steps=None,
                                budget_bytes=replan.budget_bytes,
                            )
                            candidate.use_plan(replan)
                    if replan is None:
                        log.warning(
                            "no batch fits the budget under %s; staying on "
                            "the certified tuning mode %s", clip_mode,
                            engine.mode,
                        )
                        clip_mode = engine.mode
                        candidate = None
                    else:
                        plan = replan
            if candidate is not None:
                engine = candidate

    physical, accum = args.batch, 1
    if plan is not None and plan.physical_batch:
        from repro.tuner import derive_accumulation

        # plan.physical_batch certifies ONE host's capacity (the probe was
        # sliced to the per-host share above); the cap on the *global*
        # microbatch scales back by the same factor — on a single host the
        # scale is 1 and this is the PR-2 behaviour unchanged
        host_scale = max(1, args.batch // probe_batch)
        physical, accum = derive_accumulation(
            args.batch, plan.physical_batch * host_scale
        )
    logical_eff = physical * accum
    if accum > 1:
        log.info(
            "tuned physical batch=%d (max %d): logical %d -> %d accumulation "
            "steps (effective logical %d)", physical, plan.physical_batch,
            args.batch, accum, logical_eff,
        )
    if logical_eff != args.batch:
        # accumulation rounding changed the per-step sample count: rebuild
        # the engine so the accountant's sampling rate (and sigma, when
        # derived from a target epsilon) match what actually runs
        log.info("effective logical batch %d != requested %d; re-deriving "
                 "privacy accounting", logical_eff, args.batch)
        engine = make_engine(logical_eff, clip_mode)
        if plan is not None:
            engine.use_plan(plan)

    # elastic fleet layout (runtime.elastic): recomputed on EVERY start —
    # including every --auto-restart attempt — from the shard count the
    # fleet actually has now ($REPRO_ELASTIC_SHARDS is the restart-time
    # seam; a scheduler or a shrink@step injector updates it between
    # attempts).  The logical batch (and with it the sampling rate q the
    # accountant composes) never changes; lost parallelism becomes extra
    # accumulation microsteps of the SAME per-shard microbatch, so a resumed
    # run replays the identical microbatch stream bit for bit.
    data_shards = current_data_shards(args.data_shards)
    if data_shards > 1 or args.elastic_max_per_shard:
        eplan = elastic_plan(
            logical_batch=logical_eff,
            data_shards=data_shards,
            max_per_shard=args.elastic_max_per_shard or physical,
        )
        physical, accum = eplan.execution(jax.process_count())
        log.info(
            "elastic layout: %d shard(s) x per-shard %d (accum %d) -> "
            "microbatch %d, %d microstep(s) per logical batch of %d",
            eplan.data_shards, eplan.per_shard_batch,
            eplan.accumulation_steps, physical, accum, logical_eff,
        )

    if args.consensus:
        # decisions derived rank-locally AFTER plan adoption — the --mode
        # auto re-certification (which can fall back per rank when nothing
        # fits) and the accumulation split — must also agree fleet-wide, or
        # ranks would trace different modes/microstep counts past the plan
        # consensus gate
        from repro.tuner.consensus import certify_fleet_value

        certify_fleet_value(
            "adopted mode/batch/policy",
            f"{clip_mode}:{physical}:{accum}:{policy.fingerprint()}:"
            f"{plan.consensus_hash() if plan is not None else '-'}",
        )

    # the adopted configuration, as actually traced: per-tap branch map +
    # kernel winners from the plan (or the analytic rule), plus the executed
    # batch layout (which elastic resharding may have reshaped past the
    # plan's own certificate)
    plan_fields = engine.plan_event_fields()
    plan_fields.update(
        mode=clip_mode, physical_batch=physical, accumulation_steps=accum,
        logical_batch=logical_eff, data_shards=data_shards,
    )
    obs.emit_event("plan_adopted", **plan_fields)

    dp = DPTrainConfig(
        clipping_mode=clip_mode,
        clip_norm=args.clip_norm,
        noise_multiplier=engine.noise_multiplier,
        logical_batch=logical_eff,
        accumulation_steps=accum,
        plan=plan,
        policy=policy,
    )
    step_fn = make_train_step(model, optimizer, schedule, dp)

    st_sh = state_shardings(model, mesh, cfg, jax.eval_shape(lambda: state))
    state = jax.tree_util.tree_map(jax.device_put, state, st_sh)

    # data (microbatches of the tuned physical size)
    def batch_fn(step, shard):
        b = synthetic_arch_batch(cfg, batch=physical, seq=seq, step=step, shard=shard)
        if args.poisson:
            key = jax.random.fold_in(jax.random.PRNGKey(4242), step)
            b["mask"] = poisson_sample_mask(key, physical, engine.sampling_rate)
        return b

    start_step = 0
    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(
            args.ckpt_dir, save_every=args.ckpt_every,
            on_saved=injection.on_checkpoint_saved if injection else None,
        )
        if args.resume and manager.latest() is not None:
            # restore to host first: a pre-policy checkpoint lacks the
            # state["policy"] subtree the sharding tree now carries, so
            # fill it with the init state before re-sharding
            start_step, rstate = manager.restore()
            if "policy" not in rstate:
                log.info("pre-policy checkpoint: starting the %s policy "
                         "state fresh", policy.name)
                rstate["policy"] = policy.init_state()
            state = jax.tree_util.tree_map(jax.device_put, rstate, st_sh)
            log.info("resumed from step %d", start_step)
            engine.record_step(start_step)

    pipeline = DataPipeline(batch_fn, start_step=start_step * accum).start()
    b_sh = batch_shardings(
        jax.eval_shape(lambda: batch_fn(0, 0)), mesh, cfg
    )
    with use_reshard_rules(mesh, cfg):
        if accum == 1:
            jit_step = jax.jit(
                step_fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                donate_argnums=(0,),
            ).lower(jax.eval_shape(lambda: state),
                    jax.eval_shape(lambda: batch_fn(0, 0))).compile()
        else:
            # virtual-step pattern: accumulate clipped grad sums over
            # physical microbatches, then noise + update once per logical
            # step.  AOT-compile INSIDE the reshard context (like the
            # accum==1 path): a lazy jit would trace at first call, outside
            # it, silently dropping every sharding constraint.
            #
            # The accumulator is a device-resident pytree DONATED through
            # every microstep and into the finalize: the fold runs inside
            # the jitted program (bank reductions overlap the accumulator
            # update), the buffers alias in place instead of
            # double-buffering per microstep, and the host loop performs no
            # sync until the logical-batch boundary.
            st_spec = jax.eval_shape(lambda: state)
            b_spec = jax.eval_shape(lambda: batch_fn(0, 0))
            micro_raw = make_clipped_microstep(model, dp)
            p_spec = st_spec["policy"]
            g_spec = jax.eval_shape(micro_raw, st_spec["params"], b_spec, p_spec)[1]
            # the policy update runs once per LOGICAL batch, over the
            # per-sample norms (and Poisson mask) of every microstep,
            # scattered into the accumulator's flat (physical*accum,)
            # buffers — one quantile release per noise addition
            acc_init = make_accum_init(g_spec, physical * accum)
            acc_spec = jax.eval_shape(acc_init)
            acc_sh = {
                "grads": st_sh["params"], "loss": None, "clip_hits": None,
                "norms": None, "mask": None,
            }
            idx_spec = jax.ShapeDtypeStruct((), jnp.int32)
            init_fn = jax.jit(
                acc_init, out_shardings=acc_sh,
            ).lower().compile()
            micro_fn = jax.jit(
                make_accum_microstep(model, dp),
                in_shardings=(
                    st_sh["params"], st_sh["policy"], acc_sh, b_sh, None,
                ),
                out_shardings=acc_sh,
                donate_argnums=(2,),
            ).lower(
                st_spec["params"], p_spec, acc_spec, b_spec, idx_spec
            ).compile()
            # state is donated (params/opt alias into the update); the
            # accumulator is NOT — its leaves are temps inside the finalize
            # (noise-add, optimizer) with no matching output to alias, so
            # donating them only triggers the unusable-donation warning
            fin_fn = jax.jit(
                make_accum_finalize(optimizer, schedule, dp),
                in_shardings=(st_sh, acc_sh), out_shardings=(st_sh, None),
                donate_argnums=(0,),
            ).lower(st_spec, acc_spec).compile()
            # microstep indices as device scalars, built once: the loop
            # body transfers nothing and never blocks mid-logical-batch
            idx_dev = [jnp.asarray(i, jnp.int32) for i in range(accum)]

    watchdog = StepWatchdog()
    preempt = PreemptionHandler().install()

    step = start_step
    try:
        while step < args.steps:
            # one annotation per logical step on the profiler's host timeline,
            # keyed by step number (`python -m repro.obs DIR --timeline` reads
            # it); the trace window opens before it and closes after it, so
            # every profiled step carries one.  The pipeline's index is `step`.
            if profile is not None:
                profile.before_step(step)
            with jax.profiler.StepTraceAnnotation(STEP_ANNOTATION, step_num=step):
                if accum == 1:
                    step_idx, batch = pipeline.next()
                    watchdog.start_step()
                    injection.on_step(step_idx)
                    state, metrics = jit_step(state, batch)
                else:
                    watchdog.start_step()
                    step_idx = step
                    injection.on_step(step_idx)
                    # every microstep is async dispatch into the donated
                    # accumulator; nothing on the host reads a device value, so
                    # the bank reductions of microstep i overlap the dispatch
                    # (and compute) of microstep i+1
                    acc = init_fn()
                    for i in range(accum):
                        _, batch = pipeline.next()
                        acc = micro_fn(
                            state["params"], state["policy"], acc, batch, idx_dev[i]
                        )
                    state, metrics = fin_fn(state, acc)
                # the ONE host sync per logical batch: bounds the dispatch
                # queue and makes the watchdog (and step_s) time executed work,
                # not the enqueue.  The step metrics ride the SAME sync, so the
                # record below reads already-materialized buffers —
                # instrumentation adds no second block_until_ready (test-asserted)
                jax.block_until_ready((state["step"], metrics))
                engine.record_step()
                engine.check_epsilon_alarm(args.epsilon_alarm_frac, step=step_idx + 1)
                dt = watchdog.end_step(step_idx)
                step = step_idx + 1
                if obs.metrics_active():
                    eps_m, delta_m = engine.privacy_spent()
                    obs.emit_metrics(
                        {
                            "kind": "train_step",
                            "loss": float(metrics["loss"]),
                            "lr": float(metrics["lr"]),
                            "clip_frac": float(metrics["clip_frac"]),
                            "norm_mean": float(metrics["norm_mean"]),
                            "norm_max": float(metrics["norm_max"]),
                            "epsilon": eps_m,
                            "delta": delta_m,
                            "step_s": dt,
                            "examples_per_s": logical_eff / dt if dt > 0 else None,
                            "physical_batch": physical,
                            "accumulation_steps": accum,
                            "mode": clip_mode,
                        },
                        step=step,
                    )
                if step % args.log_every == 0 or step == args.steps:
                    eps, delta = engine.privacy_spent()
                    log.info(
                        "step %d loss=%.4f lr=%.2e clip_frac=%.2f eps=%.3f (%.2fs/step)",
                        step, float(metrics["loss"]), float(metrics["lr"]),
                        float(metrics["clip_frac"]), eps, dt,
                    )
                if manager is not None:
                    if preempt.preempted():
                        manager.save(step, state, force=True)
                        manager.wait()
                        log.warning("preempted: checkpointed step %d, exiting", step)
                        obs.emit_event("preemption", step=step, checkpointed=True)
                        return 0
                    manager.save(step, state)
            if profile is not None:
                profile.after_step(step_idx)
    finally:
        pipeline.stop()
        preempt.uninstall()
        if profile is not None:
            profile.stop(step=step)
        if manager is not None:
            manager.save(step, state, force=True)
            manager.wait()
    eps, delta = engine.privacy_spent()
    log.info("done: %d steps, privacy spent (eps=%.3f, delta=%.1e)", step, eps, delta)
    obs.emit_event("run_finished", step=step, epsilon=eps, delta=delta)
    if args.ckpt_dir:
        _write_summary(
            args.ckpt_dir, step=step, epsilon=eps, delta=delta,
            logical_batch=logical_eff, microbatch=physical,
            accumulation_steps=accum, data_shards=data_shards,
        )
    return 0


# Deterministic failure classes: a config/shape/assertion error fails
# identically on every attempt, so restarting it only burns the budget a
# real transient (preempted host, flaky storage, injected crash) needs.
_NON_RETRYABLE = (
    AssertionError,
    ValueError,
    TypeError,
    KeyError,
    AttributeError,
    ImportError,
    NotImplementedError,
)


def is_retryable_failure(exc: BaseException) -> bool:
    """Should the --auto-restart supervisor retry after ``exc``?

    Consensus failures are deterministic fleet-configuration divergence
    (every restart re-derives the same mismatch), so they are classified
    non-retryable alongside the stdlib config-error types above.
    """
    try:
        from repro.tuner.consensus import PlanConsensusError
    except ImportError:  # pragma: no cover - tuner always ships
        PlanConsensusError = ()
    if isinstance(exc, PlanConsensusError):
        return False
    return not isinstance(exc, _NON_RETRYABLE)


def main(argv=None) -> int:
    args = parse_args(argv)
    reconfigure()  # re-apply $REPRO_LOG_LEVEL to module-level loggers
    use_compile_cache()
    # ONE injection plan for the whole supervision loop: injectors are
    # one-shot, so a crash that already fired does not re-fire after the
    # in-process restart (no args surgery needed)
    injection = _injection_for(args)
    if args.auto_restart <= 0:
        return run_once(args, injection)
    attempts = 0
    while True:
        try:
            return run_once(args, injection)
        except Exception as e:  # noqa: BLE001 — supervision loop
            if not is_retryable_failure(e):
                log.error(
                    "non-retryable failure (%s: %s): a deterministic "
                    "config/assertion error would fail every attempt — not "
                    "burning the %d-restart budget",
                    type(e).__name__, e, args.auto_restart,
                )
                raise
            attempts += 1
            if attempts > args.auto_restart:
                log.error("giving up after %d restarts", attempts - 1)
                raise
            log.warning("run failed (%s); auto-restart %d/%d from latest checkpoint",
                        e, attempts, args.auto_restart)
            # the crashed attempt's sinks are still installed (configure_run
            # keeps them for the same dir), so this lands in the same stream
            obs.emit_event(
                "restart_attempt", attempt=attempts,
                max_attempts=args.auto_restart,
                error=f"{type(e).__name__}: {e}",
            )
            # an actual copy: the previous `dataclasses.replace(args) if
            # is_dataclass(args) else args` was a no-op on an
            # argparse.Namespace, silently mutating the caller's args
            args = argparse.Namespace(**vars(args))
            args.resume = True
            time.sleep(0.5)


if __name__ == "__main__":
    sys.exit(main())
