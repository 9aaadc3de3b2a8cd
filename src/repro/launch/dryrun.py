from repro.launch.env import apply_env

# full harness (allocator, markers, preallocate-off) + the 512-device
# host platform this dry run lowers against — BEFORE jax initializes
apply_env(host_devices=512)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: the SPMD
partitioner must accept every sharding, the compiled program's memory
analysis must fit the chip, and the collective schedule feeds the roofline.

Roofline methodology (EXPERIMENTS.md):
- compute term: ANALYTIC flops (launch/analytic.py) — XLA's cost_analysis
  counts while-loop bodies once, undercounting every lax.scan.
- memory + collective terms: HLO-parsed, with the layer-scan undercount
  corrected by depth extrapolation: lower 1-period and 2-period variants of
  the arch, take the per-period delta, extrapolate to full depth.
- memory FIT: compiled.memory_analysis() of the full-depth program (exact).

Usage:
    python -m repro.launch.dryrun [--arch qwen2-72b] [--shape train_4k]
        [--mesh single|multi|both] [--mode mixed_ghost] [--out results/dryrun]
        [--no-calibrate]
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.configs.registry import ARCHS, SHAPES, build_model, get_arch, get_shape
from repro.core.clipping import MODES, discover_meta
from repro.core.taps import ClipRuntime
from repro.launch import analysis
from repro.launch.analytic import cell_flops, extra_fwd_flops, serve_matmul_flops
from repro.launch.flops import model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_token_specs,
    prefill_batch_specs,
    serve_state_specs,
    train_batch_specs,
)
from repro.launch.steps import (
    DPTrainConfig,
    abstract_train_state,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.optim import adam, warmup_cosine
from repro.parallel.reshard import use_reshard_rules
from repro.parallel.sharding import (
    batch_shardings,
    param_shardings,
    serve_state_shardings,
    state_shardings,
)
from repro.utils.logging import get_logger

log = get_logger("dryrun")

# the production meshes model v5e pods; the lowering itself runs on
# placeholder host devices, so the roofline names the kind it models
TARGET_DEVICE_KIND = "TPU v5 lite"


def _lower(cfg: ArchConfig, shape: ShapeConfig, mode: str, mesh):
    """Build the step for one cell and AOT-compile it.

    The explicit FSDP gather plan (reshard_param) is a TRAIN optimization:
    at decode/prefill the activations are small and GSPMD's native plan
    (keep weights sharded, replicate/reduce small activations) wins —
    measured 2-4x on jamba/mixtral serve cells, so serving lowers without
    the reshard context.
    """
    if shape.kind == "train":
        with use_reshard_rules(mesh, cfg):
            return _lower_inner(cfg, shape, mode, mesh)
    return _lower_inner(cfg, shape, mode, mesh)


def _lower_inner(cfg: ArchConfig, shape: ShapeConfig, mode: str, mesh):
    model = build_model(cfg)
    if shape.kind == "train":
        optimizer = adam(state_dtype=jnp.dtype(cfg.opt_state_dtype))
        dp = DPTrainConfig(
            clipping_mode=mode, clip_norm=1.0, noise_multiplier=1.0,
            logical_batch=shape.global_batch,
        )
        step = make_train_step(model, optimizer, warmup_cosine(1e-3, 100, 10000), dp)
        state_spec = abstract_train_state(model, optimizer)
        batch_spec = train_batch_specs(cfg, shape, shape.global_batch)
        st_sh = state_shardings(model, mesh, cfg, state_spec)
        b_sh = batch_shardings(batch_spec, mesh, cfg)
        lowered = jax.jit(
            step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
            donate_argnums=(0,),
        ).lower(state_spec, batch_spec)
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        p_sh = param_shardings(model, mesh, cfg)
        params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        batch_spec = prefill_batch_specs(cfg, shape, shape.global_batch)
        state_spec = serve_state_specs(model, cfg, shape, shape.global_batch)
        b_sh = batch_shardings(batch_spec, mesh, cfg)
        s_sh = serve_state_shardings(mesh, cfg, state_spec, shape.global_batch)
        lowered = jax.jit(
            step, in_shardings=(p_sh, b_sh, s_sh), out_shardings=(None, s_sh),
            donate_argnums=(2,),
        ).lower(params_spec, batch_spec, state_spec)
    else:  # decode
        step = make_decode_step(model)
        p_sh = param_shardings(model, mesh, cfg)
        params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        tok_spec = decode_token_specs(shape.global_batch)
        state_spec = serve_state_specs(model, cfg, shape, shape.global_batch)
        s_sh = serve_state_shardings(mesh, cfg, state_spec, shape.global_batch)
        t_sh = batch_shardings(tok_spec, mesh, cfg)
        lowered = jax.jit(
            step, in_shardings=(p_sh, t_sh, s_sh),
            out_shardings=(t_sh, None, s_sh), donate_argnums=(2,),
        ).lower(params_spec, tok_spec, state_spec)
    return lowered.compile()


def _hlo_stats(compiled):
    cost = compiled.cost_analysis()
    byts = float(cost.get("bytes accessed", 0.0))
    colls = analysis.parse_collectives(compiled.as_text())
    return byts, colls.wire_bytes, colls.to_dict()


def _period_len(cfg: ArchConfig) -> int:
    if cfg.block_pattern:
        return len(cfg.block_pattern)
    return cfg.moe_every if cfg.moe_experts else 1


def _depth_variant(cfg: ArchConfig, periods: int) -> ArchConfig:
    p_len = _period_len(cfg)
    kw = {"n_layers": periods * p_len}
    if cfg.encoder_layers:
        kw["encoder_layers"] = periods
        kw["n_layers"] = periods
    return dataclasses.replace(cfg, **kw)


def analytic_flops(cfg: ArchConfig, shape: ShapeConfig, mode: str) -> dict:
    model = build_model(cfg)
    if shape.kind == "train":
        runtime = ClipRuntime(mode=mode)
        state_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        batch_spec = train_batch_specs(cfg, shape, shape.global_batch)
        meta = discover_meta(model.loss_with_ctx, state_spec, batch_spec, clip=runtime)
        return cell_flops(meta, cfg, shape, mode).to_dict()
    fwd = serve_matmul_flops(model, cfg, shape) + extra_fwd_flops(cfg, shape)
    return {"fwd": fwd, "total": fwd, "norms": 0.0}


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               mode: str = "mixed_ghost", calibrate: bool = True):
    """Lower+compile one cell; returns (compiled, meta dict)."""
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    if not cfg.supports(shape):
        return None, {"status": "skipped",
                      "arch": arch_name, "shape": shape_name,
                      "mesh": "2x16x16" if multi_pod else "16x16",
                      "reason": "full-attention arch: long_500k not runnable "
                                "(noted in DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size

    compiled = _lower(cfg, shape, mode, mesh)
    raw_bytes, raw_wire, coll_detail = _hlo_stats(compiled)

    # depth extrapolation for scan-undercounted bytes/collectives
    n_periods = cfg.n_layers // _period_len(cfg)
    if cfg.encoder_layers:
        n_periods = cfg.n_layers
    if calibrate and n_periods >= 2:
        c1 = _lower(_depth_variant(cfg, 1), shape, mode, mesh)
        b1, w1, _ = _hlo_stats(c1)
        del c1
        c2 = _lower(_depth_variant(cfg, 2), shape, mode, mesh)
        b2, w2, _ = _hlo_stats(c2)
        del c2
        # per-period deltas can be slightly negative when fixed costs dominate
        # (partitioner noise between depth variants): clamp at zero
        bytes_corr = b1 + (n_periods - 1) * max(b2 - b1, 0.0)
        wire_corr = w1 + (n_periods - 1) * max(w2 - w1, 0.0)
    else:
        bytes_corr, wire_corr = raw_bytes, raw_wire

    flops = analytic_flops(cfg, shape, mode)
    mflops = model_flops(build_model(cfg), cfg, shape)

    terms = analysis.roofline_terms(
        compiled,
        device_kind=TARGET_DEVICE_KIND,
        n_devices=n_devices,
        flops_global=flops["total"],
        bytes_per_device=bytes_corr,
        wire_bytes_per_device=wire_corr,
        model_flops=mflops,
    )
    meta = {
        "status": "ok",
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_devices,
        "kind": shape.kind,
        "clipping_mode": mode if shape.kind == "train" else None,
        "analytic_flops": flops,
        "hlo_raw": {"bytes": raw_bytes, "wire_bytes": raw_wire,
                    "collectives": coll_detail},
        "roofline": terms.to_dict(),
    }
    return compiled, meta


def run_cell(arch_name, shape_name, *, multi_pod, mode, out_dir,
             resume=True, calibrate=True):
    tag = f"{'multi' if multi_pod else 'single'}/{arch_name}__{shape_name}"
    prior = (pathlib.Path(out_dir) / ("multi" if multi_pod else "single")
             / f"{arch_name}__{shape_name}.json")
    if resume and prior.exists():
        meta = json.loads(prior.read_text())
        if meta.get("status") in ("ok", "skipped"):
            meta.setdefault("mesh", "2x16x16" if multi_pod else "16x16")
            meta.setdefault("arch", arch_name)
            meta.setdefault("shape", shape_name)
            log.info("%s: cached %s", tag, meta["status"])
            return meta
    t0 = time.time()
    try:
        compiled, meta = lower_cell(
            arch_name, shape_name, multi_pod=multi_pod, mode=mode,
            calibrate=calibrate,
        )
        if compiled is not None:
            print(f"[{tag}] memory_analysis:", compiled.memory_analysis())
            cost = compiled.cost_analysis()
            print(f"[{tag}] cost_analysis: flops={cost.get('flops', 0):.3e} "
                  f"bytes={cost.get('bytes accessed', 0):.3e}")
    except Exception as e:  # noqa: BLE001 — any failure is a recorded bug
        meta = {
            "status": "error",
            "arch": arch_name,
            "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    meta["elapsed_s"] = round(time.time() - t0, 1)
    out = pathlib.Path(out_dir) / ("multi" if multi_pod else "single")
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{arch_name}__{shape_name}.json").write_text(json.dumps(meta, indent=2))
    status = meta["status"]
    extra = meta.get("error", "")[:140] if status == "error" else (
        meta.get("roofline", {}).get("bottleneck", "") if status == "ok" else
        meta.get("reason", ""))
    log.info("%s: %s (%.1fs) %s", tag, status, meta["elapsed_s"], extra)
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="mixed_ghost", choices=MODES)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    summary = []
    for multi in meshes:
        for a in archs:
            for s in shapes:
                meta = run_cell(a, s, multi_pod=multi, mode=args.mode,
                                out_dir=args.out, resume=not args.no_resume,
                                calibrate=not args.no_calibrate)
                summary.append((a, s, meta["mesh"], meta["status"]))
    n_ok = sum(1 for *_, st in summary if st == "ok")
    n_skip = sum(1 for *_, st in summary if st == "skipped")
    n_err = len(summary) - n_ok - n_skip
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok / {n_skip} skipped / {n_err} errors "
          f"of {len(summary)} cells")
    for a, s, m, st in summary:
        if st == "error":
            print(f"  ERROR {m} {a} {s}")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
