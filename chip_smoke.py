"""Smoke run of DP training on a TPU: the quickest proof that the system starts.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

One chip runs three phases in this one process:

1. device check: the platform must be ``tpu`` and the clipping kernels must
   dispatch to compiled Pallas (never interpret mode, never a CPU fallback);
2. VGG19 (GroupNorm) on CIFAR-10-shaped data, 32x32x3, 10 classes, physical
   batch 256, through ``PrivacyEngine``: 3 DP steps under ``mixed_ghost`` and
   3 under ``bk_mixed``, then per-sample norms on 8 samples checked against
   the XLA kernels and against the ``vmap`` oracle;
3. the train CLI, in process, on ``xlstm-350m`` at published width
   (seq 4096), 2 steps of batch 2 under ``mixed_ghost``.

``--chips 4`` runs only the data-parallel phase: one clipped step of
xlstm-350m at published width, logical batch 8, on the 4-chip host mesh,
compared with the same step on one device (same seeds, no noise), both in
float32 (see ``DP_SEQ``).

Weights and data are random, made from ``SEED``.  Times printed here are
smoke timings, not benchmarks.  The last line of stdout is one JSON object
naming the device.  Every phase runs; if any fails, the run exits non-zero
and never prints that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import statistics
import sys
import tempfile
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
VGG_BATCH = 256
VGG_STEPS = 3  # per clipping mode
PARITY_SAMPLES = 8
# Tolerances on per-sample norms, as max relative error over the samples.
# Pallas vs XLA: both run the same forward and backward at the default
# precision (a TPU f32 matmul then rounds its operands to bf16, 2^-9
# relative) and differ only in how the ghost taps' Gram sums are tiled and
# accumulated; the ghost taps are a part of each norm, and rounding errors
# of opposite sign cancel in their sums, so 1e-2 is several times the
# error of one bf16 rounding.
PALLAS_VS_XLA_RTOL = 1e-2
# Pallas at default precision vs the vmap oracle at "highest" (f32
# passes): here the whole forward and backward differ by the bf16
# roundings of 19 layers, which compound through the depth; 5e-2 still
# catches a missing or doubled tap, which moves a norm by far more.
PALLAS_VS_VMAP_RTOL = 5e-2
TRAIN_ARGV = [
    "--arch", "xlstm-350m", "--steps", "2", "--batch", "2",
    "--log-every", "1", "--mode", "mixed_ghost",
]
DP_ARCH = "xlstm-350m"
DP_BATCH = 8
DP_MICRO = 2  # the one-device reference's microbatch
# The 4-chip comparison computes in float32 with f32 matmul passes.  In the
# published bfloat16 compute dtype the two programs round differently where
# XLA fuses them differently, and the backward through the recurrence
# amplifies it: on a TPU v5e at seq 4096 the clipped-gradient sums differed
# by 1.75e-1 (rel L2) and per-sample norms by 3.3e-2, at a loss 3.8e-5
# apart, which would hide a sharding bug.  Float32 activations at seq 4096
# do not fit the one-device reference into 16 GB, hence seq 2048.
DP_SEQ = 2048
# 4 chips vs 1 in float32: the same math; only the partitioning (a
# cross-device sum of the per-shard clipped gradients) and XLA's fusion
# choices change the rounding order.  On one TPU v5e, this step over the
# same 8 samples taken 1 or 2 at a time differs by 7.6e-6 (1.2e-5 in the
# worst leaf, 2e-6 in the per-sample norms); on 4 virtual CPU devices the
# mesh agrees with one device to 1.3e-6 per leaf.  So rounding stays two
# orders of magnitude under the tolerances, while a sharding bug (a missing
# or doubled reduction, all work on one device) moves the sum, or the leaf
# it lives in, by O(1).  Per-sample norms are checked as well as the sum: a
# wrong norm moves the clip factor of its sample.
DP_LOSS_RTOL = 1e-4
DP_GRAD_RTOL = 1e-3  # clipped-gradient sum, relative L2: whole tree and each leaf
DP_NORM_RTOL = 1e-3  # per-sample norms, max relative error


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_check(chips: int) -> jax.Device:
    devices = jax.devices()
    d0 = devices[0]
    print(f"devices: {devices}")
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {d0.platform!r}")
    if len(devices) != chips:
        sys.exit(f"chip_smoke: asked for {chips} chip(s), found {len(devices)}")
    from repro.kernels import dispatch

    check(dispatch.backend() == "tpu",
          f"kernel dispatch targets {dispatch.backend()!r}, not tpu")
    for op in dispatch.OPS:
        check(dispatch.resolve(op) == "pallas",
              f"{op} resolves to {dispatch.resolve(op)!r}, not pallas")
    return d0


def peak_gb(device: jax.Device) -> float:
    return device.memory_stats()["peak_bytes_in_use"] / 1e9


def max_rel_err(got: jax.Array, want: jax.Array) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want) / jnp.abs(want)))


def vgg_engine(model, mode: str):
    from repro.core.engine import PrivacyEngine

    return PrivacyEngine(
        loss_with_ctx=model.loss_with_ctx, batch_size=VGG_BATCH,
        sample_size=50_000, steps=2 * VGG_STEPS, max_grad_norm=1.0,
        noise_multiplier=1.0, mode=mode,
    )


def vgg19_phase(device: jax.Device) -> None:
    from repro.data.synthetic import synthetic_vision_batch
    from repro.kernels import dispatch
    from repro.models.cnn import VGG
    from repro.optim import adam, apply_updates

    model = VGG("vgg19", n_classes=10)
    params = model.init(jax.random.PRNGKey(SEED))
    print(f"vgg19: {sum(x.size for x in jax.tree_util.tree_leaves(params)):,} "
          f"params, batch {VGG_BATCH} of 32x32x3")

    def batch(step: int) -> dict:
        return synthetic_vision_batch(
            batch=VGG_BATCH, image=32, channels=3, n_classes=10, step=step,
            seed=SEED,
        )

    opt = adam()
    opt_state = opt.init(params)
    noise_key = jax.random.PRNGKey(SEED + 1)
    step = 0
    for mode in ("mixed_ghost", "bk_mixed"):
        engine = vgg_engine(model, mode)
        engine.validate(params, batch(0))

        def update(params, opt_state, grad_sum, key, step, engine=engine):
            grads = engine.privatize(grad_sum, key)
            upd, opt_state = opt.update(grads, opt_state, params, step, 1e-3)
            return apply_updates(params, upd), opt_state

        t0 = time.perf_counter()
        grad_fn = jax.jit(engine.clipped_grad_fn()).lower(params, batch(0)).compile()
        g_spec = jax.eval_shape(engine.clipped_grad_fn(), params, batch(0))[1]
        update_fn = jax.jit(update).lower(
            params, opt_state, g_spec, noise_key, jnp.int32(0)
        ).compile()
        compile_s = time.perf_counter() - t0
        n_calls = grad_fn.as_text().count("tpu_custom_call")
        check(n_calls > 0, f"vgg19 {mode}: no tpu_custom_call in the clipped step")
        times = []
        for _ in range(VGG_STEPS):
            b = jax.block_until_ready(batch(step))
            t = time.perf_counter()
            loss, grad_sum, _ = grad_fn(params, b)
            params, opt_state = update_fn(
                params, opt_state, grad_sum, jax.random.fold_in(noise_key, step),
                jnp.int32(step),
            )
            loss = float(loss)  # waits for the step
            jax.block_until_ready(params)
            times.append(time.perf_counter() - t)
            engine.record_step()
            check(math.isfinite(loss), f"vgg19 {mode} step {step}: loss {loss}")
            print(f"vgg19 {mode} step {step}: loss={loss:.4f}")
            step += 1
        print(f"vgg19 {mode}: compile {compile_s:.1f}s, tpu_custom_call x{n_calls}, "
              f"smoke step {statistics.median(times[1:]):.4f}s "
              f"(steps {', '.join(f'{x:.4f}' for x in times)}), "
              f"peak {peak_gb(device):.2f} GB")

    # per-sample norms on a slice: compiled Pallas vs XLA kernels vs vmap
    small = jax.tree_util.tree_map(lambda x: x[:PARITY_SAMPLES], batch(0))

    def norms(mode: str) -> jax.Array:
        fn = jax.jit(vgg_engine(model, mode).clipped_grad_fn())
        return fn(params, small)[2]["per_sample_norms"]

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        oracle = norms("vmap")
    for mode in ("mixed_ghost", "bk_mixed"):
        pallas = norms(mode)
        with dispatch.force_impl("xla"):
            xla = norms(mode)
        e_xla, e_vmap = max_rel_err(pallas, xla), max_rel_err(pallas, oracle)
        print(f"vgg19 {mode} per-sample norms, {PARITY_SAMPLES} samples: "
              f"pallas vs xla max rel err {e_xla:.3e} (tol {PALLAS_VS_XLA_RTOL}), "
              f"pallas vs vmap@highest {e_vmap:.3e} (tol {PALLAS_VS_VMAP_RTOL})")
        check(e_xla <= PALLAS_VS_XLA_RTOL, f"{mode}: pallas vs xla {e_xla:.3e}")
        check(e_vmap <= PALLAS_VS_VMAP_RTOL, f"{mode}: pallas vs vmap {e_vmap:.3e}")
    print(f"vgg19 parity: {time.perf_counter() - t0:.1f}s incl. compiles")


def train_cli_phase(device: jax.Device) -> None:
    from repro.launch import train

    with tempfile.TemporaryDirectory() as obs_dir:
        t0 = time.perf_counter()
        rc = train.main(TRAIN_ARGV + ["--obs-dir", obs_dir])
        wall = time.perf_counter() - t0
        check(rc == 0, f"train CLI exited {rc}")
        rows = [
            json.loads(line)
            for line in (pathlib.Path(obs_dir) / "metrics.jsonl").read_text().splitlines()
        ]
    steps = [r for r in rows if r.get("kind") == "train_step"]
    check([r["step"] for r in steps] == [1, 2], f"train CLI steps: {steps}")
    for r in steps:
        check(math.isfinite(r["loss"]), f"train CLI step {r['step']}: loss {r['loss']}")
        print(f"train CLI {' '.join(TRAIN_ARGV)}: step {r['step']} "
              f"loss={r['loss']:.4f} smoke step {r['step_s']:.3f}s")
    print(f"train CLI: {wall:.1f}s wall incl. compile, peak {peak_gb(device):.2f} GB")


def mesh_clipped_step(model, dp, cfg, params, batch) -> tuple:
    """One clipped step of ``batch`` on the host mesh, in the mesh's layout:
    (loss, clipped-grad sum, per-sample norms, compile seconds)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_clipped_microstep
    from repro.parallel.reshard import use_reshard_rules
    from repro.parallel.sharding import batch_shardings, param_shardings

    mesh = make_host_mesh()
    p_sh = param_shardings(model, mesh, cfg)
    b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh, cfg)
    t0 = time.perf_counter()
    with use_reshard_rules(mesh, cfg), jax.default_matmul_precision("highest"):
        step = jax.jit(
            make_clipped_microstep(model, dp), in_shardings=(p_sh, b_sh),
            out_shardings=(None, p_sh, None),
        ).lower(params, batch).compile()
    compile_s = time.perf_counter() - t0
    loss, grads, aux = step(jax.device_put(params, p_sh), jax.device_put(batch, b_sh))
    return float(loss), grads, aux["per_sample_norms"], compile_s


def one_device_clipped_step(model, dp, params, batch, micro: int, device) -> tuple:
    """The same step on ``device`` alone, ``micro`` samples at a time:
    (loss, clipped-grad sum on the host, per-sample norms, compile seconds).

    Clipping is per sample, so the clipped-gradient sum is additive over
    microbatches and the loss is the mean of the microbatch means (the
    accumulation path of the train CLI).
    """
    from repro.launch.steps import make_clipped_microstep

    one_sh = jax.sharding.SingleDeviceSharding(device)
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    n_micro = n // micro

    def part(i: int) -> dict:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x[i * micro:(i + 1) * micro], one_sh), batch
        )

    t0 = time.perf_counter()
    # a fresh step function: jit's trace cache is keyed on the function, and
    # a sharded trace holds the mesh's sharding constraints
    with jax.default_matmul_precision("highest"):
        step = jax.jit(
            make_clipped_microstep(model, dp), in_shardings=one_sh,
            out_shardings=one_sh,
        ).lower(params, part(0)).compile()
    compile_s = time.perf_counter() - t0
    params = jax.device_put(params, one_sh)
    loss_sum, grads_sum, norms = 0.0, None, []
    for i in range(n_micro):
        loss, grads, aux = step(params, part(i))
        loss_sum += float(loss)
        # summed on the host: the step's temporaries fill most of the HBM
        grads = jax.device_get(grads)
        grads_sum = grads if grads_sum is None else jax.tree_util.tree_map(
            np.add, grads_sum, grads
        )
        norms.append(jax.device_get(aux["per_sample_norms"]))
    return loss_sum / n_micro, grads_sum, np.concatenate(norms), compile_s


def grad_errors(got, want) -> tuple[float, dict]:
    """Relative L2 error of a gradient tree as a whole, and leaf by leaf
    (keyed by the leaf's path)."""
    diff = ref = 0.0
    leaves = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        d, r = float(np.sum((a - b) ** 2)), float(np.sum(b ** 2))
        diff, ref = diff + d, ref + r
        leaves[jax.tree_util.keystr(path)] = math.sqrt(d / r) if r else math.sqrt(d)
    return math.sqrt(diff / ref), leaves


def data_parallel_case(cfg, mode: str, batch_size: int, seq: int, micro: int,
                       devices: list) -> dict:
    """One clipped step of ``cfg`` on the host mesh against the same samples
    on ``devices[0]`` (same seeds, no noise).  Returns both results, the
    devices that hold the mesh's gradients and their peak memory."""
    from repro.configs.registry import build_model
    from repro.data.synthetic import synthetic_arch_batch
    from repro.launch.steps import DPTrainConfig

    model = build_model(cfg)
    dp = DPTrainConfig(clipping_mode=mode, logical_batch=batch_size)
    params = model.init(jax.random.PRNGKey(SEED))
    batch = synthetic_arch_batch(cfg, batch=batch_size, seq=seq)
    loss4, grads4, norms4, compile4 = mesh_clipped_step(model, dp, cfg, params, batch)
    held = {d.id for leaf in jax.tree_util.tree_leaves(grads4)
            for d in leaf.sharding.device_set}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9
             for d in devices]
    mesh = (loss4, jax.device_get(grads4), jax.device_get(norms4), compile4)
    one = one_device_clipped_step(model, dp, params, batch, micro, devices[0])
    return {"mesh": mesh, "one": one, "held": held, "peaks": peaks}


def data_parallel_phase(devices: list) -> None:
    from repro.configs.registry import get_arch

    cfg = dataclasses.replace(get_arch(DP_ARCH), dtype="float32")
    res = data_parallel_case(cfg, "mixed_ghost", DP_BATCH, DP_SEQ, DP_MICRO, devices)
    loss4, grads4, norms4, compile4 = res["mesh"]
    loss1, grads1, norms1, compile1 = res["one"]
    held, peaks = res["held"], res["peaks"]
    print(f"{DP_ARCH} float32 clipped step, seq {DP_SEQ}, batch {DP_BATCH}, on "
          f"{len(devices)} chips: compile {compile4:.1f}s, loss={loss4:.6f}, grads on "
          f"devices {sorted(held)}, peak GB per device {[f'{p:.2f}' for p in peaks]}")
    print(f"{DP_ARCH} same samples on 1 device, {DP_BATCH // DP_MICRO} x {DP_MICRO}: "
          f"compile {compile1:.1f}s, loss={loss1:.6f}, "
          f"peak {peak_gb(devices[0]):.2f} GB")
    e_loss = abs(loss4 - loss1) / abs(loss1)
    e_grad, e_leaves = grad_errors(grads4, grads1)
    e_norms = float(np.max(np.abs(norms4 - norms1) / np.abs(norms1)))
    worst = sorted(e_leaves.items(), key=lambda kv: -kv[1])
    print(f"{len(devices)} chips vs 1: loss rel err {e_loss:.3e} (tol {DP_LOSS_RTOL}), "
          f"clipped-grad-sum rel L2 err {e_grad:.3e} (tol {DP_GRAD_RTOL}), "
          f"worst leaf {worst[0][1]:.3e} (tol {DP_GRAD_RTOL}), "
          f"per-sample norms max rel err {e_norms:.3e} (tol {DP_NORM_RTOL})")
    print("per-sample norms, 4 chips: " + " ".join(f"{x:.6g}" for x in norms4))
    print("per-sample norms, 1 chip:  " + " ".join(f"{x:.6g}" for x in norms1))
    print("leaves by rel L2 err: " + ", ".join(f"{k} {v:.2e}" for k, v in worst[:12]))
    check(len(held) == len(devices), f"clipped grads live on devices {held}")
    check(min(peaks) > 0.25 * max(peaks),
          f"uneven device memory {peaks}: work is not spread over the mesh")
    check(math.isfinite(loss4) and e_loss <= DP_LOSS_RTOL, f"loss err {e_loss}")
    check(e_grad <= DP_GRAD_RTOL, f"clipped-grad-sum err {e_grad}")
    check(worst[0][1] <= DP_GRAD_RTOL, f"leaf {worst[0][0]} err {worst[0][1]}")
    check(e_norms <= DP_NORM_RTOL, f"per-sample norms err {e_norms}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    device = device_check(args.chips)

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        phases = {"data-parallel": lambda: data_parallel_phase(jax.devices())}
    else:
        phases = {"vgg19": lambda: vgg19_phase(device),
                  "train CLI": lambda: train_cli_phase(device)}
    t0 = time.perf_counter()
    failed = []
    for name, phase in phases.items():
        # every phase runs, so one run reports on all of them; any failure
        # still ends the run non-zero, without the result line
        try:
            phase()
        except Exception:  # noqa: BLE001 - reported, and fails the run below
            traceback.print_exc()
            failed.append(name)
    if failed:
        sys.exit(f"chip_smoke: failed phase(s): {', '.join(failed)}")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
