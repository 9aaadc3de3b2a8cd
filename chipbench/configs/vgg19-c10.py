"""VGG19 with GroupNorm on CIFAR-10 shapes, trained through ``PrivacyEngine``.

The loop is the one a user writes (``examples/dp_finetune_cnn.py``): the
engine's jitted ``clipped_grad_fn``, then ``privatize`` and Adam in one
jitted update that donates the parameters and the optimizer state, then
``record_step`` on the host.  ``mode="non_private"`` runs the same loop
with the mean gradient and no noise.

Variants build the same loop broken or lowered on purpose, for the checks
of the comparison (``chipbench/calibrate.py`` and the tests):
``unchanged`` returns the state it was given; ``half_batch`` clips and
sums only the first half of each batch and divides by that half;
``sum_dropped`` hands the noise a zero clipped-gradient sum, so the step
trains on noise alone.  ``reference_control`` is the reference put in the
program's place with its conv and matmul operands in float8, the step
below the bfloat16 operands the TPU gives the program's float32 convs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import bench, data, refops, taps, work

REF = bench.load_reference("vgg19-c10")
VARIANTS = ("program", "unchanged", "half_batch", "sum_dropped")
CONTROL_DTYPE = "float8_e4m3fn"


def model_flops_per_sample(cfg: dict, wl: dict) -> float:
    return work.vgg_train_flops(cfg["plan"], image=cfg["image"], in_ch=cfg["channels"],
                                n_classes=cfg["n_classes"])


class Trainer:
    """One compiled training loop and the functions to read its state."""

    def __init__(self, cfg: dict, wl: dict, variant: str = "program"):
        from repro.core.engine import PrivacyEngine
        from repro.models.cnn import VGG, VGG_PLANS
        from repro.optim import adam, apply_updates

        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
        if list(VGG_PLANS[cfg["arch"]]) != list(cfg["plan"]):
            raise ValueError(f"{cfg['arch']} in the program is not the plan of the config")
        self.cfg, self.wl, self.variant = cfg, wl, variant
        self.samples_per_step = wl["batch"]
        model = VGG(cfg["arch"], n_classes=cfg["n_classes"], in_ch=cfg["channels"],
                    groups=cfg["groups"], dtype=jnp.dtype(cfg["dtype"]))
        batch = wl["batch"] // 2 if variant == "half_batch" else wl["batch"]
        mode, opt_cfg = wl["mode"], wl["optimizer"]
        self.engine = PrivacyEngine(
            loss_with_ctx=model.loss_with_ctx, batch_size=batch,
            sample_size=wl["sample_size"], steps=wl["accounting_steps"],
            max_grad_norm=wl["clip_norm"], noise_multiplier=wl["noise_multiplier"], mode=mode,
        )
        opt = adam(opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"])
        self.b1 = opt_cfg["b1"]
        self.noise_std = None if mode == "non_private" else (
            wl["noise_multiplier"] * wl["clip_norm"])
        engine = self.engine

        def update(state, grad_sum, norms):
            params, step = state["params"], state["step"]
            if mode == "non_private":
                grads = jax.tree_util.tree_map(lambda g: g / batch, grad_sum)
            else:
                grads = engine.privatize(grad_sum, jax.random.fold_in(state["noise_key"], step))
            upd, opt_state = opt.update(grads, state["opt"], params, step, opt_cfg["lr"])
            new = {"params": apply_updates(params, upd), "opt": opt_state,
                   "step": step + 1, "noise_key": state["noise_key"]}
            return new, {"norm_mean": jnp.mean(norms), "norm_max": jnp.max(norms),
                         "per_sample_norms": norms}

        def init_state(wkey, nkey):
            params = REF.init_params(cfg, wkey)
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32), "noise_key": nkey}

        self._init = jax.jit(init_state)
        state_spec = jax.eval_shape(self._init, jax.random.PRNGKey(0), jax.random.PRNGKey(0))
        abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if jax.tree_util.tree_structure(abstract) != jax.tree_util.tree_structure(
                state_spec["params"]):
            raise ValueError("the benchmark's weights do not have the program's tree")
        make = functools.partial(data.image_batch, batch=wl["batch"], image=cfg["image"],
                                 channels=cfg["channels"], n_classes=cfg["n_classes"])
        self._batch = jax.jit(make)
        b_spec = jax.eval_shape(make, jax.random.PRNGKey(0), jax.random.PRNGKey(0), 0)
        if variant == "half_batch":
            b_spec = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((batch,) + s.shape[1:], s.dtype), b_spec)
        grad_fn = engine.clipped_grad_fn()
        self._grad = jax.jit(grad_fn).lower(state_spec["params"], b_spec).compile()
        _, g_spec, aux_spec = jax.eval_shape(grad_fn, state_spec["params"], b_spec)
        donate = () if variant == "unchanged" else (0,)
        self._update = jax.jit(update, donate_argnums=donate).lower(
            state_spec, g_spec, aux_spec["per_sample_norms"]).compile()
        self._norms = jax.jit(refops.leaf_norms)
        self._sum = jax.jit(functools.partial(refops.first_sum_norms, batch=wl["batch"],
                                              b1=self.b1, std=self.noise_std))
        self._change = jax.jit(lambda p, wkey: refops.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, REF.init_params(cfg, wkey))))
        self.leaf_names = refops.leaf_names(state_spec["params"])
        self._seed = None
        self._model, self._specs = model, (state_spec["params"], b_spec)

    # -- the timed path -----------------------------------------------------
    def init_state(self, seed: int) -> dict:
        self._seed = seed
        self._keys = (data.stream_key(seed, "data"), data.stream_key(seed, "classes"))
        return self._init(data.stream_key(seed, "weights"), data.stream_key(seed, "noise"))

    def batch(self, step: int) -> dict:
        return self._batch(*self._keys, jnp.int32(step))

    def step(self, state: dict, batch: dict) -> tuple[dict, dict]:
        """Dispatch one step; (the new state, its loss and norm statistics)."""
        if self.variant == "half_batch":
            batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        loss, grad_sum, aux = self._grad(state["params"], batch)
        if self.variant == "sum_dropped":
            grad_sum = jax.tree_util.tree_map(jnp.zeros_like, grad_sum)
        new, stats = self._update(state, grad_sum, aux["per_sample_norms"])
        return (state if self.variant == "unchanged" else new), {"loss": loss, **stats}

    def record_step(self) -> None:
        self.engine.record_step()

    def ghost_norm_taps(self) -> list[tuple]:
        """(N, T, D, p, a bytes, g bytes) of each tap the program routes to
        the ghost norm, from its own tap discovery and decision."""
        return taps.ghost_norm_taps(self._model.loss_with_ctx, *self._specs, self.wl["mode"])

    # -- readings for the comparison ------------------------------------------
    def first_grad_norms(self, state: dict) -> jax.Array:
        """Per-leaf norms of the gradient Adam took, from its state after one
        step: m = (1 - b1) g."""
        return self._norms(state["opt"]["m"]) / (1.0 - self.b1)

    def first_sum_norms(self, state: dict) -> jax.Array:
        """Per-leaf norms of the first step's clipped-gradient sum, from
        Adam's state after one step with the step's noise taken out."""
        key = jax.random.fold_in(data.stream_key(self._seed, "noise"), 0)
        return self._sum(state["opt"]["m"], key)

    def change_norms(self, state: dict) -> jax.Array:
        return self._change(state["params"], data.stream_key(self._seed, "weights"))


def reference(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    return REF.first_steps(cfg, wl, seed, n_steps)


def reference_control(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    """The reference in the program's place, its conv and matmul operands in
    float8."""
    return REF.first_steps(cfg, wl, seed, n_steps, quant=CONTROL_DTYPE)
