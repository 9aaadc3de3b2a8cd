"""BEiT-large/16 at 224 px on CIFAR-10 shapes, trained through ``PrivacyEngine``.

The loop is the one a user writes (``examples/dp_finetune_cnn.py``, the VGG
cell's): the engine's jitted ``clipped_grad_fn``, then ``privatize`` and
Adam in one jitted update that donates the parameters and the optimizer
state, then ``record_step`` on the host.  The model is the program's
``models/vit.BEiT`` under ``configs/paper_native.BEIT_LARGE``, with the
widths of the configuration file and the reference's weights (its
``init_params``: the layer-scale gammas at ``weights_gamma``).

Variants build the same loop broken on purpose, for the checks of the
comparison (``chipbench/calibrate.py`` and the tests): ``unchanged``
returns the state it was given; ``half_batch`` clips and sums only the
first half of each batch and divides by that half; ``sum_dropped`` hands
the noise a zero clipped-gradient sum.  ``reference_control`` is the
reference put in the program's place with its conv and matmul operands in
float8, the step below the bfloat16 operands the program computes with.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import bench, data, refops, taps

REF = bench.load_reference("beit-large-c10-224")
VARIANTS = ("program", "unchanged", "half_batch", "sum_dropped")
CONTROL_DTYPE = "float8_e4m3fn"


def forward_macs(cfg: dict) -> int:
    """Forward multiply-accumulates of one image: the patch embedding, each
    block's q, k, v, output and MLP matmuls and its attention scores and
    weighted values (T = patches + CLS), and the head."""
    d, f, p = cfg["d_model"], cfg["d_ff"], cfg["patch"]
    patches = (cfg["image"] // p) ** 2
    t = patches + 1
    per_layer = t * (4 * d * d + 2 * d * f) + 2 * t * t * d
    return patches * p * p * cfg["channels"] * d + cfg["n_layers"] * per_layer \
        + d * cfg["n_classes"]


def model_flops_per_sample(cfg: dict, wl: dict) -> float:
    """Model FLOPs of one training sample: forward, weight gradients, and
    input gradients of every matmul but the patch embedding's (the image
    needs none).  Norms, softmax, GELU and the bias gather are not counted."""
    p = cfg["patch"]
    embed = (cfg["image"] // p) ** 2 * p * p * cfg["channels"] * cfg["d_model"]
    fwd = forward_macs(cfg)
    return 2.0 * (fwd + fwd + (fwd - embed))


def arch(cfg: dict):
    """``BEIT_LARGE`` at the configuration's widths."""
    from repro.configs.paper_native import BEIT_LARGE

    return dataclasses.replace(
        BEIT_LARGE, n_layers=cfg["n_layers"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv=cfg["n_heads"], head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
        norm_eps=cfg["ln_eps"], layer_scale=cfg["layer_scale"], dtype=cfg["dtype"],
        param_dtype=cfg["param_dtype"], opt_state_dtype=cfg["opt_state_dtype"],
    )


class Trainer:
    """One compiled training loop and the functions to read its state."""

    def __init__(self, cfg: dict, wl: dict, variant: str = "program"):
        from repro.core.engine import PrivacyEngine
        from repro.models.vit import BEiT
        from repro.optim import adam, apply_updates

        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
        self.cfg, self.wl, self.variant = cfg, wl, variant
        self.samples_per_step = wl["batch"]
        model = BEiT(arch(cfg), image_size=cfg["image"], patch=cfg["patch"],
                     n_classes=cfg["n_classes"], in_ch=cfg["channels"])
        batch = wl["batch"] // 2 if variant == "half_batch" else wl["batch"]
        opt_cfg = wl["optimizer"]
        self.engine = PrivacyEngine(
            loss_with_ctx=model.loss_with_ctx, batch_size=batch,
            sample_size=wl["sample_size"], steps=wl["accounting_steps"],
            max_grad_norm=wl["clip_norm"], noise_multiplier=wl["noise_multiplier"],
            mode=wl["mode"],
        )
        opt = adam(opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"])
        self.b1 = opt_cfg["b1"]
        engine = self.engine

        def update(state, grad_sum, norms):
            params, step = state["params"], state["step"]
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
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(abstract))
        if n_params != cfg["params"]:
            raise ValueError(f"the program's model has {n_params} parameters, "
                             f"the configuration {cfg['params']}")
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
        self._change = jax.jit(lambda p, wkey: refops.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, REF.init_params(cfg, wkey))))
        self.leaf_names = refops.leaf_names(state_spec["params"])
        self._seed, self._first_sum = None, None
        self._model, self._specs = model, (state_spec["params"], b_spec)

    # -- the timed path -----------------------------------------------------
    def init_state(self, seed: int) -> dict:
        self._seed, self._first_sum = seed, None
        self._keys = (data.stream_key(seed, "data"), data.stream_key(seed, "classes"))
        return self._init(data.stream_key(seed, "weights"), data.stream_key(seed, "noise"))

    def batch(self, step: int) -> dict:
        return self._batch(*self._keys, jnp.int32(step))

    def step(self, state: dict, batch: dict) -> tuple[dict, dict]:
        """Dispatch one step; (the new state, its loss and norm statistics).
        The first step after ``init_state`` also keeps the per-leaf norms of
        the clipped-gradient sum it hands to the noise, for the comparison."""
        if self.variant == "half_batch":
            batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        loss, grad_sum, aux = self._grad(state["params"], batch)
        if self.variant == "sum_dropped":
            grad_sum = jax.tree_util.tree_map(jnp.zeros_like, grad_sum)
        if self._first_sum is None:
            self._first_sum = self._norms(grad_sum)
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
        """Per-leaf norms of the first step's clipped-gradient sum, as the
        step's own compiled programs computed it (kept by ``step``)."""
        return self._first_sum

    def change_norms(self, state: dict) -> jax.Array:
        return self._change(state["params"], data.stream_key(self._seed, "weights"))


def reference(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    return REF.first_steps(cfg, wl, seed, n_steps)


def reference_control(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    """The reference in the program's place, its conv and matmul operands in
    float8."""
    return REF.first_steps(cfg, wl, seed, n_steps, quant=CONTROL_DTYPE)
