"""xlstm-350m trained by the train CLI's own step program.

The window drives ``launch/steps.make_train_step`` compiled as
``launch/train.run_once`` compiles it for ``accum == 1``: the state and
batch shardings of the host mesh, inside ``use_reshard_rules``, with the
state donated; then ``record_step`` of the CLI's ``PrivacyEngine`` on the
host.  The CLI's own extra per-step work (watchdog, epsilon alarm, metrics
stream, checkpoints) is not in the window.  The learning rate is constant,
so the first steps move every leaf.

Variants, for the checks of the comparison: ``unchanged`` returns the state
it was given; ``half_batch`` trains on the first half of each batch with
the logical batch halved; ``sum_dropped`` hands the noise a zero
clipped-gradient sum, so the step trains on noise alone.  The control
(matmul operands in float8) is the reference put in the program's place:
``reference_control``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp

from chipbench import bench, data, refops, taps, work

REF = bench.load_reference("xlstm-350m")
VARIANTS = ("program", "unchanged", "half_batch", "sum_dropped")
CONTROL_DTYPE = "float8_e4m3fn"
ARCH_KEYS = ("n_layers", "d_model", "n_heads", "vocab", "dtype", "param_dtype",
             "opt_state_dtype", "remat", "ssm_chunk")


def model_flops_per_sample(cfg: dict, wl: dict) -> float:
    return wl["seq"] * work.xlstm_train_flops_per_token(
        d_model=cfg["d_model"], n_heads=cfg["n_heads"], vocab=cfg["vocab"],
        pattern=list(cfg["block_pattern"]) * (cfg["n_layers"] // len(cfg["block_pattern"])))


def arch_config(cfg: dict):
    """The registry's ArchConfig with the sizes of ``cfg``."""
    from repro.configs.registry import get_arch

    arch = dataclasses.replace(
        get_arch(cfg["arch"]), block_pattern=tuple(cfg["block_pattern"]),
        **{k: cfg[k] for k in ARCH_KEYS})
    if work.slstm_ffn_width(arch.d_model) != cfg["slstm_ffn"]:
        raise ValueError("slstm_ffn is not the program's sLSTM FFN width")
    return arch


class Trainer:
    """One compiled training step and the functions to read its state."""

    def __init__(self, cfg: dict, wl: dict, variant: str = "program"):
        from repro.configs.registry import build_model
        from repro.core.engine import PrivacyEngine
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import DPTrainConfig, abstract_train_state, make_train_step
        from repro.optim import adam
        from repro.optim.schedules import constant
        from repro.parallel.reshard import use_reshard_rules
        from repro.parallel.sharding import batch_shardings, state_shardings
        from repro.policies.fixed import FixedPolicy

        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
        self.cfg, self.wl, self.variant = cfg, wl, variant
        self.samples_per_step = wl["batch"]
        arch = arch_config(cfg)
        model = build_model(arch)
        mesh = make_host_mesh()
        batch = wl["batch"] // 2 if variant == "half_batch" else wl["batch"]
        opt_cfg = wl["optimizer"]
        policy = FixedPolicy(clip_norm=wl["clip_norm"])
        optimizer = adam(opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"],
                         state_dtype=jnp.dtype(arch.opt_state_dtype))
        self.b1 = opt_cfg["b1"]
        self.noise_std = wl["noise_multiplier"] * wl["clip_norm"]
        self.engine = PrivacyEngine(
            loss_with_ctx=model.loss_with_ctx, batch_size=batch,
            sample_size=wl["sample_size"], steps=wl["accounting_steps"],
            max_grad_norm=wl["clip_norm"], noise_multiplier=wl["noise_multiplier"],
            mode=wl["mode"], clip_policy=policy,
        )
        dp = DPTrainConfig(
            clipping_mode=wl["mode"], clip_norm=wl["clip_norm"],
            noise_multiplier=wl["noise_multiplier"], logical_batch=batch,
            accumulation_steps=1, plan=None, policy=policy,
        )
        with _sum_dropped() if variant == "sum_dropped" else contextlib.nullcontext():
            step_fn = make_train_step(model, optimizer, constant(opt_cfg["lr"]), dp)

        def init_state(wkey, nkey):
            params = REF.init_params(cfg, wkey)
            return {"params": params, "opt": optimizer.init(params),
                    "step": jnp.zeros((), jnp.int32), "rng": nkey,
                    "policy": policy.init_state()}

        abstract = abstract_train_state(model, optimizer, policy)
        st_spec = jax.eval_shape(init_state, jax.random.PRNGKey(0), jax.random.PRNGKey(0))
        if jax.tree_util.tree_structure(abstract) != jax.tree_util.tree_structure(st_spec) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                    jax.tree_util.tree_leaves(abstract), jax.tree_util.tree_leaves(st_spec))):
            raise ValueError("the benchmark's state does not have the program's tree")
        st_sh = state_shardings(model, mesh, arch, abstract)
        make = functools.partial(data.token_batch, batch=wl["batch"], seq=wl["seq"],
                                 vocab=cfg["vocab"])
        b_spec = jax.eval_shape(make, jax.random.PRNGKey(0), 0)
        if variant == "half_batch":
            b_spec = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((batch,) + s.shape[1:], s.dtype), b_spec)
        b_sh = batch_shardings(b_spec, mesh, arch)
        self._init = jax.jit(init_state, out_shardings=st_sh)
        self._batch = jax.jit(make)
        donate = () if variant == "unchanged" else (0,)
        with use_reshard_rules(mesh, arch):
            self._step = jax.jit(
                step_fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                donate_argnums=donate,
            ).lower(st_spec, b_spec).compile()
        self._norms = jax.jit(refops.leaf_norms)
        self._sum = jax.jit(functools.partial(refops.first_sum_norms, batch=wl["batch"],
                                              b1=self.b1, std=self.noise_std))
        self._change = jax.jit(lambda p, wkey: refops.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, REF.init_params(cfg, wkey))))
        self.leaf_names = refops.leaf_names(st_spec["params"])
        self._model, self._specs = model, (st_spec["params"], b_spec)
        self._seed = None

    # -- the timed path -----------------------------------------------------
    def init_state(self, seed: int) -> dict:
        self._seed = seed
        self._data_key = data.stream_key(seed, "data")
        return self._init(data.stream_key(seed, "weights"), data.stream_key(seed, "noise"))

    def batch(self, step: int) -> dict:
        return self._batch(self._data_key, jnp.int32(step))

    def step(self, state: dict, batch: dict) -> tuple[dict, dict]:
        """Dispatch one step; (the new state, its loss and norm statistics)."""
        if self.variant == "half_batch":
            batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        new, metrics = self._step(state, batch)
        out = {k: metrics[k] for k in ("loss", "norm_mean", "norm_max")}
        return (state if self.variant == "unchanged" else new), out

    def record_step(self) -> None:
        self.engine.record_step()

    def ghost_norm_taps(self) -> list[tuple]:
        return taps.ghost_norm_taps(self._model.loss_with_ctx, *self._specs, self.wl["mode"])

    # -- readings for the comparison ------------------------------------------
    def first_grad_norms(self, state: dict) -> jax.Array:
        """Per-leaf norms of the gradient Adam took, from its state after one
        step: m = (1 - b1) g."""
        return self._norms(state["opt"]["m"]) / (1.0 - self.b1)

    def first_sum_norms(self, state: dict) -> jax.Array:
        """Per-leaf norms of the first step's clipped-gradient sum, from
        Adam's state after one step with the step's noise taken out (the
        step's noise key is the second of three split from the state's)."""
        key = jax.random.split(data.stream_key(self._seed, "noise"), 3)[1]
        return self._sum(state["opt"]["m"], key)

    def change_norms(self, state: dict) -> jax.Array:
        return self._change(state["params"], data.stream_key(self._seed, "weights"))


def _sum_dropped():
    """While the step is built: its clipped-gradient sum comes out zero."""
    from repro.launch import steps

    real = steps.dp_value_and_clipped_grad

    def dropped(loss_with_ctx, clip_cfg):
        fn = real(loss_with_ctx, clip_cfg)

        def zero_sum(*args, **kwargs):
            loss, grad_sum, aux = fn(*args, **kwargs)
            return loss, jax.tree_util.tree_map(jnp.zeros_like, grad_sum), aux
        return zero_sum

    return mock.patch.object(steps, "dp_value_and_clipped_grad", dropped)


def reference(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    return REF.first_steps(cfg, wl, seed, n_steps)


def reference_control(cfg: dict, wl: dict, seed: int, n_steps: int) -> dict:
    """The reference in the program's place, its matmul operands in float8."""
    return REF.first_steps(cfg, wl, seed, n_steps, quant=CONTROL_DTYPE)
