"""Plain reference of DP training of VGG with GroupNorm on CIFAR-10 shapes.

VGG (Simonyan and Zisserman, arXiv:1409.1556) as the DP paper trains it
(Bu et al., arXiv:2205.10683): 3x3 "SAME" convolutions with bias, each
followed by GroupNorm (min(16, C) groups, eps 1e-5, scale and shift) and
ReLU, 2x2 max-pool at each "M", global average pool, and a dense head;
mean cross-entropy per sample.  DP-Adam as Abadi et al. (2016) state it:
per-sample gradients by ``vmap(grad)`` in blocks of samples, each scaled to
norm at most R, summed, plus ``sigma * R * N(0, I)``, over the batch size,
then Adam.  The non-private step takes the plain mean gradient.

Everything runs in float32 at ``highest`` matmul precision; ``quant`` rounds
every conv and matmul operand to a lower type (the control).  Nothing of
the program is imported; weights and data come from the seed by the
benchmark's own generators.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import data, refops

HIGHEST = lax.Precision.HIGHEST


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Weights N(0, 1/fan_in), biases 0, GroupNorm scale 1 and shift 0,
    named as the program names them (``conv{i}``, ``gn{i}``, ``head``)."""
    params, ch, n = {}, cfg["channels"], 0
    for i, item in enumerate(cfg["plan"]):
        if item == "M":
            continue
        k = jax.random.fold_in(key, n)
        n += 1
        params[f"conv{i}"] = {
            "w": jax.random.normal(k, (3, 3, ch, item)) / math.sqrt(9 * ch),
            "b": jnp.zeros((item,)),
        }
        params[f"gn{i}"] = {"g": jnp.ones((item,)), "b": jnp.zeros((item,))}
        ch = item
    k = jax.random.fold_in(key, n)
    params["head"] = {
        "w": jax.random.normal(k, (ch, cfg["n_classes"])) / math.sqrt(ch),
        "b": jnp.zeros((cfg["n_classes"],)),
    }
    return params


def group_norm(x, g, b, groups: int, eps: float):
    n, h, w, c = x.shape
    xg = x.reshape(n, h * w, groups, c // groups)
    mu = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 3), keepdims=True)
    return ((xg - mu) / jnp.sqrt(var + eps)).reshape(x.shape) * g + b


def logits(cfg: dict, params: dict, x: jax.Array, quant=None) -> jax.Array:
    """Forward pass, operands of every conv and matmul rounded to ``quant``."""
    q = (lambda a: a) if quant is None else (lambda a: a.astype(quant).astype(jnp.float32))
    for i, item in enumerate(cfg["plan"]):
        if item == "M":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        p, gn = params[f"conv{i}"], params[f"gn{i}"]
        x = lax.conv_general_dilated(
            q(x), q(p["w"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        ) + p["b"]
        x = jax.nn.relu(group_norm(x, gn["g"], gn["b"], min(cfg["groups"], item), cfg["gn_eps"]))
    h = jnp.mean(x, axis=(1, 2))
    return jnp.dot(q(h), q(params["head"]["w"]), precision=HIGHEST) + params["head"]["b"]


def sample_loss(cfg: dict, params: dict, image: jax.Array, label: jax.Array, quant) -> jax.Array:
    return refops.xent(logits(cfg, params, image[None], quant), label[None])[0]


@functools.partial(jax.jit, static_argnames=("cfg_key", "private", "quant"))
def _block(params, image, label, *, cfg_key, private: bool, clip_norm: float, quant):
    """(sum of losses, per-sample gradient norms, sum of (clipped) per-sample
    gradients) of one block of samples."""
    cfg = dict(cfg_key)
    cfg["plan"] = list(cfg["plan"])
    vg = jax.vmap(jax.value_and_grad(functools.partial(sample_loss, cfg, quant=quant)),
                  in_axes=(None, 0, 0))
    losses, grads = vg(params, image, label)
    if private:
        sq = sum(jnp.sum(jnp.square(g.reshape(g.shape[0], -1)), axis=1)
                 for g in jax.tree_util.tree_leaves(grads))
        norms = jnp.sqrt(sq)
        c = refops.clip_factors(norms, clip_norm)
    else:  # the program's non-private step computes no norms: zeros, as it reports
        norms, c = jnp.zeros_like(losses), jnp.ones_like(losses)
    summed = jax.tree_util.tree_map(lambda g: jnp.tensordot(c, g, axes=1, precision=HIGHEST), grads)
    return jnp.sum(losses), norms, summed


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()))


def first_steps(cfg: dict, wl: dict, seed: int, n_steps: int, *, quant=None) -> dict:
    """Train ``n_steps`` steps from the seed.  Returns each step's mean loss
    and (mean, largest) per-sample gradient norm, the first step's
    per-sample gradient norms, the per-leaf norms of the first step's
    clipped-gradient sum and of its gradient as the optimizer gets it, and
    the per-leaf norms of the parameters' change over all the steps."""
    b, block = wl["batch"], wl["reference_block"]
    private = wl["mode"] != "non_private"
    opt_cfg = wl["optimizer"]
    cfg_key = _freeze({k: cfg[k] for k in ("plan", "channels", "n_classes", "groups", "gn_eps")})
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(functools.partial(init_params, cfg))(data.stream_key(seed, "weights"))
        params, opt = p0, refops.adam_init(p0)
        make = jax.jit(functools.partial(
            data.image_batch, batch=b, image=cfg["image"], channels=cfg["channels"],
            n_classes=cfg["n_classes"]))
        losses, norm_stats, first_grad, first_sum, first_norms = [], [], None, None, None
        for step in range(n_steps):
            batch = make(data.stream_key(seed, "data"), data.stream_key(seed, "classes"), step)
            loss_sum, g_sum, norms = 0.0, None, []
            for lo in range(0, b, block):
                ls, ns, gs = _block(
                    params, batch["image"][lo:lo + block], batch["label"][lo:lo + block],
                    cfg_key=cfg_key, private=private, clip_norm=wl["clip_norm"], quant=quant)
                loss_sum = loss_sum + ls
                norms.append(ns)
                g_sum = gs if g_sum is None else jax.tree_util.tree_map(jnp.add, g_sum, gs)
            norms = jnp.concatenate(norms)
            norm_stats.append(jnp.stack([jnp.mean(norms), jnp.max(norms)]))
            if step == 0:
                first_sum, first_norms = refops.leaf_norms(g_sum), norms
            if private:
                key = jax.random.fold_in(data.stream_key(seed, "noise"), step)
                noise = data.gaussian_noise_like(g_sum, key, wl["noise_multiplier"] * wl["clip_norm"])
                g_sum = jax.tree_util.tree_map(jnp.add, g_sum, noise)
            grads = jax.tree_util.tree_map(lambda g: g / b, g_sum)
            if step == 0:
                first_grad = refops.leaf_norms(grads)
            params, opt = refops.adam_step(
                params, opt, grads, step + 1, lr=opt_cfg["lr"], b1=opt_cfg["b1"],
                b2=opt_cfg["b2"], eps=opt_cfg["eps"])
            losses.append(loss_sum / b)
        change = refops.leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {
        "names": refops.leaf_names(p0),
        "losses": refops.to_numpy(jnp.stack(losses)),
        "norms": refops.to_numpy(jnp.stack(norm_stats)),
        "sample_norms": refops.to_numpy(first_norms),
        "grad": refops.to_numpy(first_grad),
        "sum": refops.to_numpy(first_sum),
        "change": refops.to_numpy(change),
    }
