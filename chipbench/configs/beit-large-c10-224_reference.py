"""Plain reference of DP training of BEiT-large/16 on CIFAR-10 at 224 px.

BEiT (Bao et al., arXiv:2106.08254) as timm's ``beit_large_patch16_224``
builds it: a 16x16 stride-16 conv patch embedding with bias, a CLS token
and no absolute position embedding; pre-norm blocks (LayerNorm, eps 1e-6)
of multi-head attention with q and v biases (no k bias) and a relative
position bias per head, gathered from a per-block table of
(2*14-1)^2 + 3 rows by timm's relative position index (the last three rows
for CLS to token, token to CLS, CLS to CLS), and an MLP with exact GELU;
both residual branches scaled by learned gammas (layer scale); then the
mean of the patch tokens, a LayerNorm (``fc_norm``) and the head.  Mean
cross-entropy per sample.  Departures from the published model: a 10-class
head, and weights drawn from the seed, not pretrained, with the layer-scale
gammas at ``weights_gamma`` (0.5) rather than the pretraining init's 1e-5,
so that the blocks carry a share of each sample's gradient as in a model
being fine-tuned.

DP-Adam as Abadi et al. (2016) state it: per-sample gradient norms by
``vmap(grad)`` in blocks of samples, each sample scaled to norm at most R,
the clipped sum as the gradient of sum_i C_i loss_i (C_i held fixed), plus
``sigma * R * N(0, I)``, over the batch size, then Adam.

Everything runs in float32 at ``highest`` matmul precision; ``quant``
rounds every conv and matmul operand, attention's too, to a lower type (the
control).  Nothing of the program is imported; weights and data come from
the seed by the benchmark's own generators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import data, refops

HIGHEST = lax.Precision.HIGHEST
STD = 0.02


def relative_index(grid: int) -> np.ndarray:
    """(T, T) rows of the relative position table, T = grid^2 + 1 (CLS first)."""
    rows = (2 * grid - 1) ** 2 + 3
    hh, ww = np.meshgrid(np.arange(grid), np.arange(grid), indexing="ij")
    h, w = hh.reshape(-1), ww.reshape(-1)
    dh = h[:, None] - h[None, :] + grid - 1
    dw = w[:, None] - w[None, :] + grid - 1
    index = np.empty((grid * grid + 1,) * 2, np.int32)
    index[1:, 1:] = dh * (2 * grid - 1) + dw
    index[0, :] = rows - 3  # CLS -> token
    index[:, 0] = rows - 2  # token -> CLS
    index[0, 0] = rows - 1
    return index


def _tn(key, shape):
    return STD * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """timm's init, named and stacked (one leading layer axis) as the
    program names them, but for the layer-scale gammas: ``weights_gamma``,
    standing for a fine-tuned checkpoint's, not the pretraining init's
    ``layer_scale``."""
    d, f, h, n = cfg["d_model"], cfg["d_ff"], cfg["n_heads"], cfg["n_layers"]
    p, c = cfg["patch"], cfg["channels"]
    rows = (2 * (cfg["image"] // p) - 1) ** 2 + 3
    ks = iter(jax.random.split(key, 16))
    zeros, ones = (lambda *s: jnp.zeros(s)), (lambda *s: jnp.ones(s))

    def dense(din, dout, bias=True):
        out = {"w": _tn(next(ks), (n, din, dout))}
        if bias:
            out["b"] = zeros(n, dout)
        return out

    layers = {
        "attn": {"q": dense(d, d), "k": dense(d, d, bias=False), "v": dense(d, d),
                 "o": dense(d, d), "relative_position_bias_table": _tn(next(ks), (n, rows, h))},
        "ls1": {"g": jnp.full((n, d), cfg["weights_gamma"])},
        "ls2": {"g": jnp.full((n, d), cfg["weights_gamma"])},
        "mlp": {"wi": dense(d, f), "wo": dense(f, d)},
        "n1": {"g": ones(n, d), "b": zeros(n, d)},
        "n2": {"g": ones(n, d), "b": zeros(n, d)},
    }
    return {
        "patch_embed": {"w": _tn(next(ks), (p, p, c, d)), "b": zeros(d)},
        "cls_token": _tn(next(ks), (1, 1, d)),
        "layers": layers,
        "fc_norm": {"g": ones(d), "b": zeros(d)},
        "head": {"w": _tn(next(ks), (d, cfg["n_classes"])), "b": zeros(cfg["n_classes"])},
    }


def layer_norm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def logits(cfg: dict, params: dict, x: jax.Array, quant=None) -> jax.Array:
    """Forward pass of images (N, H, W, C), operands of every conv and matmul
    rounded to ``quant``."""
    q = (lambda a: a) if quant is None else (lambda a: a.astype(quant).astype(jnp.float32))
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    eps, heads, p = cfg["ln_eps"], cfg["n_heads"], cfg["patch"]
    index = jnp.asarray(relative_index(cfg["image"] // p))
    pe = params["patch_embed"]
    x = lax.conv_general_dilated(q(x), q(pe["w"]), (p, p), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=HIGHEST) + pe["b"]
    n, d = x.shape[0], x.shape[-1]
    x = jnp.concatenate([jnp.broadcast_to(params["cls_token"], (n, 1, d)),
                         x.reshape(n, -1, d)], axis=1)
    t, hd = x.shape[1], d // heads

    def dense(a, w):
        return mm("ntd,de->nte", q(a), q(w["w"])) + (w["b"] if "b" in w else 0.0)

    def block(x, lp):
        at = lp["attn"]
        y = layer_norm(x, lp["n1"]["g"], lp["n1"]["b"], eps)
        qh, kh, vh = (dense(y, at[k]).reshape(n, t, heads, hd) for k in ("q", "k", "v"))
        bias = at["relative_position_bias_table"][index].transpose(2, 0, 1)  # (H, T, T)
        s = mm("nqhd,nkhd->nhqk", q(qh), q(kh)) * hd ** -0.5 + bias
        o = mm("nhqk,nkhd->nqhd", q(jax.nn.softmax(s, axis=-1)), q(vh)).reshape(n, t, d)
        x = x + lp["ls1"]["g"] * dense(o, at["o"])
        y = layer_norm(x, lp["n2"]["g"], lp["n2"]["b"], eps)
        y = dense(jax.nn.gelu(dense(y, lp["mlp"]["wi"]), approximate=False), lp["mlp"]["wo"])
        return x + lp["ls2"]["g"] * y, None

    x, _ = lax.scan(block, x, params["layers"])
    h = layer_norm(jnp.mean(x[:, 1:], axis=1), params["fc_norm"]["g"], params["fc_norm"]["b"],
                   eps)
    return mm("nd,dk->nk", q(h), q(params["head"]["w"])) + params["head"]["b"]


def _losses(cfg, params, image, label, quant):
    return refops.xent(logits(cfg, params, image, quant), label)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_norms(params, image, label, *, cfg_key, quant):
    """(losses, per-sample gradient norms) of one block of samples, by
    vmap(grad)."""
    cfg = dict(cfg_key)

    def one(p, im, lb):
        return _losses(cfg, p, im[None], lb[None], quant)[0]

    losses, grads = jax.vmap(jax.value_and_grad(one), in_axes=(None, 0, 0))(params, image, label)
    sq = sum(jnp.sum(jnp.square(g.reshape(g.shape[0], -1)), axis=1)
             for g in jax.tree_util.tree_leaves(grads))
    return losses, jnp.sqrt(sq)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_clipped_sum(params, image, label, c, *, cfg_key, quant):
    """Gradient of sum_i C_i loss_i over one block: its clipped sum."""
    cfg = dict(cfg_key)
    return jax.grad(lambda p: jnp.sum(c * _losses(cfg, p, image, label, quant)))(params)


def _freeze(cfg: dict) -> tuple:
    keys = ("image", "patch", "channels", "n_classes", "n_layers", "d_model", "n_heads", "d_ff",
            "ln_eps", "layer_scale")
    return tuple(sorted((k, cfg[k]) for k in keys))


def first_steps(cfg: dict, wl: dict, seed: int, n_steps: int, *, quant=None) -> dict:
    """Train ``n_steps`` steps from the seed.  Returns each step's mean loss
    and (mean, largest) per-sample gradient norm, the first step's
    per-sample gradient norms, the per-leaf norms of the first step's
    clipped-gradient sum and of its gradient as the optimizer gets it, and
    the per-leaf norms of the parameters' change over all the steps."""
    b, block = wl["batch"], wl["reference_block"]
    opt_cfg, cfg_key = wl["optimizer"], _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(functools.partial(init_params, cfg))(data.stream_key(seed, "weights"))
        params, opt = p0, refops.adam_init(p0)
        make = jax.jit(functools.partial(
            data.image_batch, batch=b, image=cfg["image"], channels=cfg["channels"],
            n_classes=cfg["n_classes"]))
        losses, norm_stats, first_grad, first_sum, first_norms = [], [], None, None, None
        for step in range(n_steps):
            batch = make(data.stream_key(seed, "data"), data.stream_key(seed, "classes"), step)
            blocks = [(batch["image"][lo:lo + block], batch["label"][lo:lo + block])
                      for lo in range(0, b, block)]
            loss_sum, g_sum, norms = 0.0, None, []
            for image, label in blocks:
                ls, ns = _block_norms(params, image, label, cfg_key=cfg_key, quant=quant)
                c = refops.clip_factors(ns, wl["clip_norm"])
                gs = _block_clipped_sum(params, image, label, c, cfg_key=cfg_key, quant=quant)
                loss_sum = loss_sum + jnp.sum(ls)
                norms.append(ns)
                g_sum = gs if g_sum is None else jax.tree_util.tree_map(jnp.add, g_sum, gs)
            norms = jnp.concatenate(norms)
            norm_stats.append(jnp.stack([jnp.mean(norms), jnp.max(norms)]))
            if step == 0:
                first_sum, first_norms = refops.leaf_norms(g_sum), norms
            key = jax.random.fold_in(data.stream_key(seed, "noise"), step)
            noise = data.gaussian_noise_like(g_sum, key, wl["noise_multiplier"] * wl["clip_norm"])
            grads = jax.tree_util.tree_map(lambda g, z: (g + z) / b, g_sum, noise)
            del g_sum, noise
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
