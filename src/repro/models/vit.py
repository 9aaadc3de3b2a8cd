"""BEiT (arXiv:2106.08254), the paper's headline private ViT classifier.

The layout of timm's ``beit_large_patch16_224``: a strided-conv patch
embedding, a CLS token and no absolute position embedding; pre-norm blocks
whose attention has q and v biases (no k bias) and a relative position bias
table of its own, gathered by a static index; layer scale on both residual
branches; then the mean of the patch tokens, ``fc_norm`` and the head.
Options come from ``ArchConfig`` (``configs/paper_native.BEIT_LARGE``).

Every parameter has a DP tap: the patch embedding is a conv ``matmul`` tap
(T = 196 patches, D = 16*16*3), the CLS token a ``bias`` tap at one
position, each table a ``table`` tap, the gammas ``scale`` taps.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.taps import Ctx
from repro.models.blocks import TransformerBlock
from repro.models.losses import per_sample_xent
from repro.nn.conv import Conv2d
from repro.nn.module import Dense, LayerNorm
from repro.nn.stack import ScannedStack

INIT_STD = 0.02  # timm's truncated normal for weights, CLS token and tables


def trunc_normal(key: jax.Array, shape, dtype, std: float = INIT_STD) -> jax.Array:
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape))).astype(dtype)


class BEiT:
    def __init__(self, cfg: ArchConfig, *, image_size: int = 224, patch: int = 16,
                 n_classes: int = 1000, in_ch: int = 3):
        self.cfg = cfg
        dtype = jnp.dtype(cfg.dtype)
        param_dtype = jnp.dtype(cfg.param_dtype)
        self.dtype, self.param_dtype = dtype, param_dtype
        grid = image_size // patch
        self.patch_embed = Conv2d(
            "patch_embed", in_ch, cfg.d_model, (patch, patch),
            strides=(patch, patch), padding="VALID", dtype=dtype, param_dtype=param_dtype,
        )
        block = TransformerBlock(
            "blk", dataclasses.replace(cfg, norm="layernorm"), causal=False,
            rel_pos_window=(grid, grid),
            dtype=dtype, param_dtype=param_dtype,
        )
        self.layers = ScannedStack("layers", block, cfg.n_layers, remat=cfg.remat)
        eps = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
        self.fc_norm = LayerNorm("fc_norm", cfg.d_model, dtype=dtype, param_dtype=param_dtype,
                                 **eps)
        self.head = Dense("head", cfg.d_model, n_classes, dtype=dtype, param_dtype=param_dtype)

    def init(self, key: jax.Array) -> Any:
        """timm's init: weights and the CLS token truncated normal 0.02,
        biases 0, norms 1 and 0, the gammas ``cfg.layer_scale``."""
        ks = jax.random.split(key, 6)
        params = {
            "patch_embed": self.patch_embed.init(ks[0]),
            "cls_token": trunc_normal(ks[1], (1, 1, self.cfg.d_model), self.param_dtype),
            "layers": self.layers.init(ks[2]),
            "fc_norm": self.fc_norm.init(ks[3]),
            "head": self.head.init(ks[4]),
        }
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        keys = jax.random.split(ks[5], len(flat))
        leaves = [
            trunc_normal(k, x.shape, x.dtype) if path[-1].key == "w" else x
            for (path, x), k in zip(flat, keys)
        ]
        return jax.tree_util.tree_unflatten(tree, leaves)

    def axes(self) -> Any:
        return {
            "patch_embed": self.patch_embed.axes(),
            "cls_token": (None, None, "embed"),
            "layers": self.layers.axes(),
            "fc_norm": self.fc_norm.axes(),
            "head": self.head.axes(),
        }

    def logits(self, params, image, ctx: Ctx) -> jax.Array:
        x = self.patch_embed(params["patch_embed"], image.astype(self.dtype),
                             ctx.scope("patch_embed"))
        b, d = x.shape[0], self.cfg.d_model
        x = x.reshape(b, -1, d)
        cls = jnp.broadcast_to(params["cls_token"].astype(self.dtype), (b, 1, d))
        if ctx.collect:
            cls = ctx.tap("cls_token@out", cls, kind="bias", T=1, D=1, p=d,
                          param_path="cls_token")
        x = jnp.concatenate([cls, x], axis=1)
        x, _ = self.layers(params["layers"], x, ctx.scope("layers"))
        h = jnp.mean(x[:, 1:], axis=1)  # the patch tokens
        h = self.fc_norm(params["fc_norm"], h, ctx.scope("fc_norm"))
        return self.head(params["head"], h, ctx.scope("head"))

    def loss_with_ctx(self, params, batch, ctx: Ctx) -> jax.Array:
        logits = self.logits(params, batch["image"], ctx)
        return per_sample_xent(logits[:, None, :], batch["label"][:, None],
                               batch.get("mask"))
