"""Plain float32 building blocks shared by the configurations' references.

Nothing here imports the program: these are the textbook equations, written
once, so that each reference reads as its model's equations.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data


def xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy of integer ``labels`` under ``logits`` (..., V), in f32."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def adam_init(params: Any) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params)}


def adam_step(params, opt, grads, t: int, *, lr: float, b1: float, b2: float, eps: float):
    """Adam (Kingma and Ba, 2015) at step ``t`` (1-based), bias-corrected."""
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    c1, c2 = 1 - b1**t, 1 - b2**t
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, m, v
    )
    return new, {"m": m, "v": v}


def leaf_norms(tree: Any) -> jax.Array:
    """L2 norm of every leaf, in the tree's leaf order: (n_leaves,) f32."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(tree)
    ])


def first_sum_norms(m: Any, key: jax.Array, *, batch: int, b1: float,
                    std: Optional[float]) -> jax.Array:
    """Per-leaf norms of a first step's clipped-gradient sum, worked out from
    Adam's first moment after that step, m = (1 - b1) (sum + noise) / batch:
    ``batch * m / (1 - b1)``, less ``std * N(0, I)`` drawn again from the
    step's noise key as ``data.gaussian_noise_like`` draws it (no noise where
    ``std`` is None)."""
    total = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32) * (batch / (1.0 - b1)), m)
    if std is not None:
        total = jax.tree_util.tree_map(jnp.subtract, total,
                                       data.gaussian_noise_like(total, key, std))
    return leaf_norms(total)


def leaf_names(tree: Any) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def clip_factors(norms: jax.Array, clip_norm: float) -> jax.Array:
    """Abadi et al. (2016): scale each sample's gradient to norm at most R."""
    return jnp.minimum(clip_norm / jnp.maximum(norms, 1e-12), 1.0)


def to_numpy(x) -> np.ndarray:
    return np.asarray(jax.device_get(x), dtype=np.float64)
