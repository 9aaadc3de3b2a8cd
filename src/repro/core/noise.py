"""Gaussian noise addition for the DP mechanism (Eq. 2.1, second term).

Noise is generated per parameter leaf with an independent fold_in of the step
key, in fp32, then cast to the gradient dtype.  Under pjit the normal draws
are partitioned by GSPMD along the parameter sharding, so no shard ever
materializes another shard's noise — the generation is fully parallel and
deterministic in (key, leaf index).  The draws and the additions run under
the named scope ``dp.noise``, which tags their ops on a device trace.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

NOISE_SCOPE = "dp.noise"


def add_dp_noise(grad_sum: Any, key: jax.Array, noise_std: float) -> Any:
    """grad_sum + noise_std * N(0, I), leafwise independent."""
    leaves, treedef = jax.tree_util.tree_flatten(grad_sum)
    with jax.named_scope(NOISE_SCOPE):
        keys = jax.random.split(key, len(leaves))
        noisy = [
            g + (noise_std * jax.random.normal(k, g.shape, jnp.float32)).astype(g.dtype)
            for g, k in zip(leaves, keys)
        ]
    return jax.tree_util.tree_unflatten(treedef, noisy)
