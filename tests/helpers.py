"""Shared test utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def max_tree_diff(a, b) -> float:
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def lm_batch(key, batch, seq, vocab):
    k1, k2 = jax.random.split(key)
    return {
        "tokens": jax.random.randint(k1, (batch, seq), 0, vocab, dtype=jnp.int32),
        "labels": jax.random.randint(k2, (batch, seq), 0, vocab, dtype=jnp.int32),
        "mask": jnp.ones((batch,), jnp.float32),
    }


def forced_plan(loss_with_ctx, params, batch, branch):
    """A ClipPlan that forces every matmul tap onto ``branch`` ("ghost" or
    "instantiate") in both branch maps: the paper's ghost-only and
    instantiate-only algorithms as ``mixed_ghost`` (or ``bk_mixed``) runs."""
    from repro.core.clipping import discover_meta
    from repro.tuner.plan import ClipPlan, device_string, shape_fingerprint

    meta = discover_meta(loss_with_ctx, params, batch)
    forced = tuple((n, branch) for n, m in sorted(meta.items()) if m.kind == "matmul")
    return ClipPlan(
        fingerprint=shape_fingerprint(meta), device=device_string(),
        branches=forced, bk_branches=forced,
    )
