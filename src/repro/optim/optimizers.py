"""Functional optimizers (no optax dependency).

An Optimizer is a pair of pure functions:
    init(params) -> state
    update(grads, state, params, step, lr) -> (updates, state)
Updates are ADDED to params via ``apply_updates`` (they carry the -lr sign).

DP-SGD / DP-Adam are these optimizers fed the privatized gradient (Eq. 2.1):
the mechanism lives entirely in the gradient, as in the paper.

Every ``update`` and ``apply_updates`` runs under the named scope
``dp.update``, which tags the optimizer's ops on a device trace.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = Any
State = Any
Schedule = Callable[[jax.Array], jax.Array]

UPDATE_SCOPE = "dp.update"


def _scoped(fn: Callable) -> Callable:
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(UPDATE_SCOPE):
            return fn(*args, **kwargs)
    return run


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Params], State]
    update: Callable[..., tuple[Params, State]]


@_scoped
def apply_updates(params: Params, updates: Params) -> Params:
    return jax.tree_util.tree_map(
        lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
        params,
        updates,
    )


def sgd(momentum: float = 0.0, nesterov: bool = False) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return {}
        return {"m": jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    @_scoped
    def update(grads, state, params, step, lr):
        del params, step
        if momentum == 0.0:
            return jax.tree_util.tree_map(lambda g: -lr * g.astype(jnp.float32), grads), state
        m = jax.tree_util.tree_map(
            lambda mm, g: momentum * mm + g.astype(jnp.float32), state["m"], grads
        )
        if nesterov:
            upd = jax.tree_util.tree_map(
                lambda mm, g: -lr * (momentum * mm + g.astype(jnp.float32)), m, grads
            )
        else:
            upd = jax.tree_util.tree_map(lambda mm: -lr * mm, m)
        return upd, {"m": m}

    return Optimizer(init, update)


def adam(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    *,
    weight_decay: float = 0.0,
    state_dtype=jnp.float32,
) -> Optimizer:
    """Adam / AdamW (decoupled weight decay when weight_decay > 0)."""

    def init(params):
        z = lambda p: jnp.zeros(p.shape, state_dtype)
        return {
            "m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params),
        }

    @_scoped
    def update(grads, state, params, step, lr):
        t = step.astype(jnp.float32) + 1.0
        c1 = 1.0 - b1**t
        c2 = 1.0 - b2**t

        def upd_mv(mm, vv, g):
            g = g.astype(jnp.float32)
            m_new = b1 * mm.astype(jnp.float32) + (1 - b1) * g
            v_new = b2 * vv.astype(jnp.float32) + (1 - b2) * g * g
            return m_new.astype(state_dtype), v_new.astype(state_dtype)

        mv = jax.tree_util.tree_map(
            upd_mv, state["m"], state["v"], grads,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )
        m = jax.tree_util.tree_map(lambda x: x[0], mv, is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree_util.tree_map(lambda x: x[1], mv, is_leaf=lambda x: isinstance(x, tuple))

        def upd(mm, vv, p):
            mhat = mm.astype(jnp.float32) / c1
            vhat = vv.astype(jnp.float32) / c2
            u = -lr * mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                u = u - lr * weight_decay * p.astype(jnp.float32)
            return u

        updates = jax.tree_util.tree_map(upd, m, v, params)
        return updates, {"m": m, "v": v}

    return Optimizer(init, update)


def adamw(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
    weight_decay: float = 0.01, state_dtype=jnp.float32,
) -> Optimizer:
    return adam(b1, b2, eps, weight_decay=weight_decay, state_dtype=state_dtype)
