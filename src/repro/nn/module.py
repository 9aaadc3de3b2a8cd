"""Minimal functional module system (no flax dependency).

A Module is a static (hashable config) object with three methods:

- ``init(key) -> params``      pure parameter construction
- ``axes() -> axes_tree``      logical sharding axes mirroring ``init``
- ``__call__(params, x, ctx, ...)``  pure apply; ``ctx`` threads DP taps

Params are plain nested dicts of arrays so every jax transformation applies
directly.  Logical axis names are resolved to mesh axes by
``repro.parallel.sharding``.
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.taps import Ctx
from repro.parallel.reshard import reshard_param

Params = Any
AxesTree = Any


class Module:
    """Base class; subclasses are static configuration holders."""

    name: str

    def init(self, key: jax.Array) -> Params:
        raise NotImplementedError

    def axes(self) -> AxesTree:
        raise NotImplementedError

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx, **kw):
        raise NotImplementedError


def normal_init(key: jax.Array, shape: Sequence[int], scale: float, dtype) -> jax.Array:
    return (scale * jax.random.normal(key, tuple(shape))).astype(dtype)


class Dense(Module):
    """y = x @ W + b with a DP tap on the pre-activation.

    ``x``: (B, ..., d_in) — all middle dims are positions T.
    The recorded activation is ``x`` reshaped to (B, T, d_in).
    """

    def __init__(
        self,
        name: str,
        d_in: int,
        d_out: int,
        *,
        use_bias: bool = True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        w_axes: tuple = ("embed", "mlp"),
        init_scale: float = 1.0,
        dp: bool = True,
    ):
        self.name = name
        self.d_in = d_in
        self.d_out = d_out
        self.use_bias = use_bias
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.w_axes = w_axes
        self.init_scale = init_scale
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        scale = self.init_scale / math.sqrt(self.d_in)
        p = {"w": normal_init(key, (self.d_in, self.d_out), scale, self.param_dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.d_out,), self.param_dtype)
        return p

    def axes(self) -> AxesTree:
        a = {"w": self.w_axes}
        if self.use_bias:
            a["b"] = (self.w_axes[-1],)
        return a

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        w = reshard_param(params["w"].astype(self.dtype), self.w_axes)
        x = x.astype(self.dtype)
        s = x @ w
        if self.use_bias:
            s = s + params["b"].astype(self.dtype)
        if self.dp and ctx.collect:
            batch = x.shape[0]
            t = int(math.prod(x.shape[1:-1])) if x.ndim > 2 else 1
            a_rec = x.reshape(batch, t, self.d_in) if x.ndim != 3 else x
            s = ctx.tap(
                "out",
                s,
                kind="matmul",
                a=a_rec,
                T=t,
                D=self.d_in,
                p=self.d_out,
                param_path="w",
                bias_path="b" if self.use_bias else None,
            )
        return s


class Embedding(Module):
    """Token embedding with the index-equality ghost-norm tap."""

    def __init__(
        self,
        name: str,
        vocab: int,
        d: int,
        *,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        axes_: tuple = ("vocab", "embed"),
        dp: bool = True,
    ):
        self.name = name
        self.vocab = vocab
        self.d = d
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.axes_ = axes_
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        return {"e": normal_init(key, (self.vocab, self.d), 0.02, self.param_dtype)}

    def axes(self) -> AxesTree:
        return {"e": self.axes_}

    def __call__(self, params: Params, ids: jax.Array, ctx: Ctx) -> jax.Array:
        e = reshard_param(params["e"].astype(self.dtype), self.axes_)
        s = jnp.take(e, ids, axis=0)
        if self.dp and ctx.collect:
            batch, t = ids.shape[0], int(math.prod(ids.shape[1:]))
            s = ctx.tap(
                "out",
                s,
                kind="embedding",
                a=ids.reshape(batch, t),
                T=t,
                D=self.vocab,
                p=self.d,
                param_path="e",
            )
        return s


class RMSNorm(Module):
    """RMSNorm with a DP "scale" tap on the gamma product."""

    def __init__(
        self,
        name: str,
        d: int,
        *,
        eps: float = 1e-6,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.d = d
        self.eps = eps
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        del key
        return {"g": jnp.ones((self.d,), self.param_dtype)}

    def axes(self) -> AxesTree:
        return {"g": (None,)}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        xf = x.astype(jnp.float32)
        x_hat = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        x_hat = x_hat.astype(self.dtype)
        s = x_hat * params["g"].astype(self.dtype)
        if self.dp and ctx.collect:
            batch = x.shape[0]
            t = int(math.prod(x.shape[1:-1])) if x.ndim > 2 else 1
            s = ctx.tap(
                "out",
                s,
                kind="scale",
                a=x_hat.reshape(batch, t, self.d),
                T=t,
                D=self.d,
                p=self.d,
                param_path="g",
            )
        return s


class LayerNorm(Module):
    """LayerNorm (scale+bias) with a DP "scale" tap."""

    def __init__(
        self,
        name: str,
        d: int,
        *,
        eps: float = 1e-5,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.d = d
        self.eps = eps
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        del key
        return {
            "g": jnp.ones((self.d,), self.param_dtype),
            "b": jnp.zeros((self.d,), self.param_dtype),
        }

    def axes(self) -> AxesTree:
        return {"g": (None,), "b": (None,)}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        x_hat = ((xf - mu) * jax.lax.rsqrt(var + self.eps)).astype(self.dtype)
        s = x_hat * params["g"].astype(self.dtype) + params["b"].astype(self.dtype)
        if self.dp and ctx.collect:
            batch = x.shape[0]
            t = int(math.prod(x.shape[1:-1])) if x.ndim > 2 else 1
            s = ctx.tap(
                "out",
                s,
                kind="scale",
                a=x_hat.reshape(batch, t, self.d),
                T=t,
                D=self.d,
                p=self.d,
                param_path="g",
                bias_path="b",
            )
        return s


class LayerScale(Module):
    """x * gamma, a per-channel scale on a residual branch (CaiT, BEiT),
    with a DP "scale" tap; gamma starts at ``init``."""

    def __init__(self, name: str, d: int, *, init: float, dtype=jnp.float32,
                 param_dtype=jnp.float32, dp: bool = True):
        self.name = name
        self.d = d
        self.init_value = init
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        del key
        return {"g": jnp.full((self.d,), self.init_value, self.param_dtype)}

    def axes(self) -> AxesTree:
        return {"g": (None,)}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        x = x.astype(self.dtype)
        s = x * params["g"].astype(self.dtype)
        if self.dp and ctx.collect:
            batch = x.shape[0]
            t = int(math.prod(x.shape[1:-1])) if x.ndim > 2 else 1
            s = ctx.tap(
                "out", s, kind="scale", a=x.reshape(batch, t, self.d),
                T=t, D=self.d, p=self.d, param_path="g",
            )
        return s


class GroupNorm(Module):
    """GroupNorm (the paper swaps BatchNorm for GroupNorm — BN is not DP-safe
    because batch statistics mix samples)."""

    def __init__(
        self,
        name: str,
        d: int,
        *,
        groups: int = 16,
        eps: float = 1e-5,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        assert d % groups == 0
        self.name = name
        self.d = d
        self.groups = groups
        self.eps = eps
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        del key
        return {
            "g": jnp.ones((self.d,), self.param_dtype),
            "b": jnp.zeros((self.d,), self.param_dtype),
        }

    def axes(self) -> AxesTree:
        return {"g": (None,), "b": (None,)}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        # x: (B, *spatial, d)
        batch = x.shape[0]
        spatial = x.shape[1:-1]
        xf = x.astype(jnp.float32).reshape(batch, -1, self.groups, self.d // self.groups)
        mu = jnp.mean(xf, axis=(1, 3), keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=(1, 3), keepdims=True)
        x_hat = ((xf - mu) * jax.lax.rsqrt(var + self.eps)).reshape(x.shape)
        x_hat = x_hat.astype(self.dtype)
        s = x_hat * params["g"].astype(self.dtype) + params["b"].astype(self.dtype)
        if self.dp and ctx.collect:
            t = int(math.prod(spatial))
            s = ctx.tap(
                "out",
                s,
                kind="scale",
                a=x_hat.reshape(batch, t, self.d),
                T=t,
                D=self.d,
                p=self.d,
                param_path="g",
                bias_path="b",
            )
        return s
