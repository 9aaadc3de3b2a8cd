"""Convolution layers with DP taps (the paper's central case).

``Conv2d`` records its *raw* input plus unfold metadata; the DP engine unfolds
lazily (im2col, ``unfold2d``) only on the branch the
layerwise decision selects, so the forward pass stays on the fused conv op.

``DepthwiseConv1d`` (Mamba/xLSTM frontends) records the unfolded input
directly — its kernel is tiny (k*d params) so the instantiate branch always
wins and the unfold is k copies of a (B, T, d) tensor.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.taps import ConvInfo, Ctx
from repro.nn.module import Module, Params, AxesTree, normal_init
from repro.parallel.reshard import reshard_param


def unfold2d(x: jax.Array, info: ConvInfo) -> jax.Array:
    """U(a): (B, H, W, d) -> (B, H_out*W_out, kh*kw*d).

    Features are offset-major, index = (kh_i * kw + kw_i) * d + c: one
    strided slice of the padded input per kernel offset, joined along the
    channels.  This is the conv weight's own (kh, kw, d, p) order, so a
    (kh*kw*d, p) gradient reshapes to the weight with no transpose; and the
    channels stay minor, so on a TPU the patches leave one fusion in the
    row-major layout the Pallas ghost norm reads.  Where the patches tile the
    input (stride = kernel, no padding, no dilation: a ViT's patch
    embedding) the same order is one reshape and transpose.
    """
    kh, kw = info.kernel
    sh, sw = info.strides
    dh, dw = info.rhs_dilation or (1, 1)
    b, h, w, d = x.shape
    pads = info.padding
    if isinstance(pads, str):
        pads = lax.padtype_to_pads(
            (h, w), ((kh - 1) * dh + 1, (kw - 1) * dw + 1), (sh, sw), pads
        )
    if (sh, sw) == (kh, kw) and (dh, dw) == (1, 1) and all(p == 0 for lh in pads for p in lh):
        ho, wo = h // kh, w // kw
        tiles = x[:, : ho * kh, : wo * kw].reshape(b, ho, kh, wo, kw, d)
        return tiles.transpose(0, 1, 3, 2, 4, 5).reshape(b, ho * wo, kh * kw * d)
    return _unfold2d_slices(x, info, pads)


def _unfold2d_slices(x: jax.Array, info: ConvInfo, pads) -> jax.Array:
    """``unfold2d`` as one strided slice per kernel offset, any conv."""
    kh, kw = info.kernel
    sh, sw = info.strides
    dh, dw = info.rhs_dilation or (1, 1)
    b, h, w, d = x.shape
    (top, bottom), (left, right) = pads
    xp = lax.pad(x, jnp.zeros((), x.dtype), ((0, 0, 0), (top, bottom, 0), (left, right, 0),
                                             (0, 0, 0)))
    ho = (xp.shape[1] - (kh - 1) * dh - 1) // sh + 1
    wo = (xp.shape[2] - (kw - 1) * dw - 1) // sw + 1
    cols = [
        lax.slice(xp, (0, i * dh, j * dw, 0),
                  (b, i * dh + (ho - 1) * sh + 1, j * dw + (wo - 1) * sw + 1, d),
                  (1, sh, sw, 1))
        for i in range(kh) for j in range(kw)
    ]
    return jnp.concatenate(cols, axis=-1).reshape(b, ho * wo, kh * kw * d)


class Conv2d(Module):
    """NHWC conv with a DP "matmul" tap (T = H_out*W_out, D = d*kh*kw)."""

    def __init__(
        self,
        name: str,
        d_in: int,
        d_out: int,
        kernel: tuple[int, int],
        *,
        strides: tuple[int, int] = (1, 1),
        padding="SAME",
        use_bias: bool = True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.d_in = d_in
        self.d_out = d_out
        self.kernel = kernel
        self.strides = strides
        self.padding = padding
        self.use_bias = use_bias
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        fan_in = self.d_in * math.prod(self.kernel)
        p = {
            "w": normal_init(
                key,
                (*self.kernel, self.d_in, self.d_out),
                1.0 / math.sqrt(fan_in),
                self.param_dtype,
            )
        }
        if self.use_bias:
            p["b"] = jnp.zeros((self.d_out,), self.param_dtype)
        return p

    def axes(self) -> AxesTree:
        a = {"w": (None, None, "embed", "mlp")}
        if self.use_bias:
            a["b"] = ("mlp",)
        return a

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        w = reshard_param(params["w"].astype(self.dtype), (None, None, "embed", "mlp"))
        x = x.astype(self.dtype)
        s = lax.conv_general_dilated(
            x,
            w,
            window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            s = s + params["b"].astype(self.dtype)
        if self.dp and ctx.collect:
            t = int(math.prod(s.shape[1:-1]))
            big_d = self.d_in * math.prod(self.kernel)
            s = ctx.tap(
                "out",
                s,
                kind="matmul",
                a=x,  # raw input; engine unfolds lazily
                T=t,
                D=big_d,
                p=self.d_out,
                param_path="w",
                bias_path="b" if self.use_bias else None,
                conv=ConvInfo(
                    kernel=tuple(self.kernel),
                    strides=tuple(self.strides),
                    padding=self.padding,
                ),
            )
        return s


class DepthwiseConv1d(Module):
    """Causal depthwise conv1d (Mamba / xLSTM frontend), kernel (k, d).

    s[b, t, c] = sum_j w[j, c] * x[b, t - k + 1 + j, c]  (left-padded).
    Tap kind "dw_conv": recorded act is the unfolded (B, T, k, d).
    """

    def __init__(
        self,
        name: str,
        d: int,
        k: int = 4,
        *,
        use_bias: bool = True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.d = d
        self.k = k
        self.use_bias = use_bias
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.dp = dp

    def init(self, key: jax.Array) -> Params:
        p = {"w": normal_init(key, (self.k, self.d), 1.0 / math.sqrt(self.k), self.param_dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.d,), self.param_dtype)
        return p

    def axes(self) -> AxesTree:
        a = {"w": (None, "mlp")}
        if self.use_bias:
            a["b"] = ("mlp",)
        return a

    def unfold(self, x: jax.Array, state: Optional[jax.Array] = None) -> jax.Array:
        """(B, T, d) -> (B, T, k, d): window ending at each t (causal)."""
        if state is None:
            pad = jnp.zeros((x.shape[0], self.k - 1, self.d), x.dtype)
        else:
            pad = state.astype(x.dtype)  # (B, k-1, d) trailing context
        xp = jnp.concatenate([pad, x], axis=1)  # (B, T+k-1, d)
        cols = [xp[:, j : j + x.shape[1], :] for j in range(self.k)]
        return jnp.stack(cols, axis=2)

    def __call__(
        self,
        params: Params,
        x: jax.Array,
        ctx: Ctx,
        *,
        state: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, jax.Array]:
        """Returns (y, new_state) where state is the last k-1 inputs."""
        x = x.astype(self.dtype)
        unf = self.unfold(x, state)  # (B, T, k, d)
        w = reshard_param(params["w"].astype(self.dtype), (None, "mlp"))
        s = jnp.einsum("btkd,kd->btd", unf, w)
        if self.use_bias:
            s = s + params["b"].astype(self.dtype)
        if self.dp and ctx.collect:
            s = ctx.tap(
                "out",
                s,
                kind="dw_conv",
                a=unf,
                T=int(x.shape[1]),
                D=self.k,
                p=self.d,
                param_path="w",
                bias_path="b" if self.use_bias else None,
            )
        if state is None:
            new_state = x[:, -(self.k - 1) :, :] if x.shape[1] >= self.k - 1 else None
        else:
            joint = jnp.concatenate([state.astype(x.dtype), x], axis=1)
            new_state = joint[:, -(self.k - 1) :, :]
        return s, new_state


def max_pool2d(x: jax.Array, window: int = 2, stride: int = 2) -> jax.Array:
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        "VALID",
    )


def avg_pool2d(x: jax.Array, window: int, stride: int = 1, padding="VALID") -> jax.Array:
    summed = lax.reduce_window(
        x, 0.0, lax.add, (1, window, window, 1), (1, stride, stride, 1), padding
    )
    return summed / float(window * window)


def global_avg_pool(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2))
