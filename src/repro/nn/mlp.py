"""Feed-forward blocks: gated (SwiGLU) and plain (GELU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.taps import Ctx
from repro.nn.module import Dense, Module, Params, AxesTree


class GatedMLP(Module):
    """SwiGLU: down(silu(gate(x)) * up(x)).

    Gate and up are SEPARATE matmuls: a fused (d, 2f) projection must be
    split along the TP-sharded dim afterwards, which GSPMD lowers to
    collective-permute + all-to-all redistributions (measured ~2 GB/layer on
    yi-6b — EXPERIMENTS.md §Perf iteration 2).
    """

    def __init__(
        self,
        name: str,
        d_model: int,
        d_ff: int,
        *,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.d_model = d_model
        self.d_ff = d_ff
        common = dict(dtype=dtype, param_dtype=param_dtype, dp=dp, use_bias=False)
        self.wg = Dense(f"{name}.wg", d_model, d_ff, w_axes=("embed", "mlp"), **common)
        self.wu = Dense(f"{name}.wu", d_model, d_ff, w_axes=("embed", "mlp"), **common)
        self.wo = Dense(f"{name}.wo", d_ff, d_model, w_axes=("mlp", "embed"), **common)

    def init(self, key: jax.Array) -> Params:
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wg": self.wg.init(k1), "wu": self.wu.init(k2), "wo": self.wo.init(k3)}

    def axes(self) -> AxesTree:
        return {"wg": self.wg.axes(), "wu": self.wu.axes(), "wo": self.wo.axes()}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        gate = self.wg(params["wg"], x, ctx.scope("wg"))
        up = self.wu(params["wu"], x, ctx.scope("wu"))
        return self.wo(params["wo"], jax.nn.silu(gate) * up, ctx.scope("wo"))


class MLP(Module):
    """Plain transformer FFN with GELU (whisper, ViT, phi-style); the tanh
    approximation unless ``approximate=False`` (BEiT's exact erf GELU)."""

    def __init__(
        self,
        name: str,
        d_model: int,
        d_ff: int,
        *,
        use_bias: bool = True,
        approximate: bool = True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        dp: bool = True,
    ):
        self.name = name
        self.approximate = approximate
        common = dict(dtype=dtype, param_dtype=param_dtype, dp=dp, use_bias=use_bias)
        self.wi = Dense(f"{name}.wi", d_model, d_ff, w_axes=("embed", "mlp"), **common)
        self.wo = Dense(f"{name}.wo", d_ff, d_model, w_axes=("mlp", "embed"), **common)

    def init(self, key: jax.Array) -> Params:
        k1, k2 = jax.random.split(key)
        return {"wi": self.wi.init(k1), "wo": self.wo.init(k2)}

    def axes(self) -> AxesTree:
        return {"wi": self.wi.axes(), "wo": self.wo.axes()}

    def __call__(self, params: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
        h = jax.nn.gelu(self.wi(params["wi"], x, ctx.scope("wi")), approximate=self.approximate)
        return self.wo(params["wo"], h, ctx.scope("wo"))
