"""Composable transformer blocks driven by ArchConfig."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.taps import Ctx
from repro.nn.attention import Attention, make_kv_cache
from repro.nn.mamba import MambaBlock
from repro.nn.mlp import MLP, GatedMLP
from repro.nn.module import LayerNorm, LayerScale, Module, Params, AxesTree, RMSNorm
from repro.nn.moe import MoE
from repro.nn.stack import SequentialBlocks
from repro.nn.xlstm import MLSTMBlock, SLSTMBlock


def _norm(cfg: ArchConfig, name: str, d: int, dtype, param_dtype):
    cls = RMSNorm if cfg.norm == "rmsnorm" else LayerNorm
    eps = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    return cls(name, d, dtype=dtype, param_dtype=param_dtype, **eps)


def _ffn(cfg: ArchConfig, name: str, d_ff: int, dtype, param_dtype):
    if cfg.act == "swiglu":
        return GatedMLP(name, cfg.d_model, d_ff, dtype=dtype, param_dtype=param_dtype)
    return MLP(name, cfg.d_model, d_ff, approximate=cfg.act != "gelu_erf",
               dtype=dtype, param_dtype=param_dtype)


class TransformerBlock(Module):
    """Pre-norm attention + {MLP | MoE [+ parallel dense-residual MLP]}.

    ``cfg.layer_scale`` scales both residual branches by learned per-channel
    gammas (``ls1``, ``ls2``); ``rel_pos_window`` gives the attention BEiT's
    relative position bias over that patch grid (plus a CLS token).
    """

    def __init__(
        self,
        name: str,
        cfg: ArchConfig,
        *,
        use_moe: bool = False,
        cross: bool = False,
        causal: bool = True,
        rel_pos_window: Optional[tuple[int, int]] = None,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
    ):
        self.name = name
        self.cfg = cfg
        self.use_moe = use_moe and cfg.moe_experts > 0
        self.cross = cross
        d = cfg.d_model
        self.n1 = _norm(cfg, "n1", d, dtype, param_dtype)
        self.attn = Attention(
            "attn", d, cfg.n_heads, cfg.n_kv,
            head_dim=cfg.head_dim,
            qkv_bias=cfg.qkv_bias,
            k_bias=cfg.k_bias,
            out_bias=cfg.out_bias,
            rel_pos_window=rel_pos_window,
            use_rope=cfg.norm == "rmsnorm",  # LN families (whisper) use learned pos
            rope_theta=cfg.rope_theta,
            causal=causal,
            window=cfg.window,
            dtype=dtype, param_dtype=param_dtype,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
        )
        if cross:
            self.nx = _norm(cfg, "nx", d, dtype, param_dtype)
            self.xattn = Attention(
                "xattn", d, cfg.n_heads, cfg.n_kv,
                head_dim=cfg.head_dim, use_rope=False, causal=False, cross=True,
                dtype=dtype, param_dtype=param_dtype,
                block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
            )
        self.n2 = _norm(cfg, "n2", d, dtype, param_dtype)
        if self.use_moe:
            self.moe = MoE(
                "moe", d, cfg.d_ff, cfg.moe_experts, cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor,
                dtype=dtype, param_dtype=param_dtype,
            )
            if cfg.moe_dense_ff:
                self.dense_mlp = _ffn(cfg, "dense_mlp", cfg.moe_dense_ff, dtype, param_dtype)
        else:
            self.mlp = _ffn(cfg, "mlp", cfg.d_ff, dtype, param_dtype)
        self.scales = ()
        if cfg.layer_scale:
            self.scales = tuple(
                LayerScale(n, d, init=cfg.layer_scale, dtype=dtype, param_dtype=param_dtype)
                for n in ("ls1", "ls2"))

    def init(self, key: jax.Array) -> Params:
        ks = iter(jax.random.split(key, 8))
        p = {"n1": self.n1.init(next(ks)), "attn": self.attn.init(next(ks)),
             "n2": self.n2.init(next(ks))}
        p.update({m.name: m.init(key) for m in self.scales})
        if self.cross:
            p["nx"] = self.nx.init(next(ks))
            p["xattn"] = self.xattn.init(next(ks))
        if self.use_moe:
            p["moe"] = self.moe.init(next(ks))
            if self.cfg.moe_dense_ff:
                p["dense_mlp"] = self.dense_mlp.init(next(ks))
        else:
            p["mlp"] = self.mlp.init(next(ks))
        return p

    def axes(self) -> AxesTree:
        a = {"n1": self.n1.axes(), "attn": self.attn.axes(), "n2": self.n2.axes()}
        a.update({m.name: m.axes() for m in self.scales})
        if self.cross:
            a["nx"] = self.nx.axes()
            a["xattn"] = self.xattn.axes()
        if self.use_moe:
            a["moe"] = self.moe.axes()
            if self.cfg.moe_dense_ff:
                a["dense_mlp"] = self.dense_mlp.axes()
        else:
            a["mlp"] = self.mlp.axes()
        return a

    def init_cache(self, batch: int, dtype, *, max_len: int = 0, enc_seq: int = 0):
        c = {
            "kv": make_kv_cache(
                batch, max_len, self.attn.n_kv, self.attn.head_dim, dtype,
                window=self.cfg.window,
            )
        }
        if self.cross:
            c["xkv"] = {
                "k": jnp.zeros((batch, enc_seq, self.xattn.n_kv, self.xattn.head_dim), dtype),
                "v": jnp.zeros((batch, enc_seq, self.xattn.n_kv, self.xattn.head_dim), dtype),
            }
        return c

    def __call__(
        self,
        params: Params,
        x: jax.Array,
        ctx: Ctx,
        *,
        cache: Optional[dict] = None,
        positions: Optional[jax.Array] = None,
        enc_out: Optional[jax.Array] = None,
        dispatch: str = "per_sample",
    ):
        kv_cache = cache["kv"] if cache is not None else None
        h, new_kv = self.attn(
            params["attn"], self.n1(params["n1"], x, ctx.scope("n1")),
            ctx.scope("attn"), positions=positions, cache=kv_cache,
        )
        x = x + self._scaled(0, params, h, ctx)
        new_cache = {"kv": new_kv} if cache is not None else None
        if self.cross:
            xc = cache["xkv"] if cache is not None else None
            h, new_x = self.xattn(
                params["xattn"], self.nx(params["nx"], x, ctx.scope("nx")),
                ctx.scope("xattn"), cache=xc, kv_src=enc_out,
            )
            x = x + h
            if cache is not None:
                new_cache["xkv"] = new_x
        h_in = self.n2(params["n2"], x, ctx.scope("n2"))
        if self.use_moe:
            h = self.moe(params["moe"], h_in, ctx.scope("moe"), dispatch=dispatch)
            if self.cfg.moe_dense_ff:
                h = h + self.dense_mlp(params["dense_mlp"], h_in, ctx.scope("dense_mlp"))
        else:
            h = self.mlp(params["mlp"], h_in, ctx.scope("mlp"))
        return x + self._scaled(1, params, h, ctx), new_cache

    def _scaled(self, i: int, params: Params, h: jax.Array, ctx: Ctx) -> jax.Array:
        """Residual branch ``i`` times its layer scale, where the block has one."""
        if not self.scales:
            return h
        m = self.scales[i]
        return m(params[m.name], h, ctx.scope(m.name))


class MambaWrap(Module):
    """Mamba block + optional MoE/MLP sublayer (Jamba layer layout)."""

    def __init__(self, name: str, cfg: ArchConfig, *, use_moe: bool,
                 dtype=jnp.float32, param_dtype=jnp.float32):
        self.name = name
        self.cfg = cfg
        self.use_moe = use_moe and cfg.moe_experts > 0
        d = cfg.d_model
        self.n1 = _norm(cfg, "n1", d, dtype, param_dtype)
        self.mamba = MambaBlock(
            "mamba", d, head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_d_state,
            chunk=cfg.ssm_chunk, dtype=dtype, param_dtype=param_dtype,
        )
        self.n2 = _norm(cfg, "n2", d, dtype, param_dtype)
        if self.use_moe:
            self.moe = MoE(
                "moe", d, cfg.d_ff, cfg.moe_experts, cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor, dtype=dtype, param_dtype=param_dtype,
            )
        else:
            self.mlp = _ffn(cfg, "mlp", cfg.d_ff, dtype, param_dtype)

    def init(self, key: jax.Array) -> Params:
        ks = jax.random.split(key, 4)
        p = {"n1": self.n1.init(ks[0]), "mamba": self.mamba.init(ks[1]),
             "n2": self.n2.init(ks[2])}
        if self.use_moe:
            p["moe"] = self.moe.init(ks[3])
        else:
            p["mlp"] = self.mlp.init(ks[3])
        return p

    def axes(self) -> AxesTree:
        a = {"n1": self.n1.axes(), "mamba": self.mamba.axes(), "n2": self.n2.axes()}
        if self.use_moe:
            a["moe"] = self.moe.axes()
        else:
            a["mlp"] = self.mlp.axes()
        return a

    def init_cache(self, batch: int, dtype, **kw):
        return {"mamba": self.mamba.init_cache(batch, dtype)}

    def __call__(self, params, x, ctx, *, cache=None, positions=None,
                 enc_out=None, dispatch="per_sample"):
        mc = cache["mamba"] if cache is not None else None
        h, new_mc = self.mamba(
            params["mamba"], self.n1(params["n1"], x, ctx.scope("n1")),
            ctx.scope("mamba"), cache=mc,
        )
        x = x + h
        h_in = self.n2(params["n2"], x, ctx.scope("n2"))
        if self.use_moe:
            h = self.moe(params["moe"], h_in, ctx.scope("moe"), dispatch=dispatch)
        else:
            h = self.mlp(params["mlp"], h_in, ctx.scope("mlp"))
        new_cache = {"mamba": new_mc} if cache is not None else None
        return x + h, new_cache


class XLSTMWrap(Module):
    """mLSTM or sLSTM block adapter with the uniform block interface."""

    def __init__(self, name: str, cfg: ArchConfig, kind: str,
                 dtype=jnp.float32, param_dtype=jnp.float32):
        self.name = name
        self.kind = kind
        if kind == "mlstm":
            self.block = MLSTMBlock(
                "m", cfg.d_model, cfg.n_heads, chunk=cfg.ssm_chunk,
                dtype=dtype, param_dtype=param_dtype,
            )
        else:
            self.block = SLSTMBlock(
                "s", cfg.d_model, cfg.n_heads, dtype=dtype, param_dtype=param_dtype,
            )

    def init(self, key):
        return {"b": self.block.init(key)}

    def axes(self):
        return {"b": self.block.axes()}

    def init_cache(self, batch: int, dtype, **kw):
        return {"b": self.block.init_cache(batch, dtype)}

    def __call__(self, params, x, ctx, *, cache=None, positions=None,
                 enc_out=None, dispatch="per_sample"):
        c = cache["b"] if cache is not None else None
        x, new_c = self.block(params["b"], x, ctx.scope("b"), cache=c)
        return x, ({"b": new_c} if cache is not None else None)


def build_period(cfg: ArchConfig, *, cross: bool = False, causal: bool = True,
                 dtype=jnp.float32, param_dtype=jnp.float32) -> tuple[Module, int]:
    """Build the repeating period block; returns (period_module, n_periods)."""
    pattern = cfg.block_pattern
    if not pattern:
        period_len = cfg.moe_every if cfg.moe_experts else 1
        blocks = []
        for i in range(period_len):
            use_moe = cfg.moe_experts > 0 and (i % cfg.moe_every == cfg.moe_every - 1)
            blocks.append(
                TransformerBlock(
                    f"b{i}", cfg, use_moe=use_moe, cross=cross, causal=causal,
                    dtype=dtype, param_dtype=param_dtype,
                )
            )
        assert cfg.n_layers % period_len == 0
        if period_len == 1:
            return blocks[0], cfg.n_layers
        return SequentialBlocks("period", blocks), cfg.n_layers // period_len
    # explicit pattern (jamba / xlstm)
    blocks = []
    for i, kind in enumerate(pattern):
        use_moe = cfg.moe_experts > 0 and (i % cfg.moe_every == cfg.moe_every - 1)
        if kind == "attn":
            blocks.append(TransformerBlock(f"b{i}", cfg, use_moe=use_moe,
                                           dtype=dtype, param_dtype=param_dtype))
        elif kind == "mamba":
            blocks.append(MambaWrap(f"b{i}", cfg, use_moe=use_moe,
                                    dtype=dtype, param_dtype=param_dtype))
        elif kind in ("mlstm", "slstm"):
            blocks.append(XLSTMWrap(f"b{i}", cfg, kind, dtype=dtype, param_dtype=param_dtype))
        else:
            raise ValueError(kind)
    assert cfg.n_layers % len(pattern) == 0
    return SequentialBlocks("period", blocks), cfg.n_layers // len(pattern)
