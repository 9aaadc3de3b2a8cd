"""Grouped-query attention with RoPE, sliding windows, KV cache, cross-attn.

Projections are ``Dense`` modules → each gets a DP tap; the attention math
itself is parameter-free so the mixed-ghost machinery never needs to see it,
except BEiT's relative position bias: a (R, heads) table per layer, gathered
by a static (T, T) index into an additive score bias, with a ``table`` tap.
The score computation routes through the blocked flash implementation
(``repro.kernels.flash_attention``) so (Sq, Skv) scores are never materialized.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.taps import Ctx, GatherInfo
from repro.kernels import dispatch
from repro.kernels.flash_attention.ops import flash_attention
from repro.nn.module import Dense, Module, Params, AxesTree
from repro.nn.rotary import apply_rope


def make_kv_cache(
    batch: int, max_len: int, n_kv: int, head_dim: int, dtype, window=None
) -> dict:
    """KV cache; a ring buffer of size ``window`` when sliding-window attention
    bounds the reachable context (Mixtral SWA at 500k context stores 4k slots).

    ``pos`` tracks the absolute position stored in each slot (-1 = empty);
    attention masks are computed from positions, so ring wraparound is free.
    """
    length = min(max_len, window) if window else max_len
    return {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "pos": jnp.full((length,), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }




def relative_position_index(window: tuple[int, int]) -> np.ndarray:
    """BEiT's (T, T) index into its relative position bias table, T = Wh*Ww + 1
    with the CLS token first (timm's ``gen_relative_position_index``): patch
    pairs read row (dh + Wh - 1) * (2 Ww - 1) + (dw + Ww - 1) of the offset
    grid; the last three rows are CLS -> token, token -> CLS and CLS -> CLS."""
    wh, ww = window
    n_rel = (2 * wh - 1) * (2 * ww - 1) + 3
    coords = np.stack(np.meshgrid(np.arange(wh), np.arange(ww), indexing="ij")).reshape(2, -1)
    rel = (coords[:, :, None] - coords[:, None, :]).transpose(1, 2, 0)
    rel = (rel[..., 0] + wh - 1) * (2 * ww - 1) + (rel[..., 1] + ww - 1)
    index = np.zeros((wh * ww + 1,) * 2, np.int32)
    index[1:, 1:] = rel
    index[0, :] = n_rel - 3
    index[:, 0] = n_rel - 2
    index[0, 0] = n_rel - 1
    return index


def blocked_decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k: jax.Array,  # (B, S, K, hd)
    v: jax.Array,  # (B, S, K, hd)
    pos: jax.Array,  # (S,) absolute positions, -1 = empty slot
    qpos: jax.Array,  # scalar absolute position of the query
    *,
    n_blocks: int,
    causal: bool = True,
    window=None,
    scale=None,
) -> jax.Array:
    """Context-parallel decode: per-block partial softmax + tiny combine.

    The KV sequence dim is reshaped into (n_blocks, S/n_blocks); when the
    cache is sharded over the model axis, GSPMD keeps each block's partial
    (o, m, l) local and the combine is an all-reduce of (B, H, hd) —
    context parallelism without shard_map.
    """
    b, _, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    s_len = k.shape[1]
    assert s_len % n_blocks == 0
    blk = s_len // n_blocks
    scale = scale if scale is not None else hd**-0.5

    qf = q.astype(jnp.float32).reshape(b, kh, g, hd)
    kb = k.astype(jnp.float32).reshape(b, n_blocks, blk, kh, hd)
    vb = v.astype(jnp.float32).reshape(b, n_blocks, blk, kh, hd)
    pb = pos.reshape(n_blocks, blk)

    scores = jnp.einsum("bkgd,bnskd->bnkgs", qf, kb) * scale  # (B,nb,K,g,blk)
    mask = pb <= qpos
    mask &= pb >= 0
    if window is not None:
        mask &= (qpos - pb) < window
    scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)

    m_b = jnp.max(scores, axis=-1)  # (B,nb,K,g)
    p = jnp.exp(scores - m_b[..., None])
    l_b = jnp.sum(p, axis=-1)
    o_b = jnp.einsum("bnkgs,bnskd->bnkgd", p, vb)
    # combine across blocks (the only cross-shard reduction)
    m = jnp.max(m_b, axis=1, keepdims=True)
    w = jnp.exp(m_b - m)
    l = jnp.sum(w * l_b, axis=1)
    o = jnp.sum(w[..., None] * o_b, axis=1) / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, 1, h, hd).astype(q.dtype)


class Attention(Module):
    def __init__(
        self,
        name: str,
        d_model: int,
        n_heads: int,
        n_kv: int,
        *,
        head_dim: Optional[int] = None,
        qkv_bias: bool = False,
        k_bias: bool = True,
        out_bias: bool = False,
        rel_pos_window: Optional[tuple[int, int]] = None,
        use_rope: bool = True,
        rope_theta: float = 10000.0,
        causal: bool = True,
        window: Optional[int] = None,
        cross: bool = False,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        block_q: int = 512,
        block_kv: int = 512,
        cp_threshold: int = 65536,
        cp_blocks: int = 64,
        dp: bool = True,
    ):
        self.name = name
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_kv = n_kv
        self.head_dim = head_dim or d_model // n_heads
        self.qkv_bias = qkv_bias
        self.out_bias = out_bias
        self.rel_pos_window = rel_pos_window
        if rel_pos_window is not None:
            self.rel_index = relative_position_index(rel_pos_window).reshape(-1)
            self.rel_rows = int(self.rel_index.max()) + 1
            self.rel_gather = GatherInfo(tuple(int(i) for i in self.rel_index))
        self.use_rope = use_rope
        self.rope_theta = rope_theta
        self.causal = causal
        self.window = window
        self.cross = cross
        self.dtype = dtype
        self.param_dtype = param_dtype
        self.block_q = block_q
        self.block_kv = block_kv
        self.cp_threshold = cp_threshold
        self.cp_blocks = cp_blocks
        self.dp = dp
        common = dict(dtype=dtype, param_dtype=param_dtype, dp=dp)
        self.wq = Dense(
            f"{name}.q", d_model, n_heads * self.head_dim,
            use_bias=qkv_bias, w_axes=("embed", "heads"), **common,
        )
        self.wk = Dense(
            f"{name}.k", d_model, n_kv * self.head_dim,
            use_bias=qkv_bias and k_bias, w_axes=("embed", "kv_heads"), **common,
        )
        self.wv = Dense(
            f"{name}.v", d_model, n_kv * self.head_dim,
            use_bias=qkv_bias, w_axes=("embed", "kv_heads"), **common,
        )
        self.wo = Dense(
            f"{name}.o", n_heads * self.head_dim, d_model,
            use_bias=out_bias, w_axes=("heads", "embed"),
            init_scale=1.0, **common,
        )

    def init(self, key: jax.Array) -> Params:
        ks = jax.random.split(key, 4)
        p = {
            "q": self.wq.init(ks[0]),
            "k": self.wk.init(ks[1]),
            "v": self.wv.init(ks[2]),
            "o": self.wo.init(ks[3]),
        }
        if self.rel_pos_window is not None:
            p["relative_position_bias_table"] = (0.02 * jax.random.truncated_normal(
                jax.random.fold_in(key, 4), -2.0, 2.0, (self.rel_rows, self.n_heads)
            )).astype(self.param_dtype)
        return p

    def axes(self) -> AxesTree:
        a = {
            "q": self.wq.axes(),
            "k": self.wk.axes(),
            "v": self.wv.axes(),
            "o": self.wo.axes(),
        }
        if self.rel_pos_window is not None:
            a["relative_position_bias_table"] = (None, None)
        return a

    def _rel_pos_bias(self, params: Params, b: int, s: int, ctx: Ctx) -> jax.Array:
        """(B, H, S, S) score bias: the layer's table gathered by the static
        index, broadcast over the batch so that the tap sees each sample's
        cotangent."""
        if s * s != self.rel_index.size:
            raise ValueError(f"relative position bias built for {self.rel_index.size} token "
                             f"pairs, got {s} tokens")
        table = params["relative_position_bias_table"].astype(self.dtype)
        bias = jnp.broadcast_to(jnp.take(table.T, self.rel_index, axis=1),
                                (b, self.n_heads, s * s))
        if self.dp and ctx.collect:
            bias = ctx.tap(
                "rel_pos", bias, kind="table", T=s * s, D=self.rel_rows, p=self.n_heads,
                param_path="relative_position_bias_table", gather=self.rel_gather,
            )
        return bias.reshape(b, self.n_heads, s, s)

    def __call__(
        self,
        params: Params,
        x: jax.Array,  # (B, S, d)
        ctx: Ctx,
        *,
        positions: Optional[jax.Array] = None,  # (S,) or (B, S)
        cache: Optional[dict] = None,
        kv_src: Optional[jax.Array] = None,  # encoder states for cross-attn
    ) -> tuple[jax.Array, Optional[dict]]:
        b, s, _ = x.shape
        q = self.wq(params["q"], x, ctx.scope("q")).reshape(b, s, self.n_heads, self.head_dim)

        if self.cross:
            assert kv_src is not None or cache is not None
            if cache is not None and kv_src is None:
                k, v = cache["k"], cache["v"]  # precomputed encoder projections
                new_cache = cache
            else:
                skv = kv_src.shape[1]
                k = self.wk(params["k"], kv_src, ctx.scope("k"))
                k = k.reshape(b, skv, self.n_kv, self.head_dim)
                v = self.wv(params["v"], kv_src, ctx.scope("v"))
                v = v.reshape(b, skv, self.n_kv, self.head_dim)
                new_cache = {"k": k, "v": v} if cache is not None else None
            # serving (cache present) traces through kernel dispatch; the
            # training path needs the custom-VJP XLA op directly
            fa = dispatch.flash_attention if cache is not None else flash_attention
            out = fa(
                q, k, v, causal=False, block_q=self.block_q, block_kv=self.block_kv,
            )
            y = self.wo(params["o"], out.reshape(b, s, -1), ctx.scope("o"))
            return y, new_cache

        k = self.wk(params["k"], x, ctx.scope("k")).reshape(b, s, self.n_kv, self.head_dim)
        v = self.wv(params["v"], x, ctx.scope("v")).reshape(b, s, self.n_kv, self.head_dim)
        if positions is None:
            positions = jnp.arange(s)
        if self.use_rope:
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)

        if cache is None:
            bias = None if self.rel_pos_window is None else self._rel_pos_bias(params, b, s, ctx)
            out = flash_attention(
                q, k, v, causal=self.causal, window=self.window,
                block_q=self.block_q, block_kv=self.block_kv, bias=bias,
            )
            new_cache = None
        else:
            idx = cache["idx"]
            length = cache["k"].shape[1]
            kc = k.astype(cache["k"].dtype)
            vc = v.astype(cache["v"].dtype)
            if s == 1:
                slot = jnp.mod(idx, length)
                ck = lax.dynamic_update_slice(cache["k"], kc, (0, slot, 0, 0))
                cv = lax.dynamic_update_slice(cache["v"], vc, (0, slot, 0, 0))
                pos = lax.dynamic_update_slice(cache["pos"], idx[None], (slot,))
            elif s <= length:
                # prefill from empty (idx assumed 0)
                ck = lax.dynamic_update_slice(cache["k"], kc, (0, 0, 0, 0))
                cv = lax.dynamic_update_slice(cache["v"], vc, (0, 0, 0, 0))
                pos = lax.dynamic_update_slice(
                    cache["pos"], jnp.arange(s, dtype=jnp.int32), (0,)
                )
            else:
                # ring prefill: attend over the full sequence, but only the
                # last ``length`` slots stay reachable for later decode steps.
                # Slot invariant: slot j holds position p with p % length == j,
                # so later single-token writes (slot = idx % length) line up.
                shift = s % length
                ck = jnp.roll(kc[:, s - length :], shift, axis=1)
                cv = jnp.roll(vc[:, s - length :], shift, axis=1)
                pos = jnp.roll(jnp.arange(s - length, s, dtype=jnp.int32), shift)
                new_cache = {"k": ck, "v": cv, "pos": pos, "idx": idx + s}
                out = dispatch.flash_attention(
                    q, kc, vc, causal=self.causal, window=self.window,
                    block_q=self.block_q, block_kv=self.block_kv,
                )
                y = self.wo(params["o"], out.reshape(b, s, -1), ctx.scope("o"))
                return y, new_cache
            new_cache = {"k": ck, "v": cv, "pos": pos, "idx": idx + s}
            if s == 1 and length >= self.cp_threshold:
                out = blocked_decode_attention(
                    q, ck, cv, pos, idx, n_blocks=self.cp_blocks,
                    causal=self.causal, window=self.window,
                )
            else:
                # traced q_offset + ring kv_positions: dispatch falls back
                # to the XLA path today, but the choice point is here
                out = dispatch.flash_attention(
                    q, ck, cv, causal=self.causal, window=self.window,
                    q_offset=idx, kv_positions=pos,
                    block_q=self.block_q, block_kv=self.block_kv,
                )
        y = self.wo(params["o"], out.reshape(b, s, -1), ctx.scope("o"))
        return y, new_cache
