"""Blocked online-softmax attention with a custom VJP (FlashAttention-2 style).

This is the XLA path: a two-level ``lax.scan`` (outer: query blocks, inner: KV
blocks) that never materializes the (Sq, Skv) score matrix.  Forward saves only
(q, k, v, o, lse); backward recomputes probabilities blockwise.  The Pallas TPU
kernel in ``flash_attention.py`` implements the same tiling for the MXU; this
function is its lowering fallback and its semantics oracle is ``ref.py``.

Supports GQA (H query heads over K kv heads), causal masking, sliding windows
(Mixtral SWA), decode offsets, partially-filled KV caches, and an additive
score bias (BEiT's relative position bias) whose cotangent is the score
cotangent dS the backward forms block by block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_mask(qi: jax.Array, kj: jax.Array, *, causal, window, kv_valid_len,
                require_nonneg=False):
    """(bq, bkv) boolean mask from absolute q positions qi and kv positions kj."""
    m = jnp.ones((qi.shape[0], kj.shape[0]), dtype=bool)
    qi = qi[:, None]
    kj = kj[None, :]
    if causal:
        m &= kj <= qi
    if window is not None:
        m &= (qi - kj) < window
    if kv_valid_len is not None:
        m &= kj < kv_valid_len
    if require_nonneg:
        m &= kj >= 0
    return m


def _pad_to(x: jax.Array, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _bias_blocks(bias, nq: int, nkv: int):
    """(B, Sq, K, g, Skv) -> (nq, nkv, B, bq, K, g, bkv) score-bias tiles,
    the scans' xs; None stays None."""
    if bias is None:
        return None
    b, sq, kh, g, skv = bias.shape
    tiles = bias.reshape(b, nq, sq // nq, kh, g, nkv, skv // nkv)
    return tiles.transpose(1, 5, 0, 2, 3, 4, 6)


def _bias_unblocks(tiles):
    """Inverse of ``_bias_blocks``: (nq, nkv, B, bq, K, g, bkv) -> (B, Sq, K, g, Skv)."""
    nq, nkv, b, bq, kh, g, bkv = tiles.shape
    return tiles.transpose(2, 0, 3, 4, 5, 1, 6).reshape(b, nq * bq, kh, g, nkv * bkv)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, bias, causal, window, q_offset, block_q, block_kv, scale, kv_valid_is_none):
    # Precision boundary INSIDE the custom vjp: inputs/outputs stay in the
    # model dtype so attention cotangents (and their TP all-reduces) are
    # bf16; the softmax math runs fp32 internally.  ``bias`` (B, Sq, K, g,
    # Skv) or None (an empty pytree: no cotangent).
    out, _ = _flash_fwd_impl(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        None, causal, window, q_offset, block_q, block_kv, scale, bias=bias,
    )
    return out.astype(q.dtype)


def _flash_fwd_impl(
    q, k, v, kv_valid_len, causal, window, q_offset, block_q, block_kv, scale,
    kv_positions=None, bias=None,
):
    """q: (B, Sq, K, g, hd) f32; k/v: (B, Skv, K, hd) f32.

    ``kv_positions`` (Skv,) gives the absolute position of each cache slot
    (ring buffers store positions out of order; negative marks unwritten
    slots, which the causal mask then excludes).  ``bias`` (B, Sq, K, g,
    Skv), in any float type, is added to the scaled scores.  Returns out
    and lse.
    """
    b, sq, kh, g, hd = q.shape
    skv = k.shape[1]
    nq = sq // block_q
    nkv = skv // block_kv

    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv) if kv_positions is None else kv_positions

    def q_block(carry, qb):
        q_i, qpos_i, bias_i = qb  # (B, bq, K, g, hd), (bq,), (nkv, B, bq, K, g, bkv)

        def kv_block(acc, kb):
            o, m, l = acc
            k_j, v_j, kpos_j, bias_j = kb
            s = jnp.einsum("bqkgd,bskd->bqkgs", q_i, k_j) * scale  # (B,bq,K,g,bkv)
            if bias_j is not None:
                s = s + bias_j.astype(jnp.float32)
            msk = _block_mask(
                qpos_i, kpos_j, causal=causal, window=window,
                kv_valid_len=None if kv_positions is not None else kv_valid_len,
                require_nonneg=kv_positions is not None,
            )
            s = jnp.where(msk[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            o = o * alpha[..., None] + jnp.einsum("bqkgs,bskd->bqkgd", p, v_j)
            return (o, m_new, l), None

        o0 = jnp.zeros((b, block_q, kh, g, hd), jnp.float32)
        m0 = jnp.full((b, block_q, kh, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, block_q, kh, g), jnp.float32)
        ks = k.reshape(b, nkv, block_kv, kh, hd).swapaxes(0, 1)
        vs = v.reshape(b, nkv, block_kv, kh, hd).swapaxes(0, 1)
        kps = kpos.reshape(nkv, block_kv)
        (o, m, l), _ = lax.scan(kv_block, (o0, m0, l0), (ks, vs, kps, bias_i))
        l = jnp.maximum(l, 1e-30)
        out_i = o / l[..., None]
        lse_i = m + jnp.log(l)
        return carry, (out_i, lse_i)

    qs = q.reshape(b, nq, block_q, kh, g, hd).swapaxes(0, 1)
    qps = qpos.reshape(nq, block_q)
    _, (outs, lses) = lax.scan(q_block, None, (qs, qps, _bias_blocks(bias, nq, nkv)))
    out = outs.swapaxes(0, 1).reshape(b, sq, kh, g, hd)
    lse = lses.swapaxes(0, 1).reshape(b, sq, kh, g)
    return out, lse


def _flash_fwd(q, k, v, bias, causal, window, q_offset, block_q, block_kv, scale,
               kv_valid_is_none):
    out, lse = _flash_fwd_impl(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        None, causal, window, q_offset, block_q, block_kv, scale, bias=bias,
    )
    out = out.astype(q.dtype)
    # residuals kept in the model dtype (halves flash residual memory)
    return out, (q, k, v, out, lse, bias)


def _flash_bwd(causal, window, q_offset, block_q, block_kv, scale, kv_valid_is_none, res, do):
    """(dq, dk, dv, dbias): dbias, the unscaled score cotangent, is None
    without a bias."""
    q, k, v, out, lse, bias = res
    in_dtype = q.dtype
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    out = out.astype(jnp.float32)
    b, sq, kh, g, hd = q.shape
    skv = k.shape[1]
    nq = sq // block_q
    nkv = skv // block_kv
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * out, axis=-1)  # (B, Sq, K, g)

    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)

    ks = k.reshape(b, nkv, block_kv, kh, hd).swapaxes(0, 1)
    vs = v.reshape(b, nkv, block_kv, kh, hd).swapaxes(0, 1)
    kps = kpos.reshape(nkv, block_kv)

    def q_block(carry, qb):
        dk_acc, dv_acc = carry
        q_i, do_i, lse_i, delta_i, qpos_i, bias_i = qb

        def kv_block(acc, kb):
            dq_i, dk_a, dv_a = acc
            k_j, v_j, kpos_j, idx, bias_j = kb
            s = jnp.einsum("bqkgd,bskd->bqkgs", q_i, k_j) * scale
            if bias_j is not None:
                s = s + bias_j.astype(jnp.float32)
            msk = _block_mask(qpos_i, kpos_j, causal=causal, window=window, kv_valid_len=None)
            s = jnp.where(msk[None, :, None, None, :], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])  # (B,bq,K,g,bkv)
            dp = jnp.einsum("bqkgd,bskd->bqkgs", do_i, v_j)
            if bias_j is None:
                dbias_j = None
                ds = p * (dp - delta_i[..., None]) * scale
            else:
                dbias_j = p * (dp - delta_i[..., None])
                ds = dbias_j * scale
                dbias_j = dbias_j.astype(bias_j.dtype)
            dq_i = dq_i + jnp.einsum("bqkgs,bskd->bqkgd", ds, k_j)
            dk_j = jnp.einsum("bqkgs,bqkgd->bskd", ds, q_i)
            dv_j = jnp.einsum("bqkgs,bqkgd->bskd", p, do_i)
            dk_a = lax.dynamic_update_index_in_dim(
                dk_a, lax.dynamic_index_in_dim(dk_a, idx, 0, keepdims=False) + dk_j, idx, 0
            )
            dv_a = lax.dynamic_update_index_in_dim(
                dv_a, lax.dynamic_index_in_dim(dv_a, idx, 0, keepdims=False) + dv_j, idx, 0
            )
            return (dq_i, dk_a, dv_a), dbias_j

        dq0 = jnp.zeros_like(q_i)
        (dq_i, dk_acc, dv_acc), dbias_i = lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), (ks, vs, kps, jnp.arange(nkv), bias_i)
        )
        return (dk_acc, dv_acc), (dq_i, dbias_i)

    qs = q.reshape(b, nq, block_q, kh, g, hd).swapaxes(0, 1)
    dos = do.reshape(b, nq, block_q, kh, g, hd).swapaxes(0, 1)
    lses = lse.reshape(b, nq, block_q, kh, g).swapaxes(0, 1)
    deltas = delta.reshape(b, nq, block_q, kh, g).swapaxes(0, 1)
    qps = qpos.reshape(nq, block_q)

    dk0 = jnp.zeros((nkv, b, block_kv, kh, hd), jnp.float32)
    dv0 = jnp.zeros((nkv, b, block_kv, kh, hd), jnp.float32)
    (dk_b, dv_b), (dqs, dbias) = lax.scan(
        q_block, (dk0, dv0), (qs, dos, lses, deltas, qps, _bias_blocks(bias, nq, nkv))
    )
    dq = dqs.swapaxes(0, 1).reshape(b, sq, kh, g, hd).astype(in_dtype)
    dk = dk_b.swapaxes(0, 1).reshape(b, skv, kh, hd).astype(in_dtype)
    dv = dv_b.swapaxes(0, 1).reshape(b, skv, kh, hd).astype(in_dtype)
    return dq, dk, dv, (None if dbias is None else _bias_unblocks(dbias))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, K, hd)
    v: jax.Array,  # (B, Skv, K, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,
    kv_valid_len: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    block_q: int = 512,
    block_kv: int = 512,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Public entry point. Returns (B, Sq, H, hd) in q.dtype.

    ``kv_valid_len`` (dynamic cache fill level) is handled on the
    non-differentiable path (serving); training uses static masks.
    ``bias``, broadcastable to (B, H, Sq, Skv), is added to the scaled
    scores; its gradient is the score cotangent, summed over the dims it
    was broadcast along.
    """
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = scale if scale is not None else hd**-0.5
    in_dtype = q.dtype

    bq = min(block_q, max(sq, 1))
    bkv = min(block_kv, max(k.shape[1], 1))

    qf = q.reshape(b, sq, kh, g, hd)
    kf = k
    vf = v
    if bias is not None:
        if kv_valid_len is not None or kv_positions is not None:
            raise ValueError("a score bias is supported on the training path only")
        skv = k.shape[1]
        bias = jnp.broadcast_to(bias, (b, h, sq, skv))
        bias = bias.reshape(b, kh, g, sq, skv).transpose(0, 3, 1, 2, 4)  # (B,Sq,K,g,Skv)
        bias, _ = _pad_to(bias, 1, bq)
        bias, _ = _pad_to(bias, 4, bkv)

    qf, sq0 = _pad_to(qf, 1, bq)
    kf, skv0 = _pad_to(kf, 1, bkv)
    vf, _ = _pad_to(vf, 1, bkv)
    if kv_positions is not None and kf.shape[1] != skv0:
        kv_positions = jnp.pad(kv_positions, (0, kf.shape[1] - skv0), constant_values=-1)
    # Padded kv positions must be masked out.
    if kf.shape[1] != skv0 and kv_valid_len is None and kv_positions is None:
        kv_valid_len = jnp.asarray(skv0)

    if kv_valid_len is None and kv_positions is None:
        out = _flash(qf, kf, vf, bias, causal, window, q_offset, bq, bkv, scale, True)
    else:
        # Serving path: dynamic valid length / ring positions, no grad needed
        # (padded training shapes differentiate through it by plain autodiff).
        out, _ = _flash_fwd_impl(
            qf.astype(jnp.float32), kf.astype(jnp.float32), vf.astype(jnp.float32),
            kv_valid_len, causal, window, q_offset, bq, bkv, scale,
            kv_positions=kv_positions, bias=bias,
        )
    out = out[:, :sq] if out.shape[1] != sq else out
    return out.reshape(b, sq, h, hd).astype(in_dtype)
