"""Operations and bytes that the benchmark's algorithms need, from shapes.

These are the yardstick for `train_step.mfu` and the kernel rooflines.  They
count the work the mathematics requires, never what a particular kernel
happens to do: padding, recomputation and redundant passes are left out, so a
share computed from them cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

from typing import Iterable, Sequence

# -- VGG (3x3 convolutions, "SAME" padding, 2x2 max-pool at each "M") -------


def vgg_conv_macs(plan: Sequence, *, image: int, in_ch: int) -> list[int]:
    """Forward multiply-accumulates of each 3x3 conv of a VGG plan, per image."""
    macs, side, ch = [], image, in_ch
    for item in plan:
        if item == "M":
            side //= 2
            continue
        macs.append(side * side * ch * item * 9)
        ch = item
    return macs


def vgg_forward_macs(plan: Sequence, *, image: int, in_ch: int, n_classes: int) -> int:
    """Forward multiply-accumulates of one image: the convs plus the head."""
    convs = vgg_conv_macs(plan, image=image, in_ch=in_ch)
    last = [c for c in plan if c != "M"][-1]
    return sum(convs) + last * n_classes


def vgg_train_flops(plan: Sequence, *, image: int, in_ch: int, n_classes: int) -> float:
    """Model FLOPs of one training sample: forward, weight gradients, and
    input gradients of every layer but the first (the image needs none).
    Normalization, activations and pooling are not counted."""
    convs = vgg_conv_macs(plan, image=image, in_ch=in_ch)
    fwd = vgg_forward_macs(plan, image=image, in_ch=in_ch, n_classes=n_classes)
    return 2.0 * (fwd + fwd + (fwd - convs[0]))


# -- xLSTM (mLSTM / sLSTM blocks as repro.nn.xlstm states them) -------------


def slstm_ffn_width(d_model: int, factor: float = 4.0 / 3.0) -> int:
    return max(64, int(round(factor * d_model / 64) * 64))


def xlstm_matmul_params(
    *, d_model: int, n_heads: int, vocab: int, pattern: Iterable[str], expand: int = 2,
) -> int:
    """Weights that multiply an activation once per token (the embedding
    table is a lookup and is left out; the LM head is counted)."""
    d = d_model
    di = expand * d
    d_ff = slstm_ffn_width(d)
    total = d * vocab  # lm_head
    for kind in pattern:
        if kind == "mlstm":
            # in_x, in_z, q, k, gates, out_proj
            total += d * di * 2 + di * di * 2 + di * 2 * n_heads + di * d
        elif kind == "slstm":
            # wx, recurrent wr, gated FFN (wg, wu, wo)
            total += d * 4 * d * 2 + d * d_ff * 3
        else:
            raise ValueError(f"unknown xLSTM block kind {kind!r}")
    return total


def xlstm_cell_macs_per_token(
    *, d_model: int, n_heads: int, pattern: Iterable[str], conv_k: int = 4, expand: int = 2,
) -> int:
    """Multiply-accumulates of the cells per token, in their recurrent form:
    the mLSTM matrix memory S (dk x (dv+1) per head) is updated by k v^T and
    read by q; the causal depthwise convs take conv_k per channel.  The
    decay of S and the sLSTM gates are elementwise and not counted."""
    d = d_model
    di = expand * d
    dh = di // n_heads
    total = 0
    for kind in pattern:
        if kind == "mlstm":
            total += n_heads * 2 * dh * (dh + 1) + conv_k * di
        else:
            total += conv_k * d
    return total


def xlstm_train_flops_per_token(
    *, d_model: int, n_heads: int, vocab: int, pattern: Sequence[str],
) -> float:
    """Model FLOPs per token of training: 2 per MAC, forward plus a
    backward of twice the forward."""
    macs = xlstm_matmul_params(
        d_model=d_model, n_heads=n_heads, vocab=vocab, pattern=pattern
    ) + xlstm_cell_macs_per_token(d_model=d_model, n_heads=n_heads, pattern=pattern)
    return 6.0 * macs


# -- ghost norm ---------------------------------------------------------------


def ghost_norm_work(
    n: int, t: int, d: int, p: int, a_itemsize: int, g_itemsize: int,
) -> tuple[float, float]:
    """(FLOPs, bytes) that the ghost norm of a matmul tap needs.

    Per sample, ||a^T g||_F^2 = <a a^T, g g^T>: the two T x T Gram matrices
    are symmetric, so only T(T+1)/2 entries of each are needed, at D and p
    multiply-adds each, and the elementwise product and sum over them.  The
    inputs (N, T, D) and (N, T, p), in the model's types, are read once and
    (N,) float32 written.
    """
    pairs = t * (t + 1) / 2
    flops = n * (2.0 * pairs * (d + p) + 2.0 * pairs)
    bytes_ = n * t * (d * a_itemsize + p * g_itemsize) + 4.0 * n
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, bytes_ / peak_bw)
