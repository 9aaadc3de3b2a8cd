"""Per-tap per-sample gradient norms, book-keeping banks, weighted gradients.

Given a tap's recorded activation ``a``, its cotangent ``g = dL/ds`` from the
first backward pass, and the static ``TapMeta``, this module computes the
per-sample squared gradient norm on the branch the layerwise decision picked
(Alg. 1), and — for the book-keeping mode — the weighted gradient
``sum_i C_i g_i`` directly as an einsum, skipping the second backward pass.

Each tap's norm work (``tap_norm_sq``, ``tap_bank``) runs under the named
scopes ``dp.tap_norm/<tap>`` (the weight's path, dots for slashes), so a
device trace can tell the per-tap norm ops, pads and kernel calls apart.

Three call sites:
- ``tap_norm_sq``        per-sample norm^2 from explicit (a, g) pairs; used
                         by the fused probes of ``mixed_ghost`` and by late
                         taps, whose activation only exists post-scan.
- ``tap_bank``           runs INSIDE the fused probe's backward rule: returns
                         the side-channel payload for one tap — always the
                         per-sample norm^2 ``n``, plus (book-keeping mode) the
                         residuals the weighted-grad stage needs (banked
                         per-sample gradients ``psg``/``psg_b``, or the
                         ``(a, g)`` book for ghost-banked taps).
- ``bank_weighted_grads``  the fused gradient stage: ``sum_i C_i g_i`` from a
                         tap's bank once the clip factors are known.
- ``tap_weighted_grads``   same, from explicit (a, g) (a ghost-banked book,
                         or a late tap).

Canonical layouts (stack dims folded into the row dim N):
- matmul:     a (N, T, D), g (N, T, p); N = prod(stack) * B * G
- embedding:  ids (N, T),  g (N, T, p)
- scale:      a, g (N, T, p)          grad = sum_T g*a
- bias:       g (N, T, p)             grad = sum_T g
- dw_conv:    a (N, T, k, d), g (N, T, d)
- scale_grouped: a, g (N, T, h*dh), param (h,)
- table:      g (N, p, T), no a; param (R, p) = (D, p)
"""
from __future__ import annotations

import contextlib
import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.decision import decide
from repro.core.taps import TapMeta
from repro.kernels import dispatch
from repro.kernels.ghost_norm import ops as gops
from repro.nn.conv import unfold2d

# Largest integer float32 represents exactly: the fused engine sends
# embedding ids through the bank side channel as fp32 (cotangent pytrees
# are float), so vocabs at/above this silently corrupt high token ids.
MAX_EXACT_FP32_ID = 1 << 24

# ``kernels`` arguments below: an optional per-tap {op: impl} map from a
# tuner ClipPlan ("pallas" | "xla" per dispatch op); None defers to
# repro.kernels.dispatch's backend default (pallas on TPU, xla elsewhere).
KernelChoices = Optional[Mapping[str, str]]

TAP_NORM_SCOPE = "dp.tap_norm"

# Tile sizes of the XLA norm branches: the ghost Gram's position block and
# the instantiated gradient's fan-in block (the tuner times the same ones).
GHOST_BLOCK = 512
INST_BLOCK_D = 8192


@contextlib.contextmanager
def _tap_norm_scope(meta: TapMeta):
    """Named scopes ``dp.tap_norm/<tap>`` around one tap's norm work."""
    with jax.named_scope(TAP_NORM_SCOPE), jax.named_scope(meta.param_path.replace("/", ".")):
        yield


def _check_embedding_vocab(meta: TapMeta, where: str) -> None:
    """Trace-time guard: oversized vocabs must not cross the fp32 channel.

    ``meta.D`` is the vocab size for embedding taps (nn.module.Embedding
    registers D=vocab).  Raising at trace time — before any id is cast —
    beats silently training on corrupted indices >= 2^24.
    """
    if meta.D >= MAX_EXACT_FP32_ID:
        raise ValueError(
            f"embedding tap {meta.param_path!r} has vocab size {meta.D} >= "
            f"2^24 ({MAX_EXACT_FP32_ID}): {where} carries token ids as "
            "float32, which cannot represent ids that large exactly, so "
            "high vocab indices would be silently corrupted. Shard the "
            "embedding below 2^24 rows per tap."
        )


def _fold(meta: TapMeta, x: jax.Array, trailing: tuple[int, ...]) -> jax.Array:
    """Reshape (stack..., B, <middle>) -> (L, B*G?, ...) canonical row-major."""
    lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
    return x.reshape((lead, meta.batch_size) + trailing)


def _per_sample(meta: TapMeta, row_vals: jax.Array) -> jax.Array:
    """(L*B*G,) row norms -> (B,) per-sample sums (over stack and groups)."""
    lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
    v = row_vals.reshape(lead, meta.batch_size, max(meta.n_groups, 1))
    return jnp.sum(v, axis=(0, 2))


def _canonical_ag(meta: TapMeta, a: jax.Array, g: jax.Array):
    """Return a (N, T, D), g (N, T, p) with N = L*B*G."""
    lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
    gg = g.reshape(lead * meta.batch_size * max(meta.n_groups, 1), meta.T, meta.p)
    if meta.conv is not None:
        # a is raw (lead*B, H, W, d): unfold lazily to (N, T, D)
        a4 = a.reshape((lead * meta.batch_size,) + a.shape[-3:])
        aa = unfold2d(a4, meta.conv)
    else:
        aa = a.reshape(lead * meta.batch_size * max(meta.n_groups, 1), meta.T, meta.D)
    return aa, gg


def table_segment_sum(meta: TapMeta, g: jax.Array) -> jax.Array:
    """A ``table`` tap's cotangent summed into the table's rows:
    g (N, p, T) -> (N, R, p), row r gathering every position k with
    index[k] = r, as one matmul against the index's (T, R) one-hot."""
    index = jnp.asarray(np.asarray(meta.gather.index, np.int32))
    onehot = (index[:, None] == jnp.arange(meta.D, dtype=jnp.int32)[None, :]).astype(g.dtype)
    return jnp.einsum("nct,tr->nrc", g, onehot, preferred_element_type=jnp.float32)


def tap_norm_sq(
    meta: TapMeta,
    a: Optional[jax.Array],
    g: jax.Array,
    *,
    mode: str = "mixed_ghost",
    override: Optional[str] = None,
    kernels: KernelChoices = None,
) -> jax.Array:
    """Per-sample squared norm contributions: (B,) fp32 (weight + bias).

    ``override`` forces the matmul branch (tuner ClipPlan); both branches
    compute the same norm, so it changes cost only, never the result.
    ``kernels`` picks the Pallas-vs-XLA impl per dispatch op (also
    cost-only).
    """
    with _tap_norm_scope(meta):
        return _tap_norm_sq(
            meta, a, g, branch=decide(meta, mode=mode, override=override),
            include_bias=True, kernels=kernels,
        )


def _tap_norm_sq(
    meta: TapMeta, a: Optional[jax.Array], g: jax.Array, *, branch: str,
    include_bias: bool, kernels: KernelChoices,
) -> jax.Array:
    """``tap_norm_sq`` on a decided ``branch`` (read by matmul taps only).

    ``include_bias=False`` skips the bias term (book-keeping banks it
    separately as ``psg_b`` and adds its norm from the bank)."""
    g = g.astype(jnp.float32)
    total = jnp.zeros((meta.batch_size,), jnp.float32)

    if meta.kind == "matmul":
        aa, gg = _canonical_ag(meta, a, g)
        if branch == "ghost":
            rows = dispatch.ghost_norm_sq(
                aa, gg, block=GHOST_BLOCK,
                impl=dispatch.kernels_arg(kernels, "ghost_norm"),
            )
        else:
            rows = gops.instantiated_norm_sq(aa, gg, block_d=INST_BLOCK_D)
        total = total + _per_sample(meta, rows)
    elif meta.kind == "embedding":
        if jnp.issubdtype(a.dtype, jnp.floating):
            # fused engine: ids arrived through the fp32 side channel
            _check_embedding_vocab(meta, "the per-sample norm stage")
        lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
        ids = a.reshape(lead * meta.batch_size, meta.T)
        gg = g.reshape(lead * meta.batch_size, meta.T, meta.p)
        rows = dispatch.embedding_ghost_norm_sq(
            ids, gg, impl=dispatch.kernels_arg(kernels, "embedding_ghost_norm")
        )
        total = total + _per_sample(meta, rows)
    elif meta.kind == "scale":
        af = _fold(meta, a.astype(jnp.float32), (meta.T, meta.p))
        gf = _fold(meta, g, (meta.T, meta.p))
        grad = jnp.sum(gf * af, axis=-2)  # (L, B, p)
        total = total + jnp.sum(grad * grad, axis=(0, 2))
    elif meta.kind == "table":
        lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
        grad = table_segment_sum(meta, g.reshape(lead * meta.batch_size, meta.p, meta.T))
        total = total + _per_sample(meta, jnp.sum(grad * grad, axis=(1, 2)))
    elif meta.kind == "bias":
        gf = _fold(meta, g, (meta.T, meta.p))
        grad = jnp.sum(gf, axis=-2)
        total = total + jnp.sum(grad * grad, axis=(0, 2))
    elif meta.kind == "scale_grouped":
        h, dh = meta.p, meta.D
        af = _fold(meta, a.astype(jnp.float32), (meta.T, h, dh))
        gf = _fold(meta, g, (meta.T, h, dh))
        grad = jnp.einsum("lbthd,lbthd->lbh", gf, af)
        total = total + jnp.sum(grad * grad, axis=(0, 2))
    elif meta.kind == "dw_conv":
        k = meta.D
        af = _fold(meta, a.astype(jnp.float32), (meta.T, k, meta.p))
        gf = _fold(meta, g, (meta.T, meta.p))
        grad = jnp.einsum("lbtkd,lbtd->lbkd", af, gf)
        total = total + jnp.sum(grad * grad, axis=(0, 2, 3))
    else:
        raise ValueError(f"unknown tap kind {meta.kind!r}")

    if meta.bias_path is not None and include_bias:
        lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
        gf = g.reshape(lead, meta.batch_size, -1, meta.p)  # (L, B, G*T, p)
        bias_grad = jnp.sum(gf, axis=2)  # (L, B, p)
        total = total + jnp.sum(bias_grad * bias_grad, axis=(0, 2))
    return total


def psg_param_shape(meta: TapMeta) -> tuple[int, ...]:
    """Per-layer shape of one sample's banked gradient = the param's layout.

    matmul (D, p) / grouped (G, D, p) / conv kernel+(d, p) | scale (p,) |
    scale_grouped (h,) | dw_conv (k, d) | bias (p,).
    """
    if meta.kind == "matmul":
        if meta.conv is not None:
            d_in = meta.D // math.prod(meta.conv.kernel)
            return tuple(meta.conv.kernel) + (d_in, meta.p)
        if meta.n_groups > 1:
            return (meta.n_groups, meta.D, meta.p)
        return (meta.D, meta.p)
    if meta.kind in ("dw_conv", "table"):
        return (meta.D, meta.p)
    if meta.kind in ("scale", "scale_grouped", "bias"):
        return (meta.p,)
    raise ValueError(f"no banked per-sample gradient for tap kind {meta.kind!r}")


def _matmul_psg(meta: TapMeta, a: jax.Array, g: jax.Array) -> jax.Array:
    """Per-layer per-sample weight gradients (B,) + psg_param_shape(meta).

    Convolutions go through a vmapped vjp of the conv op itself — the
    per-sample dW lowers to a conv kernel and the (B, T, D) im2col patches
    are never materialized (the explicit unfold is the single largest temp
    of the instantiate branch on CNNs).
    """
    b = meta.batch_size
    g32 = g.astype(jnp.float32)
    if meta.conv is not None:
        info = meta.conv
        a4 = a.reshape((b,) + tuple(a.shape[-3:])).astype(jnp.float32)
        go = g32.reshape((b,) + tuple(meta.s_shape[-3:]))
        w0 = jnp.zeros(psg_param_shape(meta), jnp.float32)

        def one(ab, gb):
            _, pullb = jax.vjp(
                lambda w: jax.lax.conv_general_dilated(
                    ab[None], w, info.strides, info.padding,
                    rhs_dilation=info.rhs_dilation,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=info.feature_group_count,
                ),
                w0,
            )
            (dw,) = pullb(gb[None])
            return dw

        return jax.vmap(one)(a4, go)
    gdim = max(meta.n_groups, 1)
    aa = a.astype(jnp.float32).reshape(b * gdim, meta.T, meta.D)
    gg = g32.reshape(b * gdim, meta.T, meta.p)
    psg = jnp.einsum("ntd,ntp->ndp", aa, gg)
    return psg.reshape((b,) + psg_param_shape(meta))


def _small_psg(meta: TapMeta, a: jax.Array, g: jax.Array) -> jax.Array:
    """Per-layer per-sample gradients for the tiny forced-instantiate kinds.

    Shapes (B = batch, per layer instance, no stack dims):
    scale (B, p) | scale_grouped (B, h) | dw_conv (B, k, d) | bias (B, p) |
    table (B, R, p).
    """
    b = meta.batch_size
    if meta.kind == "scale":
        af = a.astype(jnp.float32).reshape(b, meta.T, meta.p)
        gf = g.reshape(b, meta.T, meta.p)
        return jnp.sum(gf * af, axis=1)
    if meta.kind == "scale_grouped":
        h, dh = meta.p, meta.D
        af = a.astype(jnp.float32).reshape(b, meta.T, h, dh)
        gf = g.reshape(b, meta.T, h, dh)
        return jnp.einsum("bthd,bthd->bh", gf, af)
    if meta.kind == "dw_conv":
        k = meta.D
        af = a.astype(jnp.float32).reshape(b, meta.T, k, meta.p)
        gf = g.reshape(b, meta.T, meta.p)
        return jnp.einsum("btkd,btd->bkd", af, gf)
    if meta.kind == "bias":
        return jnp.sum(g.reshape(b, meta.T, meta.p), axis=1)
    if meta.kind == "table":
        return table_segment_sum(meta, g.reshape(b, meta.p, meta.T))
    raise ValueError(f"no small per-sample gradient for tap kind {meta.kind!r}")


def tap_bank(
    meta: TapMeta,
    a: Optional[jax.Array],
    g: jax.Array,
    *,
    mode: str = "mixed_ghost",
    override: Optional[str] = None,
    kernels: KernelChoices = None,
) -> dict[str, jax.Array]:
    """The fused probe's backward payload for one tap (per layer instance).

    Every bank carries ``n`` — the tap's total per-sample squared norm (B,).
    Outside book-keeping mode that is the whole bank (today's side channel).
    In ``bk_mixed`` the bank additionally carries what the weighted-grad
    stage needs once the clip factors exist:

    - forced-instantiate kinds and instantiate-branch matmuls: the per-sample
      gradients ``psg`` (+ ``psg_b`` for the bias) — the norm falls out of
      them for free, and nothing activation- or cotangent-sized survives;
    - ghost-branch matmuls and embeddings: the ``(a, g)`` book (smaller than
      pD per sample exactly when the branch rule banked it), from which both
      the ghost norm (here) and the weighted einsum (later) are formed.
    """
    if mode != "bk_mixed":  # tap_norm_sq scopes its own work
        return {"n": tap_norm_sq(meta, a, g, mode=mode, override=override, kernels=kernels)}

    with _tap_norm_scope(meta):
        b = meta.batch_size
        g32 = g.astype(jnp.float32)
        bank: dict[str, jax.Array] = {}
        n = jnp.zeros((b,), jnp.float32)

        if meta.kind == "matmul":
            branch = decide(meta, mode="bk_mixed", override=override)
            if branch == "instantiate":
                psg = _matmul_psg(meta, a, g32)
                bank["psg"] = psg
                n = n + jnp.sum(jnp.square(psg).reshape(b, -1), axis=-1)
            else:
                bank["a"], bank["g"] = a, g
                n = n + _tap_norm_sq(
                    meta, a, g, branch="ghost", include_bias=False, kernels=kernels
                )
        elif meta.kind == "embedding":
            # a is the fp32-cast ids (taps.Ctx casts before probing): exact for
            # vocab indices below 2^24 — guarded at trace time, since anything
            # larger would silently corrupt high token ids in the bank
            _check_embedding_vocab(meta, "the book-keeping bank")
            bank["a"], bank["g"] = a, g
            n = n + _tap_norm_sq(
                meta, a, g, branch="ghost", include_bias=False, kernels=kernels
            )
        else:
            psg = _small_psg(meta, a, g32)
            bank["psg"] = psg
            n = n + jnp.sum(jnp.square(psg).reshape(b, -1), axis=-1)

        if meta.bias_path is not None:
            if "g" in bank:
                # the book already reconstructs the bias grad; only the norm term
                # is still owed (tap_norm_sq above ran with include_bias=False)
                gf = g32.reshape(b, -1, meta.p)
                bias_grad = jnp.sum(gf, axis=1)
                n = n + jnp.sum(bias_grad * bias_grad, axis=-1)
            else:
                psg_b = jnp.sum(g32.reshape(b, -1, meta.p), axis=1)
                bank["psg_b"] = psg_b
                n = n + jnp.sum(psg_b * psg_b, axis=-1)
        bank["n"] = n
        return bank


def tap_weighted_grads(
    meta: TapMeta,
    a: Optional[jax.Array],
    g: jax.Array,
    clip: jax.Array,  # (B,) clip factors C_i
    param_shape: tuple[int, ...],
    kernels: KernelChoices = None,
) -> dict[str, jax.Array]:
    """BK mode: weighted gradients sum_i C_i g_i, contracted directly.

    Matmul taps run the fused clip-and-contract stage through
    ``dispatch.book_weighted_grad`` (the Pallas kernel on TPU scales
    cotangent tiles in VMEM, so the ``C_i * g_i`` temporary never reaches
    HBM; the XLA path is a single three-operand einsum).  Returns
    {param_path: grad, [bias_path: grad]} shaped like the params.
    """
    out: dict[str, jax.Array] = {}
    lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
    gdim = max(meta.n_groups, 1)
    b = meta.batch_size
    cw = clip.astype(jnp.float32)

    if meta.kind in ("embedding", "scale", "bias"):
        gw = g.astype(jnp.float32).reshape(lead, b, gdim, meta.T, meta.p)
        gw = gw * cw[None, :, None, None, None]

    if meta.kind == "matmul":
        if a is None:
            raise ValueError(f"matmul tap {meta.param_path} has no recorded activation")
        if meta.conv is not None:
            a4 = a.reshape((lead * b,) + a.shape[-3:])
            aa = unfold2d(a4, meta.conv).reshape(lead, b, gdim, meta.T, meta.D)
        else:
            aa = a.reshape(lead, b, gdim, meta.T, meta.D)
        gg = g.reshape(lead, b, gdim, meta.T, meta.p)
        # canonical (M, R, .) book: rows = (B, T) folded, one row weight per
        # (sample, position); layer/group instances ride the leading dim
        a2 = aa.transpose(0, 2, 1, 3, 4).reshape(lead * gdim, b * meta.T, meta.D)
        g2 = gg.transpose(0, 2, 1, 3, 4).reshape(lead * gdim, b * meta.T, meta.p)
        w2 = jnp.broadcast_to(
            jnp.broadcast_to(cw[:, None], (b, meta.T)).reshape(1, b * meta.T),
            (lead * gdim, b * meta.T),
        )
        w = dispatch.book_weighted_grad(
            a2, g2, w2, impl=dispatch.kernels_arg(kernels, "psg_contract")
        ).reshape(lead, gdim, meta.D, meta.p)
        # a conv's unfolded fan-in is offset-major, its weight's own order
        out[meta.param_path] = w.reshape(param_shape)
    elif meta.kind == "embedding":
        ids = a.reshape(-1)
        flat_g = gw.reshape(-1, meta.p)
        w = jnp.zeros(param_shape, jnp.float32).at[ids].add(flat_g)
        out[meta.param_path] = w
    elif meta.kind == "scale":
        af = a.astype(jnp.float32).reshape(lead, meta.batch_size, gdim, meta.T, meta.p)
        out[meta.param_path] = jnp.einsum("lbgtp,lbgtp->lp", af, gw).reshape(param_shape)
    elif meta.kind == "bias":
        out[meta.param_path] = jnp.einsum("lbgtp->lp", gw).reshape(param_shape)
    elif meta.kind == "table":
        gc = g.astype(jnp.float32).reshape(lead, b, meta.p, meta.T) * cw[None, :, None, None]
        out[meta.param_path] = table_segment_sum(meta, jnp.sum(gc, axis=1)).reshape(param_shape)
    elif meta.kind == "scale_grouped":
        h, dh = meta.p, meta.D
        af = a.astype(jnp.float32).reshape(lead, meta.batch_size, meta.T, h, dh)
        gg = g.astype(jnp.float32).reshape(lead, meta.batch_size, meta.T, h, dh)
        gg = gg * cw[None, :, None, None, None]
        out[meta.param_path] = jnp.einsum("lbthd,lbthd->lh", af, gg).reshape(param_shape)
    elif meta.kind == "dw_conv":
        k = meta.D
        af = a.astype(jnp.float32).reshape(lead, meta.batch_size, meta.T, k, meta.p)
        gg = g.astype(jnp.float32).reshape(lead, meta.batch_size, meta.T, meta.p)
        gg = gg * cw[None, :, None, None]
        out[meta.param_path] = jnp.einsum("lbtkd,lbtd->lkd", af, gg).reshape(param_shape)
    else:
        raise ValueError(f"unknown tap kind {meta.kind!r}")

    if meta.bias_path is not None:
        gb = g.astype(jnp.float32).reshape(lead, meta.batch_size, -1, meta.p)
        gb = gb * cw[None, :, None, None]
        out[meta.bias_path] = jnp.einsum("lbtp->lp", gb).reshape(
            meta.stack_dims + (meta.p,) if meta.stack_dims else (meta.p,)
        )
    return out


def bank_weighted_grads(
    meta: TapMeta,
    bank: dict[str, jax.Array],
    clip: jax.Array,  # (B,) clip factors C_i
    param_shape: tuple[int, ...],
    kernels: KernelChoices = None,
) -> dict[str, jax.Array]:
    """Fused book-keeping gradient stage: sum_i C_i g_i from a probe bank.

    ``bank`` arrives with stack dims prepended by the scan (the probes emit
    per-layer payloads; ``lax.scan`` stacks them).  Ghost-banked taps replay
    the weighted book contraction from the banked (a, g) pair; psg-banked
    taps contract the banked per-sample gradients with the clip factors
    directly — both through ``repro.kernels.dispatch``.
    """
    if "g" in bank:
        a = bank["a"]
        if meta.kind == "embedding":
            # ids crossed the side channel as fp32 (see tap_bank); exactness
            # of the round-trip is guarded at trace time
            _check_embedding_vocab(meta, "the banked-id round-trip")
            a = jnp.round(a).astype(jnp.int32)
        return tap_weighted_grads(
            meta, a, bank["g"], clip, param_shape, kernels=kernels
        )

    out: dict[str, jax.Array] = {}
    lead = math.prod(meta.stack_dims) if meta.stack_dims else 1
    b = meta.batch_size
    cw = clip.astype(jnp.float32)
    impl = dispatch.kernels_arg(kernels, "psg_contract")
    # banked per-sample grads are already in the param's own layout:
    # (L..., B, *param) -> contract the batch dim against the clip factors
    psg = bank["psg"].reshape((lead, b) + psg_param_shape(meta))
    w = dispatch.psg_contract(psg, cw, axis=1, impl=impl)
    out[meta.param_path] = w.reshape(param_shape)

    if "psg_b" in bank:
        psg_b = bank["psg_b"].reshape(lead, b, meta.p)
        out[meta.bias_path] = dispatch.psg_contract(
            psg_b, cw, axis=1, impl=impl
        ).reshape(
            meta.stack_dims + (meta.p,) if meta.stack_dims else (meta.p,)
        )
    return out
