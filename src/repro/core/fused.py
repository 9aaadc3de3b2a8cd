"""Fused probes: per-sample norms (and book-keeping banks) computed INSIDE
the backward pass.

The tap mechanism (taps.py) exposes dL/ds as an explicit output — simple, but
the stacked cotangents of every layer then coexist in HBM ((L, B, T, p) per
tap: ~4 TB/device on qwen2-72b).  The paper's PyTorch hooks never have this
problem: the norm is computed layer-by-layer during backprop and the gradient
tensor dies immediately.

This module restores that lifetime structure in JAX.  Each parameterized op
routes its pre-activation through a ``custom_vjp`` identity *probe* carrying a
dummy *bank* input z.  The probe's backward rule computes the layer's
side-channel payload from its residual ``a`` and the incoming cotangent ``g``
— and returns it as z's cotangent::

    forward:   s -> s                      (identity; residual = a)
    backward:  ds = g
               da = 0                      (a's real grad flows via the matmul)
               dz = bank                   <- the hijacked side channel

For the second-backward mode (``mixed_ghost``) the bank is just
``{"n": (B,)}`` — the per-sample squared-norm contribution (ghost or
instantiated, per the Eq. 4.1 decision).  ``vjp(..., zs)`` then yields every
layer's norms as (B,)-sized cotangents — inside ``lax.scan`` they stack to
(L, B) — while g itself never leaves the backward scan.  Under the second
pullback (cotangent C_i) the bank computation is dead code and XLA
eliminates it.

For ``bk_mixed`` (book-keeping, arXiv:2210.00038) there is no second
pullback, so the bank must also carry the residuals the weighted-grad
einsum ``sum_i C_i g_i`` needs (see ghost.tap_bank): banked per-sample
gradients for instantiate-branch taps, the (a, g) book for ghost-branch
taps.  The dummy bank inputs are broadcast-zeros created inside the traced
function and deleted by the probe's forward rule — XLA never materializes
them; only the cotangents (the banks themselves, which the algorithm
fundamentally requires) occupy memory.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ghost as ghost_mod
from repro.core import taps as taps_mod
from repro.core.decision import decide


@dataclasses.dataclass(frozen=True)
class ProbeSpec:
    """Static description of the side-channel computation for one tap."""

    meta: "taps_mod.TapMeta"
    branch_mode: str  # clipping mode used by decide()
    override: Optional[str] = None  # tuner ClipPlan branch, wins over decide()
    # measured (op, impl) kernel choices for this tap (repro.kernels.dispatch)
    kernels: tuple[tuple[str, str], ...] = ()


def bank_struct(
    meta: "taps_mod.TapMeta",
    *,
    mode: str,
    override: Optional[str] = None,
) -> dict[str, jax.ShapeDtypeStruct]:
    """Shapes/dtypes of one tap's bank (stack dims follow ``meta``).

    Must mirror ghost.tap_bank exactly: the probes' backward rule emits the
    bank as the cotangent of a dummy input built from this structure, and
    custom_vjp requires the two to agree.
    """
    sd = meta.stack_dims
    b = meta.batch_size
    f32 = jnp.float32
    out = {"n": jax.ShapeDtypeStruct(sd + (b,), f32)}
    if mode != "bk_mixed":
        return out

    banks_book = False
    if meta.kind == "matmul":
        branch = decide(meta, mode="bk_mixed", override=override)
        if branch == "instantiate":
            out["psg"] = jax.ShapeDtypeStruct(
                sd + (b,) + ghost_mod.psg_param_shape(meta), f32
            )
        else:
            banks_book = True
    elif meta.kind == "embedding":
        banks_book = True
    elif meta.kind in ("dw_conv", "scale", "scale_grouped", "bias", "table"):
        out["psg"] = jax.ShapeDtypeStruct(
            sd + (b,) + ghost_mod.psg_param_shape(meta), f32
        )
    else:
        raise ValueError(f"unknown tap kind {meta.kind!r}")

    if banks_book:
        out["a"] = jax.ShapeDtypeStruct(tuple(meta.a_shape), meta.a_dtype)
        out["g"] = jax.ShapeDtypeStruct(tuple(meta.s_shape), meta.s_dtype)
    elif meta.bias_path is not None:
        out["psg_b"] = jax.ShapeDtypeStruct(sd + (b, meta.p), f32)
    return out


def make_bank_zeros(struct: dict[str, jax.ShapeDtypeStruct]) -> dict[str, jax.Array]:
    """Dummy bank primals: broadcast-zeros, unused in the forward pass."""
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in struct.items()}


def make_probe(spec: ProbeSpec):
    from repro.core import ghost  # local import to avoid cycles

    @jax.custom_vjp
    def probe(s, a, z):
        del a, z
        return s

    def fwd(s, a, z):
        del z
        return s, a

    def bwd(a, g):
        bank = ghost.tap_bank(
            spec.meta,
            a,
            g,
            mode=spec.branch_mode,
            override=spec.override,
            kernels=dict(spec.kernels) if spec.kernels else None,
        )
        da = jnp.zeros(a.shape, a.dtype) if a is not None else None
        return g, da, bank

    probe.defvjp(fwd, bwd)
    return probe
