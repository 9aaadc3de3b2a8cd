"""Pallas TPU kernel: fused per-sample gradient ghost norm (paper Eq. 2.7).

Computes, per sample n:

    out[n] = sum_{t,t'} (a_t . a_t') * (g_t . g_t')

without ever materializing the (T, T) Gram matrices in HBM.  This is the
paper's hot spot re-thought for the TPU memory hierarchy: on GPU the authors
lean on cuBLAS batched GEMMs producing full B x T x T Grams in HBM; on TPU we
tile the (T, T) plane into (bt, bt) blocks, build *both* Gram tiles in VMEM
scratch with MXU matmuls chunked over the feature dims, fuse their
elementwise product + reduction in registers, and emit a single scalar
accumulation per sample.  HBM traffic drops from O(T^2) per sample to
O(T*(D+p)) — inputs are read once per tile row; Gram tiles never leave VMEM.

Grid: (N, nb_i, nb_j, nc), nc = feature chunks (max over the a and g widths).
The (i, j) upper triangle is skipped; off-diagonal tiles are weighted 2x
(Gram symmetry) — half the MXU work of the naive double loop.

VMEM budget per step: 4 operand tiles (bt x bf) + 2 scratch Grams
(bt x bt f32); defaults (bt=256, bf=512) ~3.5 MiB.

``embedding_ghost_norm_sq_pallas`` is the index-equality variant: the
activation Gram is replaced by an equality mask built in registers from two
(bt,) id tiles, so only the cotangent Gram needs MXU work and the (T, T)
plane still never reaches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # per-sample sums leave the kernel as one lane-dense row each


def _pad(x, axis, mult):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def ghost_norm_sq_pallas(
    a: jax.Array,  # (N, T, D)
    g: jax.Array,  # (N, T, p)
    *,
    block_t: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-sample squared gradient norm: (N,) float32."""
    n, t, _ = a.shape
    a = _pad(_pad(a, 1, block_t), 2, block_f)
    g = _pad(_pad(g, 1, block_t), 2, block_f)
    nb = a.shape[1] // block_t
    ca = a.shape[2] // block_f
    cg = g.shape[2] // block_f
    nc = max(ca, cg)

    def row_i(ni, i, j, c):
        return (ni, i, jnp.minimum(c, ca - 1))

    def row_j(ni, i, j, c):
        return (ni, j, jnp.minimum(c, ca - 1))

    def grow_i(ni, i, j, c):
        return (ni, i, jnp.minimum(c, cg - 1))

    def grow_j(ni, i, j, c):
        return (ni, j, jnp.minimum(c, cg - 1))

    def kernel(ai_ref, aj_ref, gi_ref, gj_ref, o_ref, ga_acc, gg_acc):
        i = pl.program_id(1)
        j = pl.program_id(2)
        c = pl.program_id(3)
        live = j <= i  # upper triangle skipped (symmetry)

        @pl.when(jnp.logical_and(c == 0, live))
        def _init():
            ga_acc[...] = jnp.zeros_like(ga_acc)
            gg_acc[...] = jnp.zeros_like(gg_acc)

        @pl.when(jnp.logical_and(c < ca, live))
        def _acc_a():
            ga_acc[...] += jax.lax.dot_general(
                ai_ref[0], aj_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(jnp.logical_and(c < cg, live))
        def _acc_g():
            gg_acc[...] += jax.lax.dot_general(
                gi_ref[0], gj_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(jnp.logical_and(c == nc - 1, live))
        def _finalize():
            weight = jnp.where(i == j, 1.0, 2.0).astype(jnp.float32)
            contrib = weight * jnp.sum(ga_acc[...] * gg_acc[...])

            @pl.when(jnp.logical_and(i == 0, j == 0))
            def _first():
                o_ref[...] = jnp.full(o_ref.shape, contrib, jnp.float32)

            @pl.when(jnp.logical_or(i != 0, j != 0))
            def _rest():
                o_ref[...] += contrib

    return pl.pallas_call(
        kernel,
        grid=(n, nb, nb, nc),
        in_specs=[
            pl.BlockSpec((1, block_t, block_f), row_i),
            pl.BlockSpec((1, block_t, block_f), row_j),
            pl.BlockSpec((1, block_t, block_f), grow_i),
            pl.BlockSpec((1, block_t, block_f), grow_j),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda ni, i, j, c: (ni, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, _LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, block_t), jnp.float32),
            pltpu.VMEM((block_t, block_t), jnp.float32),
        ],
        interpret=interpret,
    )(a, a, g, g)[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def embedding_ghost_norm_sq_pallas(
    ids: jax.Array,  # (N, T) token ids (int, or fp32-cast ids < 2^24)
    g: jax.Array,  # (N, T, p)
    *,
    block_t: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Index-equality ghost norm: out[n] = sum_{t,t'} [id_t == id_t'] (g_t . g_t').

    Same (T, T)-tile structure as ``ghost_norm_sq_pallas`` with the
    activation Gram replaced by an equality mask computed in registers from
    the id tiles.  The two id operands are padded with *different* sentinels
    (-1 / -2), so pad positions never match anything — real ids, the other
    pad, or each other — and correctness does not ride on ``g``'s zero
    padding.
    """
    n, t = ids.shape
    from repro.kernels.ghost_norm.ops import pad_ids_pair

    ids_i, ids_j = pad_ids_pair(ids, block_t)
    g = _pad(_pad(g, 1, block_t), 2, block_f)
    nb = g.shape[1] // block_t
    nc = g.shape[2] // block_f

    def kernel(idi_ref, idj_ref, gi_ref, gj_ref, o_ref, gg_acc):
        i = pl.program_id(1)
        j = pl.program_id(2)
        c = pl.program_id(3)
        live = j <= i  # upper triangle skipped (symmetry)

        @pl.when(jnp.logical_and(c == 0, live))
        def _init():
            gg_acc[...] = jnp.zeros_like(gg_acc)

        @pl.when(live)
        def _acc_g():
            gg_acc[...] += jax.lax.dot_general(
                gi_ref[0].astype(jnp.float32), gj_ref[0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(jnp.logical_and(c == nc - 1, live))
        def _finalize():
            eq = (idi_ref[0, 0][:, None] == idj_ref[0]).astype(jnp.float32)
            weight = jnp.where(i == j, 1.0, 2.0).astype(jnp.float32)
            contrib = weight * jnp.sum(eq * gg_acc[...])

            @pl.when(jnp.logical_and(i == 0, j == 0))
            def _first():
                o_ref[...] = jnp.full(o_ref.shape, contrib, jnp.float32)

            @pl.when(jnp.logical_or(i != 0, j != 0))
            def _rest():
                o_ref[...] += contrib

    return pl.pallas_call(
        kernel,
        grid=(n, nb, nb, nc),
        in_specs=[
            pl.BlockSpec((1, 1, block_t), lambda ni, i, j, c: (ni, 0, i)),
            pl.BlockSpec((1, 1, block_t), lambda ni, i, j, c: (ni, 0, j)),
            pl.BlockSpec((1, block_t, block_f), lambda ni, i, j, c: (ni, i, c)),
            pl.BlockSpec((1, block_t, block_f), lambda ni, i, j, c: (ni, j, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda ni, i, j, c: (ni, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, _LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, block_t), jnp.float32),
        ],
        interpret=interpret,
    )(ids_i[:, None, :], ids_j[:, None, :], g, g)[:, 0, 0]
