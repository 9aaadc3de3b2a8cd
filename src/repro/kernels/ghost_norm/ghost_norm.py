"""Pallas TPU kernel: fused per-sample gradient ghost norm (paper Eq. 2.7).

Computes, per sample n:

    out[n] = sum_{t,t'} (a_t . a_t') * (g_t . g_t')

without ever materializing the (T, T) Gram matrices in HBM.  This is the
paper's hot spot re-thought for the TPU memory hierarchy: on GPU the authors
lean on cuBLAS batched GEMMs producing full B x T x T Grams in HBM; on TPU we
build the Gram tiles in VMEM scratch with MXU matmuls chunked over the
feature dims, fuse their elementwise product + reduction in registers, and
emit a single scalar accumulation per sample.  HBM traffic drops from
O(T^2) per sample to O(T*(D+p)); Gram tiles never leave VMEM.

``ghost_tiling`` picks one of two tilings from the static shapes:

* tiled (T >= block_t): grid (N, nb_i, nb_j, nc), nc = feature chunks (max
  over the a and g widths); T is padded to block_t and the features to
  block_f in HBM.  The (i, j) upper triangle is skipped; off-diagonal tiles
  are weighted 2x (Gram symmetry) — half the MXU work of the naive double
  loop.  VMEM per step: 4 operand tiles (bt x bf) + 2 scratch Grams
  (bt x bt f32); defaults (bt=256, bf=512) ~3.5 MiB.
* packed (T < block_t): grid (ceil(N / bn), nc), bn = block_t // T whole
  samples per step, read as bn*T rows of the (N*T, D) view (a (1, T, bf)
  block of (N, T, D) where bn = 1 and T is off the sublane tiling).  T is
  never padded; a feature chunk is a multiple of 128 lanes that divides the
  width, or the whole width, so only a width above block_f that is no lane
  multiple is padded, to the next one.  One (bn*T)^2 Gram pair per step,
  masked to its per-sample diagonal blocks; a ragged last step's rows past
  N are cut off by the same mask and its sums past N are dropped.  VMEM per
  step: 2 operand blocks (bn*T x bf, double-buffered) + 2 scratch Grams;
  at most ~3 MiB with the defaults.  Its pallas call sits under
  ``ghost_norm_sq_pallas_packed``, so a trace names its calls apart.

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


def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128) VMEM tile of ``dtype``: 8 f32, 16 bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _lane_chunk(width: int, block_f: int) -> int:
    """Feature chunk of the packed path: the whole width if it fits in
    ``block_f`` (or one lane tile), else the widest multiple of 128 lanes
    up to ``block_f`` that divides the width rounded up to 128 lanes."""
    cap = max(block_f, _LANES)
    if width <= cap:
        return width
    padded = width + (-width) % _LANES
    return max(c for c in range(_LANES, cap + 1, _LANES) if padded % c == 0)


def ghost_tiling(
    n: int, t: int, d: int, p: int, *, block_t: int = 256, block_f: int = 512,
    sublanes: int = 8,
) -> tuple[str, int, int, int]:
    """How ``ghost_norm_sq_pallas`` tiles an (n, t, d) x (n, t, p) tap.

    Returns ``(path, bn, block_f_a, block_f_g)``: ``"tiled"`` (one sample
    per grid step, (block_t x block_t) Gram tiles) when ``t >= block_t``,
    else ``"packed"`` with ``bn`` whole samples per grid step.  ``bn*t``
    rows must fill whole ``sublanes``-row tiles unless they are all ``n``
    samples; where no bn > 1 does, bn is 1 and a step reads one sample's
    (t, chunk) block.  ``block_f_a`` / ``block_f_g`` are the feature chunks.
    """
    if t >= block_t:
        return "tiled", 1, block_f, block_f
    bn = min(max(1, block_t // t), n)
    if bn < n:
        while bn > 1 and (bn * t) % sublanes:
            bn -= 1
    return "packed", bn, _lane_chunk(d, block_f), _lane_chunk(p, block_f)


@functools.partial(
    jax.jit, static_argnames=("bn", "block_f_a", "block_f_g", "interpret")
)
def ghost_norm_sq_pallas_packed(
    a: jax.Array,  # (N, T, D), T < block_t
    g: jax.Array,  # (N, T, p)
    *,
    bn: int,
    block_f_a: int,
    block_f_g: int,
    interpret: bool = False,
) -> jax.Array:
    """The packed path of ``ghost_norm_sq_pallas``: (N,) float32."""
    n, t, _ = a.shape
    a = _pad(a, 2, block_f_a)
    g = _pad(g, 2, block_f_g)
    ca = a.shape[2] // block_f_a
    cg = g.shape[2] // block_f_g
    nc = max(ca, cg)
    nr = pl.cdiv(n, bn)
    rows = bn * t
    flat = bn > 1 or t % _sublanes(a.dtype) == 0 and t % _sublanes(g.dtype) == 0
    if flat:  # bn samples = bn*t consecutive rows of the (N*T, width) view
        a = a.reshape(n * t, a.shape[2])
        g = g.reshape(n * t, g.shape[2])
        a_spec = pl.BlockSpec((rows, block_f_a), lambda r, c: (r, jnp.minimum(c, ca - 1)))
        g_spec = pl.BlockSpec((rows, block_f_g), lambda r, c: (r, jnp.minimum(c, cg - 1)))
    else:  # one sample whose T is off the sublane tiling: a (1, T, chunk) block
        a_spec = pl.BlockSpec((1, t, block_f_a), lambda r, c: (r, 0, jnp.minimum(c, ca - 1)))
        g_spec = pl.BlockSpec((1, t, block_f_g), lambda r, c: (r, 0, jnp.minimum(c, cg - 1)))

    def gram(ref):
        x = ref[...] if flat else ref[0]
        return jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    def sample_of(idx):  # idx // t, exact for idx < 2**22
        return jnp.floor((idx.astype(jnp.float32) + 0.5) * (1.0 / t))

    def kernel(a_ref, g_ref, o_ref, ga_acc, gg_acc):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _init():
            ga_acc[...] = jnp.zeros_like(ga_acc)
            gg_acc[...] = jnp.zeros_like(gg_acc)

        @pl.when(c < ca)
        def _acc_a():
            ga_acc[...] += gram(a_ref)

        @pl.when(c < cg)
        def _acc_g():
            gg_acc[...] += gram(g_ref)

        @pl.when(c == nc - 1)
        def _finalize():
            prod = ga_acc[...] * gg_acc[...]
            if bn > 1:  # keep each sample's own (t, t) block
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
                prod = jnp.where(sample_of(row) == sample_of(col), prod, 0.0)
            # the masked product is symmetric: column sums are row sums
            col_sums = jnp.sum(prod, axis=0, keepdims=True)  # (1, rows)
            sample = jax.lax.broadcasted_iota(jnp.int32, (bn, rows), 0)
            pos = jax.lax.broadcasted_iota(jnp.int32, (bn, rows), 1)
            mine = sample_of(pos) == sample.astype(jnp.float32)
            sums = jnp.sum(jnp.where(mine, col_sums, 0.0), axis=1, keepdims=True)
            o_ref[0] = jnp.broadcast_to(sums, (bn, _LANES))

    out = pl.pallas_call(
        kernel,
        grid=(nr, nc),
        in_specs=[a_spec, g_spec],
        out_specs=pl.BlockSpec((1, bn, _LANES), lambda r, c: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, bn, _LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rows, rows), jnp.float32),
            pltpu.VMEM((rows, rows), jnp.float32),
        ],
        interpret=interpret,
    )(a, g)
    return out.reshape(nr * bn, _LANES)[:n, 0]  # drops a ragged last step's sums past N


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def ghost_norm_sq_pallas(
    a: jax.Array,  # (N, T, D)
    g: jax.Array,  # (N, T, p)
    *,
    block_t: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-sample squared gradient norm: (N,) float32.

    The tiling follows the static shapes (``ghost_tiling``): taps shorter
    than ``block_t`` take the packed path, the rest the tiled one.
    """
    n, t, d = a.shape
    path, bn, block_f_a, block_f_g = ghost_tiling(
        n, t, d, g.shape[2], block_t=block_t, block_f=block_f,
        sublanes=max(_sublanes(a.dtype), _sublanes(g.dtype)),
    )
    if path == "packed":
        return ghost_norm_sq_pallas_packed(
            a, g, bn=bn, block_f_a=block_f_a, block_f_g=block_f_g, interpret=interpret
        )
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
