"""Pallas TPU kernels: the fused clip-and-contract stage of book-keeping.

Book-keeping (arXiv:2210.00038) ends every step with two contractions
against the clip factors C (one scalar per sample):

- **psg bank**:   out = sum_n C_n * psg_n          psg: (N, F) -> (F,)
- **(a, g) book**: out = sum_n C_n * a_n^T g_n     a: (M, R, D), g: (M, R, p)

The XLA formulation of the book contraction (core/ghost.py before this
kernel existed) scales the cotangent first — ``g * C`` — which materializes
a cotangent-sized temporary in HBM, reads it back for the einsum, and only
then reduces.  Here the scale-and-contract is fused per VMEM tile: a
``(block_r, block_p)`` slab of ``g`` is scaled by its row weights in
registers and immediately fed to the MXU against the matching ``a`` tile;
the weighted cotangent never exists outside VMEM.  HBM traffic drops from
``2*M*R*p`` extra elements (write + read of the temp) to zero.

The psg contraction is a rank-1 batch reduction (no MXU-sized reuse), so
its kernel is a plain tiled weighted sum — it exists so the whole bank
stage can run under one dispatch decision (repro.kernels.dispatch) and be
timed as one unit by the tuner.

Grids iterate the reduction dim innermost; output blocks are revisited
across it and accumulated in place (same pattern as the ghost-norm
kernel's per-sample scalar).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pad(x, axis, mult):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_d", "block_p", "interpret")
)
def book_weighted_grad_pallas(
    a: jax.Array,  # (M, R, D)
    g: jax.Array,  # (M, R, p)
    w: jax.Array,  # (M, R) per-row weights (clip factors fanned out over T)
    *,
    block_r: int = 256,
    block_d: int = 512,
    block_p: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused weighted-book contraction: out[m] = sum_r w[m,r] a[m,r]^T g[m,r].

    Returns (M, D, p) float32.  The ``w``-scaled cotangent tile lives only
    in VMEM; rows padded up to ``block_r`` carry zero weight and contribute
    nothing regardless of the operand padding.
    """
    m, r, d = a.shape
    p = g.shape[-1]
    a = _pad(_pad(a, 1, block_r), 2, block_d)
    g = _pad(_pad(g, 1, block_r), 2, block_p)
    w = _pad(w, 1, block_r).astype(jnp.float32)
    nr = a.shape[1] // block_r
    nd = a.shape[2] // block_d
    np_ = g.shape[2] // block_p

    def kernel(a_ref, g_ref, w_ref, o_ref):
        ri = pl.program_id(3)
        gw = g_ref[0].astype(jnp.float32) * w_ref[0, 0][:, None]
        contrib = jax.lax.dot_general(
            a_ref[0].astype(jnp.float32), gw,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(ri == 0)
        def _first():
            o_ref[0] = contrib

        @pl.when(ri != 0)
        def _rest():
            o_ref[0] += contrib

    out = pl.pallas_call(
        kernel,
        grid=(m, nd, np_, nr),
        in_specs=[
            pl.BlockSpec((1, block_r, block_d), lambda mi, i, j, ri: (mi, ri, i)),
            pl.BlockSpec((1, block_r, block_p), lambda mi, i, j, ri: (mi, ri, j)),
            pl.BlockSpec((1, 1, block_r), lambda mi, i, j, ri: (mi, 0, ri)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_d, block_p), lambda mi, i, j, ri: (mi, i, j)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (m, nd * block_d, np_ * block_p), jnp.float32
        ),
        interpret=interpret,
    )(a, g, w[:, None, :])
    return out[:, :d, :p]


@functools.partial(jax.jit, static_argnames=("block_n", "block_f", "interpret"))
def psg_contract_pallas(
    psg: jax.Array,  # (N, F) banked per-sample gradients, flattened
    c: jax.Array,  # (N,) clip factors
    *,
    block_n: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Weighted bank sum: out = sum_n c[n] * psg[n].  Returns (F,) float32.

    Samples padded up to ``block_n`` carry zero weight, so the operand
    padding never leaks into the sum.
    """
    n, f = psg.shape
    psg = _pad(_pad(psg, 0, block_n), 1, block_f)
    c2 = _pad(c.astype(jnp.float32).reshape(1, n), 1, block_n)
    nn = psg.shape[0] // block_n
    nf = psg.shape[1] // block_f

    def kernel(p_ref, c_ref, o_ref):
        ni = pl.program_id(1)
        contrib = jax.lax.dot_general(
            c_ref[...], p_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(ni == 0)
        def _first():
            o_ref[...] = contrib

        @pl.when(ni != 0)
        def _rest():
            o_ref[...] += contrib

    out = pl.pallas_call(
        kernel,
        grid=(nf, nn),
        in_specs=[
            pl.BlockSpec((block_n, block_f), lambda i, ni: (ni, i)),
            pl.BlockSpec((1, block_n), lambda i, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((1, block_f), lambda i, ni: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nf * block_f), jnp.float32),
        interpret=interpret,
    )(psg, c2)
    return out[0, :f]
