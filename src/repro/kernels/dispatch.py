"""Backend-aware kernel dispatch for the three clipping hot ops.

The Pallas TPU kernels (``ghost_norm/ghost_norm.py``,
``psg_contract/psg_contract.py``) and the portable chunked-XLA ops
(``ghost_norm/ops.py``, ``psg_contract/ops.py``) compute identical values;
which one the training step traces is a pure performance decision.  This
module is the single place that decision is made:

    op               pallas impl                      xla impl
    ---------------  -------------------------------  ------------------------
    ghost_norm       ghost_norm_sq_pallas             gops.ghost_norm_sq
    embedding_ghost_norm
                     embedding_ghost_norm_sq_pallas   gops.embedding_ghost_norm_sq
    psg_contract     book_weighted_grad_pallas /      cops.book_weighted_grad /
                     psg_contract_pallas              cops.psg_contract
    flash_attention  flash_attention_pallas           fops.flash_attention
                     (static masks only; dynamic cache args fall back)

Resolution order, per call:

1. an explicit ``impl=`` argument — threaded from a tuner ``ClipPlan``'s
   per-tap ``kernels`` map through ``ClipRuntime``/``ProbeSpec`` (the
   measured choice, consensus-hash-covered on fleets);
2. a ``force_impl`` context override (tests flip the choice both ways);
3. the backend default: ``pallas`` on TPU, ``xla`` everywhere else.

Requesting ``pallas`` off-TPU runs the kernel in interpreter mode — exact
but slow, which is precisely what the parity tests and the flipped-choice
exactness oracle want; it can never happen in production because the
backend default is ``xla`` there and a plan's kernel map is only applied
by the device kind that *measured* it (``ClipPlan.kernels_for`` — merely
ratifying a fleet agreement is not enough, unlike branch overrides).
Both impls of every op compute the same sums over the same tiles; only
scheduling and HBM traffic differ, so a flipped choice moves cost, never
results (tested).

Under a mesh (``repro.parallel.reshard.use_reshard_rules``) the clipping
kernels run per device on that device's samples
(``reshard.split_over_samples``): GSPMD cannot partition a Mosaic kernel.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Iterator, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as fops
from repro.kernels.ghost_norm import ops as gops
from repro.kernels.psg_contract import ops as cops
from repro.parallel.reshard import split_over_samples

OPS = ("ghost_norm", "embedding_ghost_norm", "psg_contract", "flash_attention")
IMPLS = ("pallas", "xla")

# force_impl() state: {op: impl}; consulted at trace time, tests only
_forced: dict[str, str] = {}


def backend() -> str:
    """The platform jax will place this trace on (``tpu``/``gpu``/``cpu``)."""
    return jax.default_backend()


def available_impls() -> tuple[str, ...]:
    """Impls worth *measuring* here: both on TPU, xla-only elsewhere.

    (``pallas`` still *runs* off-TPU via the interpreter when explicitly
    requested — it is excluded here because an interpreted kernel can never
    win a timing comparison and must not be offered to the tuner.)
    """
    return IMPLS if backend() == "tpu" else ("xla",)


def default_impl(op: str) -> str:
    """The unmeasured default: the Pallas kernel on TPU, XLA elsewhere."""
    if op not in OPS:
        raise ValueError(f"unknown kernel op {op!r}; have {OPS}")
    return "pallas" if backend() == "tpu" else "xla"


def resolve(op: str, impl: Optional[str] = None) -> str:
    """Pick the impl for one op: explicit > forced > backend default."""
    if impl is None:
        impl = _forced.get(op)
    if impl is None:
        return default_impl(op)
    if op not in OPS:
        raise ValueError(f"unknown kernel op {op!r}; have {OPS}")
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r} for {op}; have {IMPLS}")
    return impl


@contextlib.contextmanager
def force_impl(
    impl: Optional[str] = None, **per_op: str
) -> Iterator[None]:
    """Context override for tests: force all ops to ``impl`` or per-op kwargs.

    ``force_impl("pallas")`` routes every op through the Pallas kernels
    (interpreted off-TPU); ``force_impl(psg_contract="xla")`` pins one op.
    Overrides apply at trace time — build and jit the function under test
    inside the context.
    """
    if impl is not None and impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; have {IMPLS}")
    for op, i in per_op.items():
        if op not in OPS:
            raise ValueError(f"unknown kernel op {op!r}; have {OPS}")
        if i not in IMPLS:
            raise ValueError(f"unknown kernel impl {i!r} for {op}; have {IMPLS}")
    saved = dict(_forced)
    try:
        if impl is not None:
            _forced.update({op: impl for op in OPS})
        _forced.update(per_op)
        yield
    finally:
        _forced.clear()
        _forced.update(saved)


def _interpret() -> bool:
    return backend() != "tpu"


def kernels_arg(kernels: Optional[Mapping[str, str]], op: str) -> Optional[str]:
    """The per-tap plan choice for ``op`` (None = no recorded choice)."""
    return None if kernels is None else kernels.get(op)


# -- the dispatched ops ----------------------------------------------------
def ghost_norm_sq(
    a: jax.Array,
    g: jax.Array,
    *,
    block: int = 512,
    impl: Optional[str] = None,
) -> jax.Array:
    """Ghost norm (Eq. 2.7): a (N,T,D), g (N,T,p) -> (N,) fp32."""
    if resolve("ghost_norm", impl) == "pallas":
        from repro.kernels.ghost_norm.ghost_norm import ghost_norm_sq_pallas

        kernel = functools.partial(ghost_norm_sq_pallas, interpret=_interpret())
        return split_over_samples(kernel, a, g)
    return gops.ghost_norm_sq(a, g, block=block)


def embedding_ghost_norm_sq(
    ids: jax.Array,
    g: jax.Array,
    *,
    block: int = 1024,
    impl: Optional[str] = None,
) -> jax.Array:
    """Index-equality ghost norm: ids (N,T), g (N,T,p) -> (N,) fp32."""
    if resolve("embedding_ghost_norm", impl) == "pallas":
        from repro.kernels.ghost_norm.ghost_norm import (
            embedding_ghost_norm_sq_pallas,
        )

        kernel = functools.partial(
            embedding_ghost_norm_sq_pallas, interpret=_interpret()
        )
        return split_over_samples(kernel, ids, g)
    return gops.embedding_ghost_norm_sq(ids, g, block=block)


def book_weighted_grad(
    a: jax.Array,
    g: jax.Array,
    w: jax.Array,
    *,
    impl: Optional[str] = None,
) -> jax.Array:
    """Weighted (a,g)-book contraction: sum_r w[m,r] a[m,r]^T g[m,r].

    a (M,R,D), g (M,R,p), w (M,R) -> (M,D,p) fp32.  The Pallas impl scales
    cotangent tiles in VMEM so the ``g * w`` temporary never reaches HBM.
    """
    if resolve("psg_contract", impl) == "pallas":
        from repro.kernels.psg_contract.psg_contract import (
            book_weighted_grad_pallas,
        )

        # rows are (sample, position) pairs: the sum over them splits by
        # sample across devices
        kernel = functools.partial(
            book_weighted_grad_pallas, interpret=_interpret()
        )
        return split_over_samples(kernel, a, g, w, dim=1, reduce=True)
    return cops.book_weighted_grad(a, g, w)


def psg_contract(
    psg: jax.Array,
    c: jax.Array,
    *,
    axis: int = 0,
    impl: Optional[str] = None,
) -> jax.Array:
    """Weighted bank sum over the sample axis: sum_n c[n] * psg[..n..].

    ``psg`` has the batch on ``axis`` (the probe banks carry it *after* the
    stack dims); the result drops that axis, keeping the remaining dims in
    order, fp32.
    """
    if resolve("psg_contract", impl) == "pallas":
        from repro.kernels.psg_contract.psg_contract import psg_contract_pallas

        moved = jnp.moveaxis(psg, axis, 0)
        out_shape = moved.shape[1:]
        flat = moved.reshape(moved.shape[0], -1)
        kernel = functools.partial(psg_contract_pallas, interpret=_interpret())
        return split_over_samples(kernel, flat, c, reduce=True).reshape(out_shape)
    return jnp.tensordot(
        c.astype(jnp.float32), psg.astype(jnp.float32), axes=(0, axis)
    )


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
    impl: Optional[str] = None,
) -> jax.Array:
    """Serving attention (B, Sq, H, hd layout), forward only.

    The Pallas kernel covers the static-mask cases (causal/window with an
    int ``q_offset``).  Dynamic cache shapes — a traced ``q_offset``, ring
    ``kv_positions``, or a ``kv_valid_len`` fill level — fall back to the
    XLA path regardless of the resolved impl: the kernel has no scalar-
    prefetch story for them yet (the paged-attention follow-on).  Training
    never routes through here (it needs the custom VJP in
    ``flash_attention.ops``); this wrapper is for cache-serving traces.
    """
    pallas_ok = (
        kv_positions is None
        and kv_valid_len is None
        and scale is None
        and isinstance(q_offset, int)
    )
    if resolve("flash_attention", impl) == "pallas" and pallas_ok:
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention_pallas,
        )

        h, kh = q.shape[2], k.shape[2]
        qt = jnp.moveaxis(q, 1, 2)  # (B, H, Sq, hd)
        kt = jnp.moveaxis(k, 1, 2)
        vt = jnp.moveaxis(v, 1, 2)
        if kh != h:
            # GQA: query head h reads kv head h // g (matches the XLA
            # (B, S, K, g, hd) grouping)
            kt = jnp.repeat(kt, h // kh, axis=1)
            vt = jnp.repeat(vt, h // kh, axis=1)
        out = flash_attention_pallas(
            qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
            block_q=min(block_q, 128), block_kv=min(block_kv, 128),
            interpret=_interpret(),
        )
        return jnp.moveaxis(out, 1, 2)
    return fops.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        kv_valid_len=kv_valid_len, kv_positions=kv_positions,
        block_q=block_q, block_kv=block_kv, scale=scale,
    )
