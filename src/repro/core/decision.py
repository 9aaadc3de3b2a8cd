"""The paper's complexity model (Tables 1-2) and layerwise decision (Eq 4.1).

All quantities are per layer, in elements (multiply by dtype size for bytes).
B = batch, T = output positions, D = fan-in (d*kh*kw), p = fan-out.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core.taps import TapMeta


@dataclasses.dataclass(frozen=True)
class ModuleCost:
    time: float
    space: float


def back_propagation(B, T, D, p) -> ModuleCost:
    # Table 1 col 1: 2BTD(2p+1) time; BTp + 2BTD + pD space.
    return ModuleCost(time=2 * B * T * D * (2 * p + 1), space=B * T * p + 2 * B * T * D + p * D)


def ghost_norm(B, T, D, p) -> ModuleCost:
    # Table 1 col 2: 2BT^2(D+p+1) - B time; B(2T^2+1) space.
    return ModuleCost(time=2 * B * T * T * (D + p + 1) - B, space=B * (2 * T * T + 1))


def grad_instantiation(B, T, D, p) -> ModuleCost:
    # Table 1 col 3: 2B(T+1)pD time; B(pD+1) space.
    return ModuleCost(time=2 * B * (T + 1) * p * D, space=B * (p * D + 1))


def weighted_grad(B, T, D, p) -> ModuleCost:
    # Table 1 col 4: 2BpD time; 0 space.
    return ModuleCost(time=2 * B * p * D, space=0.0)


def ghost_is_cheaper(T: int, D: int, p: int, *, by: str = "space") -> bool:
    """Eq (4.1): choose ghost norm over instantiation iff 2T^2 < pD.

    ``by="time"`` implements the speed-priority variant (Remark 4.1):
    ghost iff 2T^2(D+p+1) < 2(T+1)pD.
    """
    if by == "time":
        return 2 * T * T * (D + p + 1) < 2 * (T + 1) * p * D
    return 2 * T * T < p * D


def bk_bank_prefers_ghost(
    T: int, D: int, p: int, *, groups: int = 1, a_elems: Optional[int] = None
) -> bool:
    """Book-keeping branch rule: which residual bank is smaller per sample?

    Book-keeping (arXiv:2210.00038) skips the second backward pass, so Eq
    (4.1) does not apply: every tap must *bank* enough of the backward pass to
    reconstruct ``sum_i C_i g_i`` after the clip factors are known.  The two
    banks are

    - ``instantiate``: the per-sample gradients a_i^T g_i themselves
      (G*pD elements; the per-sample norm falls out for free), or
    - ``ghost``: the (a_i, g_i) book (``a_elems`` + G*Tp elements — for
      convolutions ``a`` is banked *raw*, not unfolded, so the book is the
      true activation size) plus the ghost-norm Gram tiles (~2T^2
      transient), contracting with C_i afterwards.

    Time always favours ``instantiate`` (the psg einsum doubles as the norm),
    so — unlike Eq 4.1 — the rule is purely space-driven: bank the gradients
    unless the (a, g) book is strictly smaller.
    """
    book = (a_elems if a_elems is not None else groups * T * D) + groups * T * p
    return book + 2 * T * T < groups * D * p


def decide(
    meta: TapMeta,
    *,
    mode: str = "mixed_ghost",
    by: str = "space",
    override: Optional[str] = None,
) -> str:
    """Per-tap branch: 'ghost' | 'instantiate'.

    Non-matmul kinds have a forced branch: scale/bias/dw_conv/table per-sample
    grads are tiny (instantiate; a table's index-equality Gram would cost T^2
    per sample against its R*p rows); embeddings always use the index-equality
    ghost norm (instantiating a (V, p) gradient per sample is never viable).

    ``mode`` is ``mixed_ghost`` (Eq. 4.1) or ``bk_mixed`` (the bank-size
    rule).  ``override`` is a measured-cost branch from a ``repro.tuner``
    ClipPlan: it wins over the analytic rule (both branches compute the
    same per-sample norm, so the choice is pure performance), but never
    over a forced kind.  A plan that overrides every matmul tap forces one
    branch everywhere (the paper's ghost-only and instantiate-only rivals).
    """
    if meta.kind == "embedding":
        return "ghost"
    if meta.kind != "matmul":
        return "instantiate"
    if mode not in ("mixed_ghost", "bk_mixed"):
        raise ValueError(f"unknown clipping mode {mode!r}")
    if override is not None:
        if override not in ("ghost", "instantiate"):
            raise ValueError(f"invalid branch override {override!r}")
        return override
    if mode == "bk_mixed":
        # book-keeping banks residuals instead of paying a second
        # backward; its branch economics are bank-size driven
        a_elems = None
        if meta.a_shape is not None:
            rows = max(meta.n_stack * meta.batch_size, 1)
            a_elems = math.prod(meta.a_shape) // rows
        return "ghost" if bk_bank_prefers_ghost(
            meta.T, meta.D, meta.p,
            groups=max(meta.n_groups, 1), a_elems=a_elems,
        ) else "instantiate"
    return "ghost" if ghost_is_cheaper(meta.T, meta.D, meta.p, by=by) else "instantiate"


# Table 1's rivals with one branch everywhere
_RIVAL_BRANCH = {"ghost": "ghost", "fastgradclip": "instantiate"}


def algorithm_cost(
    metas: dict[str, TapMeta], mode: str, *, by: str = "space"
) -> dict[str, float]:
    """Table 2: total per-iteration time/space of a clipping algorithm,
    summing matmul taps (the paper's analysis covers linear/conv layers).

    ``mode`` is a clipping mode or one of Table 1's rivals: ``opacus``
    (per-sample gradients), ``ghost`` (ghost norm everywhere) and
    ``fastgradclip`` (instantiation everywhere).  The rivals are cost rows
    only; the engine runs them as ``mixed_ghost`` under a forcing plan."""
    time = 0.0
    space = 0.0
    peak_clip_space = 0.0
    for m in metas.values():
        if m.kind != "matmul":
            continue
        reps = m.n_stack * max(m.n_groups, 1)
        B, T, D, p = m.batch_size, m.T, m.D, m.p
        bp = back_propagation(B, T, D, p)
        if mode == "non_private":
            time += reps * 3 * bp.time / 2  # fwd (~bp/2) + bwd
            space += reps * bp.space
            continue
        if mode == "opacus":
            gi = grad_instantiation(B, T, D, p)
            wg = weighted_grad(B, T, D, p)
            time += reps * (3 * bp.time / 2 + gi.time + wg.time)
            # Opacus holds per-sample grads of ALL layers simultaneously
            space += reps * (bp.space + gi.space)
            continue
        branch = _RIVAL_BRANCH.get(mode) or decide(m, mode=mode, by=by)
        mod = ghost_norm(B, T, D, p) if branch == "ghost" else grad_instantiation(B, T, D, p)
        if mode == "bk_mixed":
            # no second backward; instead every tap banks residuals until the
            # clip factors are known, then pays the weighted contraction.
            # Ghost-branch taps replay the full (a, g) book (2BTDp); the
            # instantiate branch already paid the psg einsum inside
            # grad_instantiation, leaving only the Table-1 col-4 C_i sum.
            if branch == "ghost":
                wg_time = 2 * B * T * D * p
                bank = B * T * (D + p)
            else:
                wg_time = weighted_grad(B, T, D, p).time
                bank = B * p * D
            time += reps * (3 * bp.time / 2 + mod.time + wg_time)
            space += reps * (bp.space + bank)
        else:
            time += reps * (3 * bp.time / 2 + mod.time + bp.time)
            space += reps * bp.space
            peak_clip_space = max(peak_clip_space, reps * mod.space)
    return {"time": time, "space": space + peak_clip_space}
