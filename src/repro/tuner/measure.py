"""Measured per-tap branch costs: the three-way clipping decision.

The analytic decision (Eq 4.1) counts multiplies; this module instead times
the branch kernels on the actual device over the tap's real canonical
shapes — a (N, T, D) activation against a (N, T, p) cotangent, exactly what
``ghost.tap_norm_sq`` feeds them at train time — with warmup and
median-of-k.  Convolution taps are timed post-unfold: both norm branches
consume the unfolded activation, so the (shared) im2col cost cancels out of
the comparison.

Five timings per matmul tap:

- ``ghost_us`` / ``instantiate_us``: the norm kernels (second-backward
  modes pick the cheaper and then pay ``second_bwd_us`` on top);
- ``bk_ghost_us`` / ``bk_instantiate_us``: the full book-keeping pipelines —
  ghost norm + weighted einsum from the (a, g) book, vs per-sample-gradient
  bank (norm falls out free) + clip contraction;
- ``second_bwd_us``: the tap's dW + dX matmuls — its share of the second
  backward pass that book-keeping skips.

This is what makes the tuner *plan-aware across modes*: per tap it can
answer {ghost+2nd-bwd, instantiate+2nd-bwd, book-keeping-einsum} and emit a
branch map per mode (plan.branches / plan.bk_branches) plus a measured
``recommended_mode``.

On TPU each hot op additionally has two *implementations* — the Pallas
kernel and the chunked-XLA lowering (repro.kernels.dispatch) — so before
the branches are timed, ``measure_kernels`` races the impls per tap
(ghost norm + psg bank contraction for matmuls, the index-equality ghost
norm for embeddings) and the branch timings are then taken *under the
winning impls*, which are recorded in the plan's v5 ``kernels`` map.  Off
TPU there is exactly one production impl (xla), recorded without timing.

Only matmul taps get branch timings.  Embedding / scale / bias / dw_conv /
table taps have a single viable branch (decision.decide's forced cases) and are
never overridden — embeddings still get a kernel-impl measurement.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core.decision import decide
from repro.core.ghost import GHOST_BLOCK, INST_BLOCK_D
from repro.core.taps import TapMeta
from repro.kernels import dispatch
from repro.kernels.ghost_norm import ops as gops
from repro.tuner.plan import (
    ClipPlan,
    TapTiming,
    device_string,
    shape_fingerprint,
    tap_signature,
)
from repro.utils.logging import get_logger

log = get_logger("tuner.measure")


@dataclasses.dataclass(frozen=True)
class MeasureConfig:
    """Profiling knobs shared by every tuner measurement pass.

    The defaults favour cheap, stable comparisons over absolute accuracy:
    medians over ``repeats`` timed runs absorb scheduler noise, ``warmup``
    burns compilation, and ``max_rows`` clamps the profiled row count so a
    huge-batch model can be tuned without OOMing the device being sized
    (timings scale ~linearly in rows, so the *comparison* survives).
    """

    repeats: int = 5  # timed iterations; the median is kept
    warmup: int = 2  # discarded iterations (compile + caches)
    # clamp the row dim N = stack*B*groups during profiling; timings scale
    # ~linearly in N, so the *comparison* is preserved while huge-batch taps
    # stay cheap to profile (tuning must never OOM the device it is sizing).
    # None = use the discovered batch as-is.
    max_rows: Optional[int] = 64
    seed: int = 0


def time_us(fn, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Median wall-clock microseconds per ``fn(*args)`` call.

    Blocks on all outputs (``jax.block_until_ready``) so asynchronous
    dispatch cannot under-report; the first ``warmup`` calls absorb
    compilation and cache effects and are discarded.
    """
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    return samples[len(samples) // 2]


def _tap_rows(meta: TapMeta, max_rows: Optional[int]) -> int:
    n = meta.n_stack * max(meta.batch_size, 1) * max(meta.n_groups, 1)
    if max_rows is not None:
        n = max(1, min(n, max_rows))
    return n


# dispatch ops with a measurable impl choice, per tap kind; scale / bias /
# dw_conv / table taps bank tiny per-sample grads and keep the dispatch default
KERNEL_OPS_BY_KIND = {
    "matmul": ("ghost_norm", "psg_contract"),
    "embedding": ("embedding_ghost_norm",),
}


def _book(x: jax.Array, y: jax.Array, cc: jax.Array, impl: Optional[str]):
    """The fused book contraction as the engine runs it: (n,T,D) x (n,T,p)
    rows folded to one (M=1, R=n*T) book, one row weight per (sample, t)."""
    nn, tt, dd = x.shape
    a2 = x.reshape(1, nn * tt, dd)
    g2 = y.reshape(1, nn * tt, y.shape[-1])
    w2 = jnp.broadcast_to(cc[:, None], (nn, tt)).reshape(1, nn * tt)
    return dispatch.book_weighted_grad(a2, g2, w2, impl=impl)[0]


def measure_tap_kernels(
    meta: TapMeta, cfg: MeasureConfig = MeasureConfig()
) -> dict[str, str]:
    """Race Pallas vs XLA per dispatch op for one tap; return the winners.

    ``{op: impl}`` for every op in ``KERNEL_OPS_BY_KIND[kind]`` ({} for
    kinds with no dispatchable op).  Where only one impl is available
    (everywhere but TPU) it is recorded without timing — the plan then
    states the choice explicitly instead of leaving it to the backend
    default at trace time.
    """
    ops_ = KERNEL_OPS_BY_KIND.get(meta.kind, ())
    if not ops_:
        return {}
    avail = dispatch.available_impls()
    if len(avail) == 1:
        return {op: avail[0] for op in ops_}

    n = _tap_rows(meta, cfg.max_rows)
    key = jax.random.PRNGKey(cfg.seed)
    ka, kg, kc = jax.random.split(key, 3)
    out: dict[str, str] = {}

    def race(op: str, make_fn, *args) -> None:
        per_impl = {}
        for impl in avail:
            per_impl[impl] = time_us(
                jax.jit(make_fn(impl)), *args,
                repeats=cfg.repeats, warmup=cfg.warmup,
            )
        winner = min(sorted(per_impl), key=per_impl.get)
        log.info("%s kernels: %s -> %s", op,
                 " ".join(f"{i}={t:.1f}us" for i, t in sorted(per_impl.items())),
                 winner)
        out[op] = winner

    if meta.kind == "matmul":
        dtype = jnp.dtype(meta.s_dtype)
        a = jax.random.normal(ka, (n, meta.T, meta.D), jnp.float32).astype(dtype)
        g = jax.random.normal(kg, (n, meta.T, meta.p), jnp.float32)
        c = jax.random.uniform(kc, (n,), jnp.float32)
        race(
            "ghost_norm",
            lambda impl: lambda x, y: dispatch.ghost_norm_sq(
                x, y, block=GHOST_BLOCK, impl=impl
            ),
            a, g,
        )
        race(
            "psg_contract",
            lambda impl: lambda x, y, cc: _book(x, y, cc, impl),
            a, g, c,
        )
    elif meta.kind == "embedding":
        # the fused engine sends ids through the bank channel as fp32
        # (core/taps.py) — time exactly that
        vocab = min(meta.D, 1 << 24)
        ids = jax.random.randint(ka, (n, meta.T), 0, vocab).astype(jnp.float32)
        g = jax.random.normal(kg, (n, meta.T, meta.p), jnp.float32)
        race(
            "embedding_ghost_norm",
            lambda impl: lambda i, y: dispatch.embedding_ghost_norm_sq(
                i, y, impl=impl
            ),
            ids, g,
        )
    return out


def measure_tap(
    meta: TapMeta,
    cfg: MeasureConfig = MeasureConfig(),
    kernels: Optional[Mapping[str, str]] = None,
) -> Optional[TapTiming]:
    """Time every branch of the three-way decision for one matmul tap.

    Returns a ``TapTiming`` with the five per-tap costs (ghost norm,
    instantiated norm, both book-keeping pipelines, and the tap's share of
    a second backward) measured on synthetic data of the tap's canonical
    shape, or ``None`` for non-matmul kinds, whose branch is forced by
    ``decision.decide`` and never measured.  ``kernels`` pins the
    Pallas-vs-XLA impl per dispatch op (``measure_tap_kernels``'s winners)
    so the branch comparison prices the kernels that will actually trace.
    """
    if meta.kind != "matmul":
        return None
    k_ghost = dispatch.kernels_arg(kernels, "ghost_norm")
    k_psg = dispatch.kernels_arg(kernels, "psg_contract")
    n = _tap_rows(meta, cfg.max_rows)
    key = jax.random.PRNGKey(cfg.seed)
    ka, kg, kw, kc = jax.random.split(key, 4)
    dtype = jnp.dtype(meta.s_dtype)
    # match the train-time kernels exactly: activations stay in their
    # storage dtype, but tap_norm_sq upcasts the cotangent to fp32 before
    # either branch runs (core/ghost.py) — time what will actually execute
    a = jax.random.normal(ka, (n, meta.T, meta.D), jnp.float32).astype(dtype)
    g = jax.random.normal(kg, (n, meta.T, meta.p), jnp.float32)
    w = jax.random.normal(kw, (meta.D, meta.p), jnp.float32)
    c = jax.random.uniform(kc, (n,), jnp.float32)

    # -- second-backward norm branches (both consume unfolded patches at
    # train time, so the shared im2col cost cancels out of THIS comparison)
    ghost_fn = jax.jit(
        lambda x, y: dispatch.ghost_norm_sq(
            x, y, block=GHOST_BLOCK, impl=k_ghost
        )
    )
    inst_fn = jax.jit(
        lambda x, y: gops.instantiated_norm_sq(x, y, block_d=INST_BLOCK_D)
    )
    ghost_us = time_us(ghost_fn, a, g, repeats=cfg.repeats, warmup=cfg.warmup)
    inst_us = time_us(inst_fn, a, g, repeats=cfg.repeats, warmup=cfg.warmup)

    # -- book-keeping pipelines (norm + bank + weighted contraction) ------
    # These time the kernels dp_value_and_clipped_grad actually runs, which
    # for convolutions are NOT the im2col einsums: the psg bank goes through
    # the conv op's own vjp on the raw activation (ghost._matmul_psg, no
    # unfold), while the ghost book pays the unfold itself.
    is_conv = meta.conv is not None and meta.a_shape is not None
    if is_conv:
        import dataclasses as _dc

        from repro.core.ghost import _matmul_psg
        from repro.nn.conv import unfold2d

        m1 = _dc.replace(
            meta, batch_size=n, stack_dims=(),
            s_shape=(n,) + tuple(meta.s_shape[-3:]),
            a_shape=(n,) + tuple(meta.a_shape[-3:]),
        )
        a_raw = jax.random.normal(
            ka, (n,) + tuple(meta.a_shape[-3:]), jnp.float32
        ).astype(meta.a_dtype or dtype)
        g_out = g.reshape((n,) + tuple(meta.s_shape[-3:]))

        def bk_ghost(xraw, y, cc):
            aa = unfold2d(xraw, meta.conv).astype(jnp.float32)
            yy = y.reshape(n, meta.T, meta.p)
            norms = dispatch.ghost_norm_sq(
                aa, yy, block=GHOST_BLOCK, impl=k_ghost
            )
            wg = _book(aa, yy, cc, k_psg)
            return norms, wg

        def bk_inst(xraw, y, cc):
            psg = _matmul_psg(m1, xraw, y)
            norms = jnp.sum(jnp.square(psg).reshape(n, -1), axis=-1)
            wg = dispatch.psg_contract(psg, cc, impl=k_psg)
            return norms, wg

        bk_ghost_us = time_us(jax.jit(bk_ghost), a_raw, g_out, c,
                              repeats=cfg.repeats, warmup=cfg.warmup)
        bk_inst_us = time_us(jax.jit(bk_inst), a_raw, g_out, c,
                             repeats=cfg.repeats, warmup=cfg.warmup)
    else:
        def bk_ghost(x, y, cc):
            norms = dispatch.ghost_norm_sq(
                x, y, block=GHOST_BLOCK, impl=k_ghost
            )
            wg = _book(x.astype(jnp.float32), y, cc, k_psg)
            return norms, wg

        def bk_inst(x, y, cc):
            psg = jnp.einsum("ntd,ntp->ndp", x.astype(jnp.float32), y)
            norms = jnp.sum(jnp.square(psg).reshape(psg.shape[0], -1), axis=-1)
            wg = dispatch.psg_contract(psg, cc, impl=k_psg)
            return norms, wg

        bk_ghost_us = time_us(jax.jit(bk_ghost), a, g, c,
                              repeats=cfg.repeats, warmup=cfg.warmup)
        bk_inst_us = time_us(jax.jit(bk_inst), a, g, c,
                             repeats=cfg.repeats, warmup=cfg.warmup)

    # -- the tap's share of a second backward pass (dW + dX) --------------
    def second_bwd(x, y, ww):
        dw = jnp.einsum("ntd,ntp->dp", x.astype(jnp.float32), y)
        dx = jnp.einsum("ntp,dp->ntd", y, ww)
        return dw, dx

    second_bwd_us = time_us(jax.jit(second_bwd), a, g, w,
                            repeats=cfg.repeats, warmup=cfg.warmup)

    return TapTiming(
        ghost_us=ghost_us, instantiate_us=inst_us,
        bk_ghost_us=bk_ghost_us, bk_instantiate_us=bk_inst_us,
        second_bwd_us=second_bwd_us,
    )


def _shape_key(name: str, meta: TapMeta) -> tuple:
    sig = tap_signature(name, meta)
    del sig["name"]
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in sig.items()))


def measure_kernels(
    metas: Mapping[str, TapMeta], cfg: MeasureConfig = MeasureConfig()
) -> dict[str, dict[str, str]]:
    """Per-tap kernel-impl winners, one measurement per unique shape.

    Covers every tap whose kind has a dispatchable op (matmul, embedding);
    same shape-signature dedupe as ``measure_branches`` and for the same
    reason — identically-shaped layers must trace identical kernels.
    """
    by_shape: dict[tuple, dict[str, str]] = {}
    out: dict[str, dict[str, str]] = {}
    for name in sorted(metas):
        meta = metas[name]
        if meta.kind not in KERNEL_OPS_BY_KIND:
            continue
        key = _shape_key(name, meta)
        choices = by_shape.get(key)
        if choices is None:
            choices = measure_tap_kernels(meta, cfg)
            by_shape[key] = choices
        if choices:
            out[name] = choices
    return out


def measure_branches(
    metas: Mapping[str, TapMeta],
    cfg: MeasureConfig = MeasureConfig(),
    kernels: Optional[Mapping[str, Mapping[str, str]]] = None,
) -> dict[str, TapTiming]:
    """One timing per *unique shape signature*, fanned out to all taps.

    Identically-shaped layers (every layer of a homogeneous stack) must get
    the same branch: measuring them independently multiplies profiling cost
    and lets timer noise encode jitter as per-layer "hardware truth".
    ``kernels`` (``measure_kernels``'s winners) pins the impl each branch
    timing runs under; None times the dispatch backend default.
    """
    by_shape: dict[tuple, TapTiming] = {}
    out: dict[str, TapTiming] = {}
    for name in sorted(metas):
        meta = metas[name]
        if meta.kind != "matmul":
            continue
        key = _shape_key(name, meta)
        timing = by_shape.get(key)
        if timing is None:
            timing = measure_tap(
                meta, cfg, kernels=None if kernels is None else kernels.get(name)
            )
            by_shape[key] = timing
            analytic = decide(meta, mode="mixed_ghost")
            mark = "" if analytic == timing.winner else "  (!= analytic %s)" % analytic
            log.info(
                "%s: ghost=%.1fus inst=%.1fus bk_ghost=%.1fus bk_inst=%.1fus "
                "2nd_bwd=%.1fus -> %s/%s%s",
                name, timing.ghost_us, timing.instantiate_us,
                timing.bk_ghost_us, timing.bk_instantiate_us,
                timing.second_bwd_us, timing.winner, timing.bk_winner, mark,
            )
        out[name] = timing
    return out


def _plan_fields(timings: Mapping[str, TapTiming]) -> dict:
    return dict(
        branches=tuple((name, t.winner) for name, t in sorted(timings.items())),
        bk_branches=tuple(
            (name, t.bk_winner) for name, t in sorted(timings.items())
        ),
        timings=tuple(t.as_tuple(name) for name, t in sorted(timings.items())),
    )


def _kernel_rows(
    kernels: Mapping[str, Mapping[str, str]]
) -> tuple[tuple[str, str, str], ...]:
    """Flatten {tap: {op: impl}} to the sorted triples ClipPlan stores."""
    return tuple(
        (name, op, impl)
        for name in sorted(kernels)
        for op, impl in sorted(kernels[name].items())
    )


def build_plan(
    metas: Mapping[str, TapMeta],
    *,
    measure: MeasureConfig = MeasureConfig(),
    arch: Optional[str] = None,
) -> ClipPlan:
    """Profile every matmul tap and assemble the measured-cost ClipPlan.

    Kernel impls are raced first (``measure_kernels``); the branch timings
    are then taken under the winners, and both land in the plan — the
    branch maps drive ghost-vs-instantiate, the v5 ``kernels`` map drives
    Pallas-vs-XLA at trace time.
    """
    kernels = measure_kernels(metas, measure)
    timings = measure_branches(metas, measure, kernels=kernels)
    return ClipPlan(
        fingerprint=shape_fingerprint(metas),
        device=device_string(),
        arch=arch,
        kernels=_kernel_rows(kernels),
        **_plan_fields(timings),
    )


def remeasure_at_batch(
    plan: ClipPlan,
    metas: Mapping[str, TapMeta],
    physical_batch: int,
    cfg: MeasureConfig = MeasureConfig(),
    *,
    cap_bytes: int = 1 << 30,
) -> ClipPlan:
    """Re-time the branches at the tuned physical batch and refresh the plan.

    Branch timings are first measured at the (row-clamped) probe batch;
    after the max-batch search settles, the step actually runs at
    ``physical_batch``.  Timings scale ~linearly in rows so flips are rare,
    but re-measuring closes the loop and removes the assumption (ROADMAP
    "profile at the tuned physical batch").  The fingerprint is batch-free,
    so the refreshed plan stays valid for the same model/device.

    ``cap_bytes`` bounds the largest profiling array per tap (tuning must
    never OOM the device it is sizing — the max-batch search certified the
    *training* graph, not per-tap psg instantiation at full rows): taps whose
    full-batch measurement would exceed it are clamped to the largest batch
    that fits, which preserves the comparison since timings scale ~linearly.

    Kernel winners are RE-RACED at the rebatched shapes, not carried over
    from the probe batch: Pallas-vs-XLA crossover moves with rows (grid
    occupancy and the bank-contraction tile both depend on B), so a plan
    recorded at the certified batch must carry winners raced there — the
    re-timed branches then run under those winners and both land in the
    refreshed plan together.
    """
    rebatched = {}
    clamped = 0
    for name, m in metas.items():
        b = physical_batch
        if m.kind == "matmul":
            reps = max(m.n_stack * max(m.n_groups, 1), 1)
            # a, g, and (bk_inst) psg are all live at once per profiled row
            per_row = 4 * (m.T * m.D + m.T * m.p + m.D * m.p)
            b_cap = max(1, cap_bytes // max(per_row * reps, 1))
            if b_cap < b:
                b, clamped = b_cap, clamped + 1
        rebatched[name] = dataclasses.replace(m, batch_size=b)
    if clamped:
        log.info("remeasure: %d tap(s) clamped below physical batch %d to "
                 "respect the %.1fGB profiling cap", clamped, physical_batch,
                 cap_bytes / 1024**3)
    cfg_full = dataclasses.replace(cfg, max_rows=None)
    kernels = measure_kernels(rebatched, cfg_full)
    old_kernels = plan.kernel_map()
    kernel_flips = sum(
        1 for name, ops in kernels.items()
        for op, impl in ops.items()
        if old_kernels.get(name, {}).get(op, impl) != impl
    )
    if kernel_flips:
        log.info("re-racing kernels at physical batch %d flipped %d "
                 "winner(s)", physical_batch, kernel_flips)
    timings = measure_branches(rebatched, cfg_full, kernels=kernels)
    flips = sum(
        1 for name, b in plan.branches if timings.get(name) and
        timings[name].winner != b
    ) + sum(
        1 for name, b in plan.bk_branches if timings.get(name) and
        timings[name].bk_winner != b
    )
    if flips:
        log.info("re-measuring at physical batch %d flipped %d branch(es)",
                 physical_batch, flips)
    return dataclasses.replace(
        plan, measured_at_physical=True, kernels=_kernel_rows(kernels),
        **_plan_fields(timings)
    )


def close_physical_batch_loop(
    plan: ClipPlan,
    metas: Mapping[str, TapMeta],
    search,  # (plan) -> max physical batch under the caller's budget, <=0 = none
    logical_batch: int,
    budget_bytes: int,
    cfg: MeasureConfig = MeasureConfig(),
    *,
    max_iters: int = 3,
) -> ClipPlan:
    """Converge {branch maps, physical batch} to a mutually consistent pair.

    The coupled loop behind the ROADMAP "profile at the tuned physical
    batch" item: branch timings must be taken at the batch that will run,
    but flipping a branch changes per-tap clipping memory, which can change
    the max batch that fits — so re-measure and re-search alternate until a
    fixpoint (almost always one round; ``max_iters`` bounds pathological
    oscillation).  On a failed re-search the last *certified* plan (branches
    and batch from the same measurement) is returned rather than a plan
    whose branches contradict its own timings.
    """
    from repro.tuner.max_batch import derive_accumulation

    mp = plan.physical_batch
    if not mp or mp <= 0:
        return plan
    for _ in range(max_iters):
        certified = plan
        plan = remeasure_at_batch(plan, metas, mp, cfg)
        if (plan.branches, plan.bk_branches) == (
            certified.branches, certified.bk_branches
        ):
            return plan  # branches stable at the certified batch: converged
        mp2 = search(plan)
        if mp2 <= 0:
            log.warning(
                "re-measured branches no longer fit the budget at batch %d; "
                "keeping the certified plan", mp,
            )
            return certified
        if mp2 == mp:
            return plan  # flips did not move the certificate: converged
        log.info("branch flips moved the max physical batch %d -> %d; "
                 "re-measuring there", mp, mp2)
        _, steps = derive_accumulation(logical_batch, mp2)
        plan = dataclasses.replace(
            plan.replace_batch(
                physical_batch=mp2, logical_batch=logical_batch,
                accumulation_steps=steps, budget_bytes=budget_bytes,
            ),
            # timings are still from mp; only the next remeasure may claim it
            measured_at_physical=False,
        )
        mp = mp2
    log.warning("branch/batch loop did not converge in %d rounds; timings "
                "were last taken one batch behind", max_iters)
    return plan
