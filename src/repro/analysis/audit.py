"""The auditor: trace a model's clipped step and run all three passes.

``audit_loss_fn`` is the core entry — it takes the same
``loss_with_ctx(params, batch, ctx)`` contract the clipping engines consume
(src/repro/core/clipping.py), traces the *explicit-tap* formulation
(zero taps added, activations recorded — the channel the fused engine
keeps for late taps), and runs:

1. the batch-axis taint pass (``repro.analysis.taint``) over the jaxpr,
   checking every tap-add site, every recorded activation, and the
   per-sample losses output for batch-diagonality;
2. the gradient-path coverage pass (``repro.analysis.coverage``) proving
   each claimed param leaf's cotangent is intercepted by its tap and each
   unclaimed leaf never reaches the loss;
3. optionally (``audit_arch``) the tracing-hygiene pass over the full
   jitted train step (``repro.analysis.hygiene``).

Audit batches deliberately use ``batch=3`` — distinct from every model
dimension in the reduced configs — so the reshape rule's prefix-product
matching can never confuse the batch axis with a feature axis of the same
size.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from repro.analysis import allowlist as allowlist_mod
from repro.analysis import hygiene
from repro.analysis.coverage import coverage_report
from repro.analysis.report import Finding
from repro.analysis.taint import Taint, TaintInterpreter
from repro.core.clipping import discover_meta
from repro.core.taps import Ctx, make_zero_taps


def _path_str(path) -> str:
    """jax key-path -> the "a/b/w" form used by TapMeta.param_path."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - unknown key type
            parts.append(str(k))
    return "/".join(parts)


def _flat_paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_path_str(p) for p, _ in flat]


def audit_loss_fn(
    loss_with_ctx: Callable,
    params: Any,
    batch: Any,
    *,
    arch: str = "-",
    meta: Optional[dict] = None,
    frozen_prefixes: tuple = (),
    apply_allowlist: bool = True,
    entries=None,
) -> list:
    """Taint + coverage findings for one model/batch pair."""
    if meta is None:
        meta = discover_meta(loss_with_ctx, params, batch, clip=None)
    taps0 = make_zero_taps(meta)

    def traced(p, taps, b):
        ctx = Ctx(taps=taps, meta={})
        losses = loss_with_ctx(p, b, ctx)
        return losses, ctx.acts

    closed, out_shape = jax.make_jaxpr(traced, return_shape=True)(
        params, taps0, batch
    )

    param_paths = _flat_paths(params)
    tap_names = _flat_paths(taps0)
    batch_paths = _flat_paths(batch)
    n_p, n_t = len(param_paths), len(tap_names)
    in_taints = (
        [None] * (n_p + n_t)
        + [Taint(0, (f"batch[{p}] (network input)",)) for p in batch_paths]
    )
    in_taps = [None] * n_p + list(tap_names) + [None] * len(batch_paths)

    out_flat, _ = jax.tree_util.tree_flatten_with_path(out_shape)
    losses_out_index = None
    act_out_names: dict[int, str] = {}
    for i, (path, _sds) in enumerate(out_flat):
        if getattr(path[0], "idx", None) == 0:
            losses_out_index = i
        elif len(path) > 1:
            act_out_names[i] = _path_str(path[1:])
    assert losses_out_index is not None, "loss_with_ctx returned no losses"
    batch_size = out_flat[losses_out_index][1].shape[0]

    interp = TaintInterpreter(batch_size)
    result = interp.run(closed, in_taints, in_taps)
    findings: list = []

    # -- pass 1: per-sample isolation ------------------------------------
    for site in result.sites:
        if site.taint is None:
            continue  # sample-independent pre-activation: nothing to leak
        if site.taint.mixed:
            findings.append(
                Finding(
                    code="sample_mixing",
                    severity="error",
                    arch=arch,
                    subject=site.tap,
                    detail=(
                        "pre-activation at the tap-add site is sample-mixed: "
                        "its cotangent dL/ds is not batch-diagonal, so ghost "
                        "norms are NOT the per-sample gradient norms"
                    ),
                    provenance=site.taint.trail + (f"tap add: {site.summary}",),
                )
            )
        elif site.taint.axis != 0:
            findings.append(
                Finding(
                    code="batch_axis_moved",
                    severity="error",
                    arch=arch,
                    subject=site.tap,
                    detail=(
                        f"batch axis arrived at the tap-add site on axis "
                        f"{site.taint.axis}, expected 0: per-sample reductions "
                        "would reduce the wrong dimension"
                    ),
                    provenance=site.taint.trail + (f"tap add: {site.summary}",),
                )
            )

    for i, name in act_out_names.items():
        t = result.out_taints[i]
        if t is None:
            continue
        expected = meta[name].batch_axis if name in meta else 0
        if t.mixed:
            findings.append(
                Finding(
                    code="sample_mixing",
                    severity="error",
                    arch=arch,
                    subject=f"{name}:act",
                    detail=(
                        "recorded activation is sample-mixed: the ghost-norm "
                        "Gram a_i a_j^T would pair data across samples"
                    ),
                    provenance=t.trail,
                )
            )
        elif t.axis != expected:
            findings.append(
                Finding(
                    code="batch_axis_moved",
                    severity="error",
                    arch=arch,
                    subject=f"{name}:act",
                    detail=(
                        f"recorded activation carries the batch on axis "
                        f"{t.axis}, but TapMeta (stack_dims) expects axis "
                        f"{expected}"
                    ),
                    provenance=t.trail,
                )
            )

    t_loss = result.out_taints[losses_out_index]
    if t_loss is None or t_loss.mixed or t_loss.axis != 0:
        findings.append(
            Finding(
                code="sample_mixing",
                severity="error",
                arch=arch,
                subject="losses",
                detail=(
                    "per-sample losses output is not batch-diagonal on axis 0 "
                    + (
                        "(sample-independent)"
                        if t_loss is None
                        else "(mixed)"
                        if t_loss.mixed
                        else f"(batch on axis {t_loss.axis})"
                    )
                    + ": L_i must depend on sample i only"
                ),
                provenance=() if t_loss is None else t_loss.trail,
            )
        )

    for site in result.routed:
        findings.append(
            Finding(
                code="routed_scatter",
                severity="error",
                arch=arch,
                subject=site.summary,
                detail=(
                    (
                        "sample-derived scatter positions: writes are proven "
                        "block-isolated per sample (batching dims), but "
                        "collision order-sensitivity needs a value-level "
                        "invariant the analysis cannot discharge"
                    )
                    if site.isolated
                    else (
                        "sample-derived scatter positions without batching "
                        "isolation: writes may land in other samples' blocks"
                    )
                ),
                provenance=() if site.taint is None else site.taint.trail,
            )
        )

    for prim in result.unknown_prims:
        findings.append(
            Finding(
                code="unknown_primitive",
                severity="warn",
                arch=arch,
                subject=prim,
                detail=(
                    "no taint transfer rule; outputs were conservatively "
                    "marked sample-mixed — add a rule to analysis/taint.py "
                    "if this primitive is isolation-preserving"
                ),
            )
        )

    # -- pass 2: gradient-path coverage ----------------------------------
    param_invars = {p: i for i, p in enumerate(param_paths)}
    cov = coverage_report(
        closed,
        param_invars,
        losses_out_index,
        result.sites,
        meta,
        frozen_prefixes=frozen_prefixes,
    )
    for tap, leaks in sorted(cov.bypassed.items()):
        findings.append(
            Finding(
                code="tap_bypass",
                severity="error",
                arch=arch,
                subject=tap,
                detail=(
                    "claimed param leaf(s) have a gradient route around the "
                    f"tap's cut set: {', '.join(leaks)} — their full cotangent "
                    "is not intercepted, so clipping under-counts them"
                ),
            )
        )
    for path in cov.uncovered_live:
        findings.append(
            Finding(
                code="uncovered_param",
                severity="error",
                arch=arch,
                subject=path,
                detail=(
                    "trainable param leaf reaches the loss but no tap claims "
                    "it: its gradient escapes clipping entirely (privacy bug); "
                    "declare it frozen or add a tap"
                ),
            )
        )
    for path in cov.uncovered_dead:
        findings.append(
            Finding(
                code="dead_param",
                severity="warn",
                arch=arch,
                subject=path,
                detail=(
                    "param leaf never reaches the loss: unclipped but inert "
                    "(gradient is identically zero)"
                ),
            )
        )
    for tap in cov.unthreaded:
        findings.append(
            Finding(
                code="tap_unthreaded",
                severity="error",
                arch=arch,
                subject=tap,
                detail=(
                    "tap is declared in meta but its zero array is never "
                    "added in the traced graph: its cotangent would be "
                    "identically zero and the layer's norm silently missing"
                ),
            )
        )

    if apply_allowlist:
        findings, _ = allowlist_mod.apply(
            arch,
            findings,
            entries=allowlist_mod.ALLOWLIST if entries is None else entries,
        )
    return findings


def audit_step_hygiene(model, batch, *, arch: str, batch_size: int) -> list:
    """Trace the full jitted DP train step and lint the jaxpr."""
    from repro.launch.steps import DPTrainConfig, make_train_state, make_train_step
    from repro.optim import adam, warmup_cosine

    optimizer = adam()
    state = make_train_state(model, jax.random.PRNGKey(0), optimizer)
    dp = DPTrainConfig(
        clipping_mode="mixed_ghost",
        clip_norm=1.0,
        noise_multiplier=0.5,
        logical_batch=batch_size,
    )
    step = make_train_step(model, optimizer, warmup_cosine(1e-3, 2, 10), dp)
    closed = jax.make_jaxpr(step)(state, batch)
    return hygiene.jaxpr_hygiene(closed, arch=arch)


def audit_arch(
    name: str,
    *,
    batch: int = 3,
    seq: int = 16,
    reduced: bool = True,
    hygiene_pass: bool = True,
    apply_allowlist: bool = True,
) -> list:
    """Audit one registry config end to end (taint + coverage + hygiene)."""
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import build_model, get_arch
    from repro.launch.specs import materialize, train_batch_specs

    cfg = get_arch(name)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("audit", seq, batch, "train")
    b = materialize(
        train_batch_specs(cfg, shape, batch),
        jax.random.PRNGKey(1),
        vocab=cfg.vocab,
    )
    findings = audit_loss_fn(
        model.loss_with_ctx,
        params,
        b,
        arch=name,
        apply_allowlist=apply_allowlist,
    )
    if hygiene_pass:
        findings += audit_step_hygiene(model, b, arch=name, batch_size=batch)
    return findings
