"""Per-sample gradient clipping engines (the paper's Algorithm 1 and rivals).

The model exposes ``loss_with_ctx(params, batch, ctx) -> per_sample_losses``;
everything else happens here.  Every mode is a ``ClipExecutor`` — one shared
three-stage pipeline

    norms stage    -> per-sample squared norms (mode-specific machinery)
    factor stage   -> C_i = clip_fn(||g_i||, R) * mask     (shared)
    gradient stage -> sum_i C_i g_i                        (mode-specific)

In the compiled program the norms and factor stages run under the named
scope ``dp.norm_pass`` (each tap's norm work under ``dp.tap_norm/<tap>``,
see ``ghost``) and the gradient stage under ``dp.second_pass``; a device
trace attributes each op to its stage through the op's metadata.

The factor stage is delegated to a **ClipPolicy** (``repro.policies``):
``fixed`` (the paper's flat R, the default), ``automatic`` (AUTO-S/AUTO-V
normalization, no R), ``quantile`` (DP-adaptive R tracking a norm quantile,
paying for its release in the accountant), and ``per_layer`` (per-tap-group
thresholds).  Policies may carry state — pass it as the executor's third
argument and thread the updated state through the train step
(``launch.steps.make_train_step``).

Modes
-----
- ``non_private`` no clipping (C_i = 1); the baseline every overhead claim is
                  measured against.
- ``vmap``        Opacus analogue and the exactness oracle: materialize
                  per-sample grads via vmap(grad), clip, sum.  O(B x |params|)
                  memory.
- ``mixed_ghost`` the paper's Algorithm 1: Eq-(4.1) layerwise decision
                  between ghost norm and instantiation + second backward.
- ``bk_mixed``    beyond-paper: book-keeping (arXiv:2210.00038) — the fused
                  probes bank per-sample gradients (or the (a, g) book) during
                  the single backward pass and the gradient stage is a direct
                  einsum against the clip factors.  No second backward; DP
                  cost ~= non-private cost.

The clipped mechanism is the same in every mode (tested against ``vmap``):
the paper's claim that the implementation "does not affect the mathematics".
The paper's ghost-only and instantiate-only rivals are ``mixed_ghost``
under a ``ClipConfig(plan=...)`` whose branch map forces every matmul tap to
``ghost`` or to ``instantiate``; a plan moves cost, never results.

Mode selection guide
--------------------
Which engine wins depends on {memory budget, architecture, device}:

- **Tight memory budget** (the paper's ≤10%-overhead regime — large CNNs or
  long sequences on small devices): ``mixed_ghost``.  The fused probes keep
  per-layer cotangents inside the backward scan, the Eq-(4.1) decision never
  materializes a large branch, and the second backward reuses residuals
  instead of banking anything.
- **Throughput-bound training with headroom** (fine-tuning, mid-size models,
  accelerators with spare HBM): ``bk_mixed``.  It trades the whole second
  backward for per-tap banks (per-sample grads where pD is small, the (a, g)
  book where it is not); per-step time approaches ``non_private`` while peak
  memory stays within ~10% of it on conv nets (see BENCH_modes.json).
- **Unknown hardware**: run ``repro.tuner`` — it times ghost / instantiate /
  book-keeping per tap on the device and writes a ClipPlan whose
  ``recommended_mode()`` settles the question with measurements; ``launch.train
  --tune --mode auto`` adopts it end to end.
- **Debugging / cross-checking**: ``vmap`` (the oracle, tiny models only).

Flow for the fused second-backward family (1 forward + 2 backward, Fig. 1
right)::

    (losses, acts), pullback = vjp(f, params, banks)  # banks = dummy zeros
    _, nb, gs  = pullback(ones)     # per-tap banks {"n": norms^2} via probes
    norms2     = sum_tap nb[tap]["n"]
    C          = clip_fn(sqrt(norms2), R) * mask
    grads, _   = pullback(C)        # == grad of sum_i C_i L_i  (2nd backward)

``bk_mixed`` runs the same pipeline but its banks also carry the weighted-
gradient residuals, and the gradient stage is ``bank_weighted_grads`` —
no tap-sized zeros, no activation dict, no second backward.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import fused as fused_mod
from repro.core import ghost
from repro.core.taps import ClipRuntime, Ctx, TapMeta, make_zero_taps
from repro.utils.tree import flatten_dict, unflatten_dict

LossFn = Callable[..., jax.Array]  # (params, batch, ctx) -> (B,) losses

MODES = ("non_private", "vmap", "mixed_ghost", "bk_mixed")

# stage scopes: the first pass (forward, first backward, per-sample norms,
# clip factors) and the gradient stage (second backward or bank einsums)
NORM_PASS_SCOPE = "dp.norm_pass"
SECOND_PASS_SCOPE = "dp.second_pass"


@dataclasses.dataclass(frozen=True)
class ClipConfig:
    mode: str = "mixed_ghost"
    clip_norm: float = 1.0
    clip_fn: str = "abadi"
    # taps whose params are frozen (no clipping/noise/coverage requirement)
    frozen_prefixes: tuple[str, ...] = ()
    # measured-cost branch plan (repro.tuner.ClipPlan, duck-typed to keep
    # core free of tuner imports).  Consulted before the analytic branch
    # rule; a plan whose device/shape fingerprint does not match the model
    # is rejected at trace time and the analytic rule applies.
    plan: Optional[Any] = None
    # clipping policy (repro.policies.ClipPolicy).  None builds the fixed
    # flat-R policy from (clip_norm, clip_fn) — exactly the pre-policy
    # behavior.  Stateful policies (quantile R) receive their state as the
    # executor's third argument.
    policy: Optional[Any] = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown clipping mode {self.mode!r}; have {MODES}")


def _plan_overrides(
    plan: Optional[Any], meta: dict[str, TapMeta], mode: str
) -> dict[str, str]:
    """Validated per-tap branch overrides from a tuner plan ({} if stale).

    Plans are mode-specific: the book-keeping branch trades bank size, not
    norm cost, so ``bk_mixed`` consumes a different branch map than
    ``mixed_ghost``.  ``plan.overrides_for`` must dispatch on the mode —
    a mode-blind plan object would silently drive bank-size decisions with
    norm-cost winners, so there is deliberately no fallback signature.
    """
    if plan is None:
        return {}
    return plan.overrides_for(meta, mode=mode)


def _plan_kernels(
    plan: Optional[Any], meta: dict[str, TapMeta]
) -> dict[str, dict[str, str]]:
    """Validated per-tap kernel-impl choices from a tuner plan ({} if stale).

    ``{tap: {op: "pallas" | "xla"}}`` routed to ``repro.kernels.dispatch``
    through the executors; plans predating v5 (no ``kernels_for``) and
    stale plans fall back to the dispatch backend default.
    """
    if plan is None:
        return {}
    fn = getattr(plan, "kernels_for", None)
    return fn(meta) if fn is not None else {}


def discover_meta(
    loss_with_ctx: LossFn, params: Any, batch: Any, clip: Optional[ClipRuntime] = None
) -> dict[str, TapMeta]:
    """Trace once abstractly to enumerate taps."""
    meta: dict[str, TapMeta] = {}

    def probe(p, b):
        ctx = Ctx(taps=None, meta=meta, clip=clip)
        return loss_with_ctx(p, b, ctx)

    jax.eval_shape(probe, params, batch)
    return meta


def validate_coverage(
    meta: dict[str, TapMeta], params: Any, frozen_prefixes: tuple[str, ...] = ()
) -> list[str]:
    """Every trainable param leaf must be covered by exactly one tap.

    Uncovered parameters would silently escape clipping — a privacy bug —
    so callers should raise unless the leaf is declared frozen.  Duplicate
    coverage (two taps claiming the same param leaf) would silently
    double-count that leaf's per-sample norm, inflating ||g_i|| and
    over-clipping — also a correctness bug — so it raises here directly,
    naming the offending taps.  Returns the sorted list of uncovered paths.
    """
    flat = flatten_dict(params)
    claimed: dict[str, list[str]] = {}
    for name, m in meta.items():
        claimed.setdefault(m.param_path, []).append(name)
        if m.bias_path:
            claimed.setdefault(m.bias_path, []).append(name)
    duplicates = {
        path: names for path, names in claimed.items() if len(names) > 1
    }
    if duplicates:
        detail = "; ".join(
            f"{path} <- taps {sorted(names)}" for path, names in sorted(duplicates.items())
        )
        raise ValueError(
            "duplicate per-sample clipping coverage (norms would be "
            f"double-counted): {detail}"
        )
    missing = []
    for path in flat:
        if path in claimed:
            continue
        if any(path.startswith(p) for p in frozen_prefixes):
            continue
        missing.append(path)
    return sorted(missing)


def _batch_mask(batch: Any) -> Optional[jax.Array]:
    if isinstance(batch, dict):
        return batch.get("mask")
    return None


def _assemble_bk_grads(
    meta: dict[str, TapMeta], params: Any, ws_fn: Callable
) -> Any:
    """Book-keeping gradient assembly from per-tap weighted gradients.

    ``ws_fn(name, m, param_shape)`` yields one tap's {path: weighted grad};
    uncovered leaves (frozen params) are zero-filled and everything is cast
    back to the leaf dtype.  Contributions to the same leaf are summed
    defensively, but two taps on one param leaf is a coverage bug —
    ``validate_coverage`` raises on it because the summed per-tap squared
    norms would drop the cross term.
    """
    flat_params = flatten_dict(params)
    flat_grads: dict[str, jax.Array] = {}
    for name, m in meta.items():
        ws = ws_fn(name, m, flat_params[m.param_path].shape)
        for path, val in ws.items():
            flat_grads[path] = (
                flat_grads[path] + val if path in flat_grads else val
            )
    for path, leaf in flat_params.items():
        if path not in flat_grads:
            flat_grads[path] = jnp.zeros_like(leaf)
        else:
            flat_grads[path] = flat_grads[path].astype(leaf.dtype)
    return unflatten_dict(flat_grads)


def _grouped_second_backward(st: "_NormState", c: Any, params: Any) -> Any:
    """Second-backward gradient stage under per-layer-group clip factors.

    The pullback cotangent is per-*sample* — one scalar weight per loss —
    so a factor that differs per layer group cannot ride a single second
    backward.  Run one pullback per group and keep each group's own leaves:
    correct for any G, at G x the second-backward cost.  Book-keeping does
    this for free (per-tap einsums); prefer it when G is large.
    """
    out: dict[str, jax.Array] = {}
    for gi in range(len(c.groups)):
        clipped = st.pull(c.factors[gi].astype(st.losses.dtype))[0]
        for path, val in flatten_dict(clipped).items():
            if c.group_index(path) == gi:
                out[path] = val
    return unflatten_dict(out)


@dataclasses.dataclass
class _NormState:
    """What the norms stage hands the gradient stage (one step's plumbing)."""

    losses: jax.Array
    norms2: jax.Array
    pull: Optional[Callable] = None  # vjp pullback (second-backward modes)
    banks: Optional[dict] = None  # per-tap probe cotangents (fused engine)
    acts: Optional[dict] = None  # explicit activations (late taps)
    gs: Optional[dict] = None  # explicit tap cotangents (late taps)
    meta: Optional[dict] = None
    # per-tap kernel-impl choices from the plan ({} = dispatch defaults)
    kernels: Optional[dict] = None
    per_sample_grads: Optional[Any] = None  # vmap oracle only
    # per-param-path squared norm contributions (grouped policies only):
    # {param_path: (B,)}, summing to norms2
    path_norms2: Optional[dict[str, jax.Array]] = None


class ClipExecutor:
    """Template for every clipping mode: norms -> clip factors -> gradients.

    Subclasses implement ``_norm_state`` and ``_weighted_grads``; the factor
    stage (delegated to the ClipPolicy) and the (loss, grads, aux) contract
    are shared.  Instances are plain callables: ``fn(params, batch,
    policy_state=None) -> (mean_loss, clipped_grad_sum, aux)`` with aux =
    {"per_sample_norms": (B,), "clip_factors": (B,)} — jit/pjit-safe, noise
    added downstream by the privacy engine.  ``policy_state`` is the pytree
    a stateful policy carries between steps (``policy.init_state()`` when
    omitted — correct for stateless policies, a fresh default otherwise).
    """

    def __init__(self, loss_with_ctx: LossFn, cfg: ClipConfig):
        self.loss = loss_with_ctx
        self.cfg = cfg
        if cfg.policy is not None:
            self.policy = cfg.policy
        else:
            from repro.policies.fixed import FixedPolicy

            self.policy = FixedPolicy(
                clip_norm=cfg.clip_norm, clip_fn=cfg.clip_fn
            )
        self.grouped = bool(getattr(self.policy, "grouped", False))

    # -- stage 1: mode-specific -------------------------------------------
    def _norm_state(self, params, batch) -> _NormState:
        raise NotImplementedError

    # -- stage 2: shared (policy-delegated) --------------------------------
    def _clip_factors(self, norms: jax.Array, mask, st: _NormState, pstate):
        c = self.policy.clip_factors(norms, pstate, path_norms2=st.path_norms2)
        if hasattr(c, "factors"):  # GroupedFactors
            f = c.factors
            if mask is not None:
                f = f * mask.astype(f.dtype)[None, :]
            return dataclasses.replace(c, factors=jax.lax.stop_gradient(f))
        if mask is not None:
            c = c * mask.astype(c.dtype)
        return jax.lax.stop_gradient(c)

    # -- stage 3: mode-specific -------------------------------------------
    def _weighted_grads(self, st: _NormState, c, params) -> Any:
        raise NotImplementedError

    def _validate_groups(self, meta: dict[str, TapMeta]) -> None:
        """A group boundary must not split a tap's (weight, bias) pair —
        their per-sample norm is computed jointly."""
        for name, m in meta.items():
            if m.bias_path is None:
                continue
            if self.policy.group_of(m.param_path) != self.policy.group_of(
                m.bias_path
            ):
                raise ValueError(
                    f"layer groups split tap {name!r}: weight "
                    f"{m.param_path!r} and bias {m.bias_path!r} land in "
                    "different groups but share one per-sample norm"
                )

    def __call__(self, params, batch, policy_state=None):
        mask = _batch_mask(batch)
        # the scopes are metadata only: they name each stage's ops, not change them
        with jax.named_scope(NORM_PASS_SCOPE):
            st = self._norm_state(params, batch)
            norms = jnp.sqrt(st.norms2)
            pstate = policy_state if policy_state is not None else self.policy.init_state()
            c = self._clip_factors(norms, mask, st, pstate)
        with jax.named_scope(SECOND_PASS_SCOPE):
            grads = self._weighted_grads(st, c, params)
        b = st.losses.shape[0]
        rep = c.representative if hasattr(c, "representative") else c
        aux = {"per_sample_norms": norms, "clip_factors": rep}
        return jnp.sum(st.losses) / b, grads, aux


class NonPrivateExecutor(ClipExecutor):
    """C_i = 1 for all i: plain summed gradients through the same skeleton."""

    def _norm_state(self, params, batch) -> _NormState:
        losses, pull = jax.vjp(
            lambda p: self.loss(p, batch, Ctx.disabled()), params
        )
        return _NormState(
            losses=losses,
            norms2=jnp.zeros((losses.shape[0],), jnp.float32),
            pull=pull,
        )

    def _clip_factors(self, norms, mask, st, pstate):
        return jnp.ones_like(norms)

    def _weighted_grads(self, st, c, params):
        (grads,) = st.pull(c.astype(st.losses.dtype))
        return grads


class VmapExecutor(ClipExecutor):
    """Opacus analogue and correctness oracle: vmap(grad) per sample."""

    def _norm_state(self, params, batch) -> _NormState:
        def single(p, ex):
            losses = self.loss(p, ex, Ctx.disabled())
            return losses[0]

        # add a singleton batch dim per sample
        per_ex = jax.tree_util.tree_map(lambda x: x[:, None], batch)
        losses, grads = jax.vmap(
            lambda ex: jax.value_and_grad(single, argnums=0)(params, ex)
        )(per_ex)
        path_norms2 = None
        if self.grouped:
            # same trace-time gate as the tap engines: a group boundary
            # through a tap's (weight, bias) pair would give this oracle
            # semantics no other executor can reproduce
            self._validate_groups(discover_meta(self.loss, params, batch))
            # exact per-leaf contributions: grouped policies sum them per
            # group, and weight/bias leaves fall into the same group as the
            # tap engines assign them (validated above)
            path_norms2 = {
                path: jnp.sum(
                    jnp.square(g.astype(jnp.float32)).reshape(g.shape[0], -1),
                    axis=-1,
                )
                for path, g in flatten_dict(grads).items()
            }
            norms2 = sum(path_norms2.values())
        else:
            flat, _ = jax.tree_util.tree_flatten(grads)
            norms2 = sum(
                jnp.sum(
                    jnp.square(g.astype(jnp.float32)).reshape(g.shape[0], -1),
                    axis=-1,
                )
                for g in flat
            )
        return _NormState(
            losses=losses, norms2=norms2, per_sample_grads=grads,
            path_norms2=path_norms2,
        )

    def _weighted_grads(self, st, c, params):
        if hasattr(c, "for_path"):  # GroupedFactors: per-leaf group factors
            flat = flatten_dict(st.per_sample_grads)
            out = {
                path: jnp.einsum(
                    "b...,b->...", g.astype(jnp.float32), c.for_path(path)
                ).astype(g.dtype)
                for path, g in flat.items()
            }
            return unflatten_dict(out)
        return jax.tree_util.tree_map(
            lambda g: jnp.einsum(
                "b...,b->...", g.astype(jnp.float32), c
            ).astype(g.dtype),
            st.per_sample_grads,
        )


def _fold_bank_norm(n: jax.Array, b: int) -> jax.Array:
    """Stacked (L..., B) per-sample norm cotangents -> (B,) sums."""
    return n.astype(jnp.float32).reshape(-1, b).sum(axis=0)


class FusedExecutor(ClipExecutor):
    """Probe engine: norms (and bk banks) computed inside the backward pass.

    Covers mixed_ghost (gradient stage = second backward over the shared
    pullback) and bk_mixed (gradient stage = bank einsums; the single
    backward is all the backpropagation there is).
    Taps registered with ``late=True`` (recurrent weights whose activation
    only exists after the time scan) fall back to the explicit-tap channel
    within the same pipeline.
    """

    def __init__(self, loss_with_ctx: LossFn, cfg: ClipConfig):
        super().__init__(loss_with_ctx, cfg)
        self.base_runtime = ClipRuntime(mode=cfg.mode)

    @property
    def is_bk(self) -> bool:
        return self.cfg.mode == "bk_mixed"

    def _norm_state(self, params, batch) -> _NormState:
        cfg = self.cfg
        meta = discover_meta(self.loss, params, batch, clip=self.base_runtime)
        overrides = _plan_overrides(cfg.plan, meta, cfg.mode)
        kernel_map = _plan_kernels(cfg.plan, meta)
        runtime = dataclasses.replace(
            self.base_runtime,
            overrides=tuple(sorted(overrides.items())),
            kernels=tuple(
                (name, tuple(sorted(ks.items())))
                for name, ks in sorted(kernel_map.items())
            ),
        )
        zs0 = {
            name: fused_mod.make_bank_zeros(
                fused_mod.bank_struct(m, mode=cfg.mode, override=overrides.get(name))
            )
            for name, m in meta.items() if m.fused
        }
        taps0 = make_zero_taps({n: m for n, m in meta.items() if not m.fused})

        def f(p, zs, taps):
            ctx = Ctx(taps=taps, zs=zs, meta={}, clip=runtime)
            losses = self.loss(p, batch, ctx)
            return losses, ctx.acts

        losses, pull, acts = jax.vjp(f, params, zs0, taps0, has_aux=True)
        b = losses.shape[0]
        ones = jnp.ones_like(losses)
        _, banks, gs_late = pull(ones)  # param grads DCE'd

        if self.grouped:
            self._validate_groups(meta)
        norms2 = jnp.zeros((b,), jnp.float32)
        path_norms2: Optional[dict[str, jax.Array]] = {} if self.grouped else None
        for name, m in meta.items():
            if m.fused:
                n = _fold_bank_norm(banks[name]["n"], b)
            else:
                n = ghost.tap_norm_sq(
                    m, acts.get(name), gs_late[name], mode=cfg.mode,
                    override=overrides.get(name), kernels=kernel_map.get(name),
                )
            norms2 = norms2 + n
            if path_norms2 is not None:
                path_norms2[m.param_path] = (
                    path_norms2[m.param_path] + n
                    if m.param_path in path_norms2 else n
                )
        return _NormState(
            losses=losses, norms2=norms2, pull=pull, banks=banks,
            acts=acts, gs=gs_late, meta=meta, path_norms2=path_norms2,
            kernels=kernel_map,
        )

    def _weighted_grads(self, st, c, params):
        grouped = hasattr(c, "for_path")
        if not self.is_bk:
            if grouped:
                return _grouped_second_backward(st, c, params)
            clipped, _, _ = st.pull(c.astype(st.losses.dtype))  # 2nd backward
            return clipped

        # book-keeping: direct einsums from the banks; nothing re-propagates.
        # Grouped policies are free here — each tap contracts against its own
        # group's factors.
        def ws_fn(name, m, param_shape):
            cw = c.for_path(m.param_path) if grouped else c
            kernels = (st.kernels or {}).get(name)
            if m.fused:
                return ghost.bank_weighted_grads(
                    m, st.banks[name], cw, param_shape, kernels=kernels
                )
            return ghost.tap_weighted_grads(
                m, st.acts.get(name), st.gs[name], cw, param_shape,
                kernels=kernels,
            )

        return _assemble_bk_grads(st.meta, params, ws_fn)


_EXECUTORS = {
    "non_private": NonPrivateExecutor,
    "vmap": VmapExecutor,
    "mixed_ghost": FusedExecutor,
    "bk_mixed": FusedExecutor,
}


def dp_value_and_clipped_grad(
    loss_with_ctx: LossFn,
    cfg: ClipConfig = ClipConfig(),
) -> Callable[..., tuple[jax.Array, Any, dict]]:
    """Returns fn(params, batch, policy_state=None) -> (mean_loss,
    clipped_grad_sum, aux).

    ``clipped_grad_sum`` is sum_i C_i g_i (noise is added by the optimizer /
    privacy engine; keeping it separate lets benchmarks isolate clipping).
    aux = {"per_sample_norms": (B,), "clip_factors": (B,)}.  The optional
    ``policy_state`` feeds a stateful ClipPolicy (``cfg.policy``); the
    policy's *update* runs outside this function (once per logical batch,
    see ``launch.steps``), so the executor stays a pure clipping map.
    An unknown ``cfg.mode`` raises ``ValueError`` when ``ClipConfig`` is
    built.
    """
    return _EXECUTORS[cfg.mode](loss_with_ctx, cfg)
