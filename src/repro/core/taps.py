"""The zero-tap mechanism: JAX's answer to PyTorch backward hooks.

The paper's algorithm needs, for every parameterized linear op
``s = U(a) @ W + b``, the pair ``(a_i, dL/ds_i)`` per sample.  PyTorch gets these
with forward/backward hooks.  In JAX we instead make every pre-activation an
explicit function of a zeros-valued *tap*::

    s = op(a, W) + b + tap[name]    # tap == 0, so forward is unchanged

and take one ``jax.vjp`` of the per-sample-loss function w.r.t. ``(params, taps)``.
The tap cotangents are exactly ``dL/ds`` per layer; activations are returned as
auxiliary outputs.  Pulling the same vjp back a *second* time with the clip
factors ``C_i`` as the cotangent of the per-sample losses yields the weighted
gradient ``sum_i C_i g_i`` — the paper's "second back-propagation" — while
reusing the forward residuals (1 forward + 2 backward total).

Tap kinds and their per-sample gradient semantics
-------------------------------------------------
- ``matmul``     s = a @ W (+ b);  a: (B, [G,] T, D), s: (B, [G,] T, p).
                 Per-sample grad ``g_i = a_i^T gs_i`` (D, p): ghost norm
                 (paper Eq. 2.7) or instantiation, per the layerwise decision.
                 G is an optional group dim (MoE experts, attention heads for
                 per-head mats); norms are summed over G.  Convolutions record
                 the *raw* input plus unfold info; the engine unfolds lazily
                 (im2col) so the forward stays on the fused conv op.
- ``bias``       handled as a flag on a host tap: per-sample grad = sum_T gs_i.
- ``scale``      s = x_hat * gamma (+ beta) (norm scales, SSM A/D vectors).
                 Per-sample grad = sum_T gs_i * x_hat_i  (elementwise).
- ``embedding``  s = E[ids].  Ghost norm via the index-equality Gram
                 (never materializes the (V, p) per-sample gradient).
- ``table``      s[b, c, k] = W[index[k], c]: a parameter table W (R, p)
                 gathered by a static index that every sample shares and
                 broadcast over the batch (BEiT's relative position bias:
                 (2*14-1)^2 + 3 = 732 rows x 16 heads, gathered to
                 (heads, 197, 197)); s: (B, p, T), T = positions gathered,
                 D = R rows, no recorded activation (``GatherInfo`` holds
                 the index).  Per-sample grad = the segment sum of gs_i into
                 W's rows (R, p), always instantiated: the index-equality
                 Gram would cost T^2 per sample.

Stacked layers (``ScannedStack``) register the same tap names with a leading
stack dimension; the engine folds stack dims into the layer-norm reduction
(per-sample norms sum over layers, Alg. 1 line "sum_l").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

TapKind = str  # "matmul" | "scale" | "embedding" | "table" | "bias" | ...


@dataclasses.dataclass(frozen=True)
class ConvInfo:
    """Unfold (im2col) parameters for convolution taps."""

    kernel: tuple[int, ...]  # spatial kernel dims, e.g. (kh, kw) or (k,)
    strides: tuple[int, ...]
    padding: Any  # str or tuple of (lo, hi) pairs
    feature_group_count: int = 1
    rhs_dilation: tuple[int, ...] | None = None


@dataclasses.dataclass(frozen=True, repr=False)
class GatherInfo:
    """Static row index of a ``table`` tap: position k reads row index[k]."""

    index: tuple[int, ...]

    def __repr__(self) -> str:
        return f"GatherInfo(n={len(self.index)}, rows={max(self.index) + 1})"


@dataclasses.dataclass(frozen=True)
class TapMeta:
    """Static metadata for one tap (trace-time only, hashable)."""

    kind: TapKind
    # Dimension parameters of the paper's complexity model (per layer instance):
    T: int  # positions per sample (H_out*W_out for conv, seq len for dense)
    D: int  # fan-in = d * prod(kernel)
    p: int  # fan-out
    s_shape: tuple[int, ...]  # full shape of the tapped pre-activation
    s_dtype: Any
    param_path: str  # param-tree path ("a/b/w") of the weight for this tap
    bias_path: Optional[str] = None  # set when the op has a bias param
    n_groups: int = 1  # group dim between B and T (MoE experts); norms sum over it
    stack_dims: tuple[int, ...] = ()  # leading dims added by ScannedStack
    conv: Optional[ConvInfo] = None
    gather: Optional[GatherInfo] = None  # ``table`` taps
    batch_size: int = 0
    # fused taps compute their norm (and, in book-keeping mode, the residuals
    # the weighted-grad einsum needs) inside the backward pass (core/fused.py)
    # and expose them as the cotangents of a dummy "bank" input
    fused: bool = False
    # shape/dtype of the recorded activation as the probe receives it
    # (embedding ids are fp32-cast before probing); None for late taps
    a_shape: Optional[tuple[int, ...]] = None
    a_dtype: Any = None

    def with_stack(self, n: int) -> "TapMeta":
        return dataclasses.replace(
            self,
            stack_dims=(n,) + self.stack_dims,
            s_shape=(n,) + tuple(self.s_shape),
            a_shape=(n,) + tuple(self.a_shape) if self.a_shape is not None else None,
        )

    @property
    def n_stack(self) -> int:
        out = 1
        for s in self.stack_dims:
            out *= s
        return out

    @property
    def batch_axis(self) -> int:
        """Axis of ``s_shape``/``a_shape`` carrying the batch dimension.

        0 for plain taps; ScannedStack prepends one stack dim per level, so
        stacked taps carry the batch right after them.  The static auditor
        (``repro.analysis``) uses this to locate each tap's sample axis in
        the traced jaxpr."""
        return len(self.stack_dims)


@dataclasses.dataclass(frozen=True)
class ClipRuntime:
    """Static knobs the fused probes need at trace time."""

    mode: str = "mixed_ghost"
    # measured-cost branch overrides from a tuner ClipPlan, as sorted
    # (tap_name, branch) pairs (tuple: ClipRuntime must stay hashable)
    overrides: tuple[tuple[str, str], ...] = ()
    # measured kernel-impl choices from a tuner ClipPlan, as sorted
    # (tap_name, ((op, impl), ...)) pairs routed to repro.kernels.dispatch;
    # empty = the dispatch backend default (pallas on TPU, xla elsewhere)
    kernels: tuple[tuple[str, tuple[tuple[str, str], ...]], ...] = ()

    def override_for(self, name: str) -> Optional[str]:
        for tap_name, branch in self.overrides:
            if tap_name == name:
                return branch
        return None

    def kernels_for(self, name: str) -> tuple[tuple[str, str], ...]:
        for tap_name, choices in self.kernels:
            if tap_name == name:
                return choices
        return ()


class Ctx:
    """Per-apply context threading taps in and activations out.

    Two engines:
    - fused (``clip`` set): each tap routes through a custom-vjp probe whose
      dummy *bank* input's cotangent carries the per-sample norm^2 — and, in
      book-keeping mode, the weighted-gradient residuals (core/fused.py).
      Nothing tap-sized ever escapes the backward pass except what the
      algorithm itself must bank.
    - explicit (``clip`` None, or a ``late=True`` tap): pre-activations get
      zero taps added and activations recorded; dL/ds comes back as tap
      cotangents.

    ``taps=None``/``zs=None`` means discovery mode (meta only).
    ``collect=False`` disables DP bookkeeping entirely (serving path).
    """

    __slots__ = ("taps", "zs", "acts", "meta", "path", "collect", "clip")

    def __init__(
        self,
        taps: Optional[dict[str, jax.Array]] = None,
        acts: Optional[dict[str, Any]] = None,
        meta: Optional[dict[str, TapMeta]] = None,
        path: str = "",
        collect: bool = True,
        zs: Optional[dict[str, jax.Array]] = None,
        clip: Optional[ClipRuntime] = None,
    ):
        self.taps = taps
        self.zs = zs
        self.acts = {} if acts is None else acts
        self.meta = {} if meta is None else meta
        self.path = path
        self.collect = collect
        self.clip = clip

    # -- scoping ---------------------------------------------------------
    def scope(self, name: str) -> "Ctx":
        return Ctx(self.taps, self.acts, self.meta, self._join(name),
                   self.collect, self.zs, self.clip)

    def _join(self, name: str) -> str:
        return f"{self.path}/{name}" if self.path else name

    # -- tap registration ------------------------------------------------
    def tap(
        self,
        name: str,
        s: jax.Array,
        *,
        kind: TapKind,
        a: Optional[jax.Array] = None,
        T: int,
        D: int,
        p: int,
        param_path: str,
        bias_path: Optional[str] = None,
        n_groups: int = 1,
        conv: Optional[ConvInfo] = None,
        gather: Optional[GatherInfo] = None,
        late: bool = False,
    ) -> jax.Array:
        """Register pre-activation ``s`` with recorded input ``a``.

        ``late=True`` forces the explicit-tap path even under the fused
        engine (recurrent weights whose activation only exists after the
        scan — see record_act).
        """
        if not self.collect:
            return s
        full = self._join(name)
        fused = self.clip is not None and not late
        meta = TapMeta(
            kind=kind,
            T=T,
            D=D,
            p=p,
            s_shape=tuple(int(d) for d in s.shape),
            s_dtype=s.dtype,
            param_path=self._join(param_path),
            bias_path=self._join(bias_path) if bias_path else None,
            n_groups=n_groups,
            conv=conv,
            gather=gather,
            batch_size=int(s.shape[0]),
            fused=fused,
            a_shape=tuple(int(d) for d in a.shape) if a is not None else None,
            a_dtype=(jnp.float32 if kind == "embedding" else a.dtype)
            if a is not None else None,
        )
        self.meta[full] = meta
        if fused:
            if self.zs is not None and full in self.zs:
                from repro.core.fused import ProbeSpec, make_probe

                a_p = a.astype(jnp.float32) if kind == "embedding" else a
                probe = make_probe(
                    ProbeSpec(
                        meta=meta,
                        branch_mode=self.clip.mode,
                        override=self.clip.override_for(full),
                        kernels=self.clip.kernels_for(full),
                    )
                )
                s = probe(s, a_p, self.zs[full])
            return s
        if a is not None:
            self.acts[full] = a
        if self.taps is not None:
            tap = self.taps.get(full)
            if tap is not None:
                s = s + tap.astype(s.dtype)
        return s

    def record_act(self, name: str, a: jax.Array) -> None:
        """Late activation recording for taps registered with ``a=None``.

        Used for recurrent weights: the tap is added to the *input stream* of a
        time scan (addition commutes into the scan, so the tap cotangent is
        still dL/ds_t), while the recorded activation (h_{t-1}, emitted by the
        scan) only exists afterwards.
        """
        if self.collect:
            self.acts[self._join(name)] = a

    @staticmethod
    def disabled() -> "Ctx":
        return Ctx(taps=None, collect=False)


def make_zero_taps(meta: dict[str, TapMeta]) -> dict[str, jax.Array]:
    """Build the zeros tap pytree from discovered metadata."""
    return {name: jnp.zeros(m.s_shape, m.s_dtype) for name, m in meta.items()}


def tap_specs(meta: dict[str, TapMeta]) -> dict[str, jax.ShapeDtypeStruct]:
    return {
        name: jax.ShapeDtypeStruct(m.s_shape, m.s_dtype) for name, m in meta.items()
    }
