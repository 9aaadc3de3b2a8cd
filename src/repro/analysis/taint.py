"""Batch-axis taint propagation over jaxprs: the per-sample isolation pass.

The ghost/book-keeping norms of Algorithm 1 are only the true per-sample
gradient norms when the traced computation is *batch-diagonal*: sample i's
data influences tap pre-activation rows ``s[i]`` and loss ``L_i`` only.  By
linearity of the vjp, forward diagonality is equivalent to cotangent
diagonality (``dL_i/ds_j = 0`` for ``i != j`` iff no forward path carries
sample j into ``L_i``), so ONE abstract forward pass over the explicit-tap
jaxpr certifies both halves of every tap's (activation, cotangent) pair —
see docs/ARCHITECTURE.md "Static analysis" for the full argument.

The abstract value per jaxpr var is a :class:`Taint`:

- ``None``            CLEAN — no sample data flows here (params, constants).
- ``Taint(axis=k)``   samples ride axis ``k``; element ``i`` of that axis is
                      a function of sample ``i`` (and clean inputs) only.
- ``Taint(axis=None)``MIXED — some eqn combined samples; ``trail`` records
                      the originating eqn plus the propagation path (capped).

Per-primitive transfer rules keep the axis through shape ops, drop it through
batch-axis reductions/contractions/scans, and understand the
``operand_batching_dims`` that jax >= 0.4.31 emits for vmapped
gather/scatter (what proves the MoE per-sample dispatch block-isolated).
Unknown primitives are *conservative*: any tainted input makes the output
MIXED with an "unknown primitive" trail, so gaps fail loudly instead of
certifying silently.

Scatters whose write positions are themselves sample-derived (the MoE slot
table) are block-isolated but order-sensitive under collisions — proving the
recorded activations faithful needs the value-level occupancy invariant the
lattice cannot express, so they are surfaced separately as *routed* sites
for the per-config allowlist (``repro.analysis.allowlist``).

This module walks jax internals (``jax._src.core``).  Call-like primitives
(``jit``, remat, custom-derivative calls) are recognised by structure — see
:func:`call_body` — so a renamed primitive does not silently fall through to
the conservative fallback.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from jax._src import source_info_util as _siu
from jax._src.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal, Var

TRAIL_CAP = 8
_SCAN_FIXPOINT_CAP = 16


@dataclasses.dataclass(frozen=True)
class Taint:
    """Batch-axis location (``axis``) or sample-mixedness (``axis=None``)."""

    axis: Optional[int]
    trail: tuple[str, ...] = ()

    @property
    def mixed(self) -> bool:
        return self.axis is None


def eqn_summary(eqn: JaxprEqn) -> str:
    """One-line human-locatable eqn identity: prim, shapes, source site."""
    ins = ",".join(
        "x".join(map(str, getattr(a.aval, "shape", ()))) for a in eqn.invars
    )
    outs = ",".join(
        "x".join(map(str, getattr(v.aval, "shape", ()))) for v in eqn.outvars
    )
    return f"{eqn.primitive.name}[{ins}->{outs}] @ {_siu.summarize(eqn.source_info)}"


# control-flow primitives with their own transfer rules (their sub-jaxprs
# do not map one-to-one onto the eqn's operands)
CONTROL_PRIMS = frozenset({"scan", "while", "cond"})


def call_body(eqn: JaxprEqn) -> Optional[Jaxpr]:
    """The body of a call-like eqn, or None.

    A call is an eqn carrying one sub-jaxpr whose invars and outvars map
    one-to-one onto the eqn's own (``jit``, remat/checkpoint,
    ``custom_jvp_call``/``custom_vjp_call``, ...).  Recognising calls by
    this structure rather than by primitive name keeps the passes exact
    across jax releases that rename them (``pjit`` became ``jit``).
    """
    if eqn.primitive.name in CONTROL_PRIMS:
        return None
    for sub in eqn.params.values():
        body = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
        if (
            isinstance(body, Jaxpr)
            and len(body.invars) == len(eqn.invars)
            and len(body.outvars) == len(eqn.outvars)
        ):
            return body
    return None


@dataclasses.dataclass
class TapSite:
    """One tap-add eqn: where a zero tap joins its pre-activation."""

    tap: str
    taint: Optional[Taint]  # taint of the pre-activation operand
    summary: str
    eqn: JaxprEqn
    jaxpr: Jaxpr  # the (sub)jaxpr the add lives in — coverage cuts start here


@dataclasses.dataclass
class RoutedSite:
    """A scatter with sample-derived write positions (MoE slot tables)."""

    summary: str
    taint: Optional[Taint]
    isolated: bool  # True when batching dims confine writes per sample


@dataclasses.dataclass
class TaintResult:
    out_taints: list  # one Optional[Taint] per top-level outvar
    sites: list  # TapSite, deduped per add eqn
    routed: list  # RoutedSite, deduped per scatter eqn
    unknown_prims: list  # sorted prim names hit by the conservative fallback


# primitives that reduce over params["axes"]
_REDUCE_PRIMS = frozenset(
    {
        "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
        "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    }
)
_CUM_PRIMS = frozenset(
    {"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}
)
_SCATTER_PRIMS = frozenset(
    {"scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"}
)
# tapness (the identity of a zero tap) survives these before its add
_TAP_TRANSPARENT = frozenset(
    {"convert_element_type", "broadcast_in_dim", "reshape", "transpose", "copy"}
)


def _worse(a: Optional[Taint], b: Optional[Taint]) -> Optional[Taint]:
    """Severity order for site dedup across scan fixpoint iterations."""
    if a is None:
        return b
    if b is None:
        return a
    return a if (a.mixed or not b.mixed) else b


class TaintInterpreter:
    """Abstract forward interpreter; one instance per traced model."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self._sites: dict[int, TapSite] = {}
        self._routed: dict[int, RoutedSite] = {}
        self._unknown: set[str] = set()

    # -- public ----------------------------------------------------------
    def run(
        self,
        closed: ClosedJaxpr,
        in_taints: list,
        in_taps: list,
    ) -> TaintResult:
        outs, _ = self._run_jaxpr(closed.jaxpr, in_taints, in_taps)
        return TaintResult(
            out_taints=outs,
            sites=list(self._sites.values()),
            routed=list(self._routed.values()),
            unknown_prims=sorted(self._unknown),
        )

    # -- environment helpers ---------------------------------------------
    @staticmethod
    def _read(env: dict, atom: Any) -> Optional[Taint]:
        return None if isinstance(atom, Literal) else env.get(atom)

    def _mix(
        self, eqn: JaxprEqn, parents: list, why: str
    ) -> Taint:
        """A mixed taint whose trail extends the first mixed parent's."""
        base: tuple[str, ...] = ()
        extra = 0
        for t in parents:
            if t is not None and t.trail:
                if not base:
                    base = t.trail
                else:
                    extra += 1
        here = eqn_summary(eqn) + (f" ({why})" if why else "")
        if extra:
            here += f" [+{extra} more tainted sources]"
        trail = base + (here,) if len(base) < TRAIL_CAP else base
        return Taint(None, trail)

    def _join_elementwise(self, eqn: JaxprEqn, in_t: list) -> Optional[Taint]:
        live = [t for t in in_t if t is not None]
        if not live:
            return None
        axes = {t.axis for t in live if not t.mixed}
        return self._join(eqn, live, axes)

    def _join(
        self, eqn: JaxprEqn, taints: list, axes: set
    ) -> Optional[Taint]:
        """Join already-mapped output axes; conflicting axes mean the eqn
        pairs two different sample axes in one value (an outer product over
        the batch) — mixed."""
        live = [t for t in taints if t is not None]
        if not live and not axes:
            return None
        if any(t.mixed for t in live):
            return self._mix(eqn, live, "propagates mixed input")
        if len(axes) > 1:
            return self._mix(eqn, live, "pairs two sample axes")
        if not axes:
            return None
        trail = next((t.trail for t in live if t.trail), ())
        return Taint(axes.pop(), trail)

    # -- jaxpr traversal -------------------------------------------------
    def _run_jaxpr(
        self, jaxpr: Jaxpr, in_taints: list, in_taps: list
    ) -> tuple[list, list]:
        env: dict[Var, Taint] = {}
        taps: dict[Var, str] = {}
        for v, t in zip(jaxpr.invars, in_taints):
            if t is not None:
                env[v] = t
        for v, name in zip(jaxpr.invars, in_taps):
            if name is not None:
                taps[v] = name
        # constvars carry trace-time constants: clean by construction (the
        # audit passes params/taps/batch as arguments, never via closure)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, taps, jaxpr)
        out_t = [self._read(env, v) for v in jaxpr.outvars]
        out_taps = [
            taps.get(v) if isinstance(v, Var) else None for v in jaxpr.outvars
        ]
        return out_t, out_taps

    # -- per-eqn dispatch ------------------------------------------------
    def _eqn(
        self, eqn: JaxprEqn, env: dict, taps: dict, jaxpr: Jaxpr
    ) -> None:
        prim = eqn.primitive.name
        in_t = [self._read(env, a) for a in eqn.invars]
        in_tap = [
            taps.get(a) if isinstance(a, Var) else None for a in eqn.invars
        ]

        # tap-add site: exactly one operand is a (possibly cast/sliced) zero
        # tap; the other is the pre-activation whose diagonality we certify
        if prim == "add" and sum(n is not None for n in in_tap) == 1:
            k = 0 if in_tap[0] is not None else 1
            name = in_tap[k]
            site = TapSite(
                tap=name,
                taint=in_t[1 - k],
                summary=eqn_summary(eqn),
                eqn=eqn,
                jaxpr=jaxpr,
            )
            old = self._sites.get(id(eqn))
            if old is None or _worse(old.taint, site.taint) is site.taint:
                self._sites[id(eqn)] = site
            # the sum is the real pre-activation stream; tapness is consumed
            self._set_out(eqn, env, taps, self._join_elementwise(eqn, in_t))
            return

        if prim in _TAP_TRANSPARENT and in_tap[0] is not None:
            taps[eqn.outvars[0]] = in_tap[0]

        out = self._rule(prim, eqn, env, taps, in_t, in_tap)
        if out is not _HANDLED:
            self._set_out(eqn, env, taps, out)

    def _set_out(self, eqn: JaxprEqn, env: dict, taps: dict, out: Any) -> None:
        """Assign taints to outvars; ``out`` is one taint (broadcast to all
        outvars) or a list aligned with them."""
        if not isinstance(out, list):
            out = [out] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out):
            if t is not None:
                env[v] = t

    # -- transfer rules --------------------------------------------------
    def _rule(
        self,
        prim: str,
        eqn: JaxprEqn,
        env: dict,
        taps: dict,
        in_t: list,
        in_tap: list,
    ) -> Any:
        if all(t is None for t in in_t):
            # clean in, clean out — except subjaxpr prims, which may need
            # tapness threaded (a tap slice rides scan xs while clean)
            if (prim not in CONTROL_PRIMS and call_body(eqn) is None) or all(
                n is None for n in in_tap
            ):
                return None

        if prim == "broadcast_in_dim":
            t = in_t[0]
            if t is None or t.mixed:
                return t
            bdims = tuple(eqn.params["broadcast_dimensions"])
            return Taint(bdims[t.axis], t.trail)

        if prim == "reshape":
            return self._reshape(eqn, in_t[0])

        if prim == "transpose":
            t = in_t[0]
            if t is None or t.mixed:
                return t
            perm = tuple(eqn.params["permutation"])
            return Taint(perm.index(t.axis), t.trail)

        if prim == "squeeze":
            t = in_t[0]
            if t is None or t.mixed:
                return t
            dims = tuple(eqn.params["dimensions"])
            if t.axis in dims:
                return self._mix(eqn, [t], "squeezes the batch axis")
            return Taint(
                t.axis - sum(1 for d in dims if d < t.axis), t.trail
            )

        if prim in _REDUCE_PRIMS:
            t = in_t[0]
            if t is None or t.mixed:
                return t
            axes = tuple(eqn.params["axes"])
            if t.axis in axes:
                return self._mix(eqn, [t], "reduces over the batch axis")
            return Taint(t.axis - sum(1 for ax in axes if ax < t.axis), t.trail)

        if prim in _CUM_PRIMS:
            t = in_t[0]
            if t is None or t.mixed:
                return t
            if eqn.params["axis"] == t.axis:
                return self._mix(eqn, [t], "cumulates over the batch axis")
            return t

        if prim == "dot_general":
            return self._dot_general(eqn, in_t)

        if prim == "conv_general_dilated":
            return self._conv(eqn, in_t)

        if prim == "gather":
            return self._gather(eqn, in_t)

        if prim in _SCATTER_PRIMS:
            return self._scatter(eqn, in_t)

        if prim == "concatenate":
            dim = eqn.params["dimension"]
            axes = set()
            for t in in_t:
                if t is not None and not t.mixed:
                    if t.axis == dim:
                        return self._mix(
                            eqn, in_t, "concatenates along the batch axis"
                        )
                    axes.add(t.axis)
            return self._join(eqn, in_t, axes)

        if prim == "slice":
            t = in_t[0]
            if t is None or t.mixed:
                return t
            start = eqn.params["start_indices"][t.axis]
            limit = eqn.params["limit_indices"][t.axis]
            strides = eqn.params["strides"]
            stride = 1 if strides is None else strides[t.axis]
            full = eqn.invars[0].aval.shape[t.axis]
            if start == 0 and limit == full and stride == 1:
                return t
            return self._mix(eqn, [t], "slices a subrange of the batch axis")

        if prim == "dynamic_slice":
            t = in_t[0]
            if any(x is not None for x in in_t[1:]):
                return self._mix(eqn, in_t, "sample-dependent slice start")
            if t is None or t.mixed:
                return t
            if eqn.params["slice_sizes"][t.axis] == eqn.invars[0].aval.shape[t.axis]:
                return t
            return self._mix(eqn, [t], "dynamic-slices the batch axis")

        if prim == "dynamic_update_slice":
            op_t, upd_t = in_t[0], in_t[1]
            if any(x is not None for x in in_t[2:]):
                return self._mix(eqn, in_t, "sample-dependent update position")
            if upd_t is not None and (
                upd_t.mixed
                or tuple(eqn.invars[1].aval.shape) != tuple(eqn.invars[0].aval.shape)
            ):
                return self._mix(
                    eqn, in_t, "partial update into a sample-carrying buffer"
                ) if (op_t is not None or upd_t is not None) else None
            axes = {
                t.axis for t in (op_t, upd_t) if t is not None and not t.mixed
            }
            return self._join(eqn, in_t, axes)

        if prim == "pad":
            t = in_t[0]
            if in_t[1] is not None:  # padding value tainted: scalar -> mixed
                return self._mix(eqn, in_t, "sample-dependent pad value")
            if t is None or t.mixed:
                return t
            lo, hi, interior = eqn.params["padding_config"][t.axis]
            if lo == 0 and hi == 0 and interior == 0:
                return t
            return self._mix(eqn, [t], "pads the batch axis")

        if prim == "rev":
            t = in_t[0]
            if t is None or t.mixed:
                return t
            if t.axis in tuple(eqn.params["dimensions"]):
                return self._mix(eqn, [t], "reverses the batch axis")
            return t

        if prim == "sort":
            dim = eqn.params["dimension"]
            axes = set()
            for t in in_t:
                if t is None:
                    continue
                if t.mixed:
                    return [self._mix(eqn, in_t, "")] * len(eqn.outvars)
                if t.axis == dim:
                    return [
                        self._mix(eqn, in_t, "sorts along the batch axis")
                    ] * len(eqn.outvars)
                axes.add(t.axis)
            return [self._join(eqn, in_t, set(axes))] * len(eqn.outvars)

        if prim == "top_k":
            t = in_t[0]
            if t is None or t.mixed:
                return [t, t]
            last = len(eqn.invars[0].aval.shape) - 1
            if t.axis == last:
                m = self._mix(eqn, [t], "selects top-k over the batch axis")
                return [m, m]
            return [t, t]

        if prim == "split":
            t = in_t[0]
            if t is None or t.mixed:
                return [t] * len(eqn.outvars)
            if eqn.params.get("axis") == t.axis:
                m = self._mix(eqn, [t], "splits the batch axis")
                return [m] * len(eqn.outvars)
            return [t] * len(eqn.outvars)

        if prim == "scan":
            return self._scan(eqn, in_t, in_tap)

        if prim == "while":
            return self._while(eqn, in_t)

        if prim == "cond":
            return self._cond(eqn, in_t, in_tap)

        body = call_body(eqn)
        if body is not None:
            outs, out_taps = self._run_jaxpr(body, in_t, in_tap)
            for v, name in zip(eqn.outvars, out_taps):
                if name is not None:
                    taps[v] = name
            return outs

        # elementwise fallback: covers every elementwise/unary primitive
        # (add, mul, exp, select_n, compares, convert_element_type, ...)
        # without enumerating them, including lax's rank-matching size-1
        # broadcasting (keepdims stats in the norms).  Safe because
        # shape-preserving prims that PERMUTE the distinguished axis
        # (rev, sort) were handled above; anything else maps element i ->
        # element i along every full-size axis.
        tainted = [
            (a, t) for a, t in zip(eqn.invars, in_t) if t is not None
        ]
        if not tainted:
            return None
        out_shape = tuple(eqn.outvars[0].aval.shape)
        if all(tuple(v.aval.shape) == out_shape for v in eqn.outvars):
            axes: set = set()
            applicable = True
            for a, t in tainted:
                if t.mixed:
                    continue
                s = tuple(a.aval.shape)
                if len(s) != len(out_shape) or any(
                    d != o and d != 1 for d, o in zip(s, out_shape)
                ):
                    applicable = False
                    break
                if s[t.axis] == out_shape[t.axis]:
                    axes.add(t.axis)
                else:
                    # a size-1 "batch" axis broadcast up: cannot be the
                    # real batch; conservative
                    return [
                        self._mix(eqn, in_t, "broadcasts the batch axis")
                    ] * len(eqn.outvars)
            if applicable:
                return [self._join(eqn, in_t, axes)] * len(eqn.outvars)

        # conservative: unknown primitive with tainted inputs
        self._unknown.add(prim)
        m = self._mix(
            eqn, in_t, f"no transfer rule for primitive {prim!r} (conservative)"
        )
        return [m] * len(eqn.outvars)

    # -- structured primitives -------------------------------------------
    def _reshape(self, eqn: JaxprEqn, t: Optional[Taint]) -> Optional[Taint]:
        if t is None or t.mixed:
            return t
        if eqn.params.get("dimensions") is not None:
            return self._mix(eqn, [t], "reshape with permutation")
        src = tuple(eqn.invars[0].aval.shape)
        dst = tuple(eqn.params["new_sizes"])
        # the batch dim survives as a unit iff some out axis has the same
        # size AND the same prefix product (position) — splitting or merging
        # it folds samples into another axis
        pre = 1
        for d in src[: t.axis]:
            pre *= d
        acc = 1
        for b, d in enumerate(dst):
            if acc == pre and d == src[t.axis]:
                return Taint(b, t.trail)
            acc *= d
        return self._mix(
            eqn, [t], "reshape merges/splits the batch axis"
        )

    def _dot_general(self, eqn: JaxprEqn, in_t: list) -> Optional[Taint]:
        lhs_t, rhs_t = in_t[0], in_t[1]
        if (lhs_t is not None and lhs_t.mixed) or (
            rhs_t is not None and rhs_t.mixed
        ):
            return self._mix(eqn, in_t, "propagates mixed input")
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        rhs_shape = eqn.invars[1].aval.shape
        axes = set()

        def free_out_axis(shape, contract, batch, axis, offset):
            free = [
                d
                for d in range(len(shape))
                if d not in contract and d not in batch
            ]
            return len(lb) + offset + free.index(axis)

        for t, contract, batch, shape, is_lhs in (
            (lhs_t, tuple(lc), tuple(lb), lhs_shape, True),
            (rhs_t, tuple(rc), tuple(rb), rhs_shape, False),
        ):
            if t is None:
                continue
            if t.axis in contract:
                return self._mix(
                    eqn, in_t, "contracts over the batch axis"
                )
            if t.axis in batch:
                axes.add(batch.index(t.axis))
                continue
            # a free sample axis on BOTH operands would pair samples
            offset = 0
            if not is_lhs:
                offset = len(
                    [
                        d
                        for d in range(len(lhs_shape))
                        if d not in tuple(lc) and d not in tuple(lb)
                    ]
                )
            axes.add(free_out_axis(shape, contract, batch, t.axis, offset))
        return self._join(eqn, in_t, axes)

    def _conv(self, eqn: JaxprEqn, in_t: list) -> Optional[Taint]:
        lhs_t, rhs_t = in_t[0], in_t[1]
        if rhs_t is not None:
            return self._mix(eqn, in_t, "sample data in convolution weights")
        if lhs_t is None or lhs_t.mixed:
            return lhs_t
        dn = eqn.params["dimension_numbers"]
        if lhs_t.axis == dn.lhs_spec[0]:
            return Taint(dn.out_spec[0], lhs_t.trail)
        return self._mix(
            eqn, [lhs_t], "convolves over a sample-carrying axis"
        )

    def _gather(self, eqn: JaxprEqn, in_t: list) -> Optional[Taint]:
        op_t, idx_t = in_t[0], in_t[1]
        if (op_t is not None and op_t.mixed) or (
            idx_t is not None and idx_t.mixed
        ):
            return self._mix(eqn, in_t, "propagates mixed input")
        dn = eqn.params["dimension_numbers"]
        operand = eqn.invars[0].aval
        indices = eqn.invars[1].aval
        out_rank = len(eqn.outvars[0].aval.shape)
        offset_dims = tuple(int(d) for d in dn.offset_dims)
        obd = tuple(int(d) for d in getattr(dn, "operand_batching_dims", ()))
        sbd = tuple(
            int(d) for d in getattr(dn, "start_indices_batching_dims", ())
        )
        non_offset_out = [d for d in range(out_rank) if d not in offset_dims]
        axes = set()
        if op_t is not None:
            a = op_t.axis
            if a in obd:
                # vmapped gather: reads are confined to the matching block
                axes.add(non_offset_out[sbd[obd.index(a)]])
            else:
                sim = tuple(int(d) for d in dn.start_index_map)
                csd = tuple(int(d) for d in dn.collapsed_slice_dims)
                slice_sizes = tuple(eqn.params["slice_sizes"])
                if (
                    a not in sim
                    and a not in csd
                    and slice_sizes[a] == operand.shape[a]
                ):
                    kept = [
                        d
                        for d in range(len(operand.shape))
                        if d not in csd and d not in obd
                    ]
                    axes.add(offset_dims[kept.index(a)])
                else:
                    return self._mix(
                        eqn, in_t, "gathers across the batch axis"
                    )
        if idx_t is not None:
            j = idx_t.axis
            if j == len(indices.shape) - 1:
                return self._mix(
                    eqn, in_t, "sample data in the gather index vector"
                )
            axes.add(non_offset_out[j])
        return self._join(eqn, in_t, axes)

    def _scatter(self, eqn: JaxprEqn, in_t: list) -> Optional[Taint]:
        op_t, idx_t, upd_t = in_t[0], in_t[1], in_t[2]
        if any(t is not None and t.mixed for t in in_t):
            return self._mix(eqn, in_t, "propagates mixed input")
        dn = eqn.params["dimension_numbers"]
        indices = eqn.invars[1].aval
        obd = tuple(int(d) for d in getattr(dn, "operand_batching_dims", ()))
        sibd = tuple(
            int(d) for d in getattr(dn, "scatter_indices_batching_dims", ())
        )
        uwd = tuple(int(d) for d in dn.update_window_dims)
        axes = set()
        if op_t is not None:
            axes.add(op_t.axis)  # operand axes are preserved in the output
        if idx_t is not None:
            j = idx_t.axis
            if j == len(indices.shape) - 1 or j not in sibd:
                return self._mix(
                    eqn, in_t, "sample-dependent scatter positions without "
                    "batching isolation"
                )
            axes.add(obd[sibd.index(j)])
        if upd_t is not None:
            u = upd_t.axis
            if u in uwd:
                return self._mix(
                    eqn, in_t, "sample axis inside a scattered window"
                )
            scatter_batch = [
                d
                for d in range(len(eqn.invars[2].aval.shape))
                if d not in uwd
            ]
            k = scatter_batch.index(u)  # k-th non-last indices dim
            if k in sibd:
                axes.add(obd[sibd.index(k)])
            else:
                return self._mix(
                    eqn, in_t, "sample updates at data-dependent positions"
                )
        out = self._join(eqn, in_t, axes)
        if idx_t is not None:
            # block-isolated, but which of a sample's updates survives a slot
            # collision is a value-level invariant: surface for the allowlist
            site = RoutedSite(
                summary=eqn_summary(eqn),
                taint=out,
                isolated=out is not None and not out.mixed,
            )
            self._routed.setdefault(id(eqn), site)
        return out

    def _scan(self, eqn: JaxprEqn, in_t: list, in_tap: list) -> list:
        p = eqn.params
        closed: ClosedJaxpr = p["jaxpr"]
        body = closed.jaxpr
        nc, ncar = p["num_consts"], p["num_carry"]
        n_xs = len(eqn.invars) - nc - ncar
        consts_t = in_t[:nc]
        carry_t = list(in_t[nc : nc + ncar])
        xs_t = in_t[nc + ncar :]
        xs_body_t: list = []
        for t in xs_t:
            if t is None or t.mixed:
                xs_body_t.append(t)
            elif t.axis == 0:
                xs_body_t.append(
                    self._mix(eqn, [t], "scans over the batch axis")
                )
            else:
                xs_body_t.append(Taint(t.axis - 1, t.trail))
        body_taps = list(in_tap[:nc]) + [None] * ncar + list(
            in_tap[nc + ncar :]
        )
        out_t: list = [None] * len(body.outvars)
        for _ in range(_SCAN_FIXPOINT_CAP):
            out_t, _ = self._run_jaxpr(
                body, consts_t + carry_t + xs_body_t, body_taps
            )
            new_carry = []
            changed = False
            for cur, nxt in zip(carry_t, out_t[:ncar]):
                joined = self._join_carry(cur, nxt)
                changed = changed or joined != cur
                new_carry.append(joined)
            carry_t = new_carry
            if not changed:
                break
        ys_out = []
        for t in out_t[ncar:]:
            if t is None or t.mixed:
                ys_out.append(t)
            else:
                ys_out.append(Taint(t.axis + 1, t.trail))
        del n_xs
        return carry_t + ys_out

    @staticmethod
    def _join_carry(a: Optional[Taint], b: Optional[Taint]) -> Optional[Taint]:
        if a is None:
            return b
        if b is None:
            return a
        if a.mixed:
            return a
        if b.mixed:
            return b
        if a.axis == b.axis:
            return a
        return Taint(None, a.trail + (f"carry axis conflict {a.axis}/{b.axis}",))

    def _while(self, eqn: JaxprEqn, in_t: list) -> list:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body: ClosedJaxpr = p["body_jaxpr"]
        cond: ClosedJaxpr = p["cond_jaxpr"]
        body_consts = in_t[cn : cn + bn]
        carry_t = list(in_t[cn + bn :])
        for _ in range(_SCAN_FIXPOINT_CAP):
            out_t, _ = self._run_jaxpr(
                body.jaxpr, body_consts + carry_t, [None] * (bn + len(carry_t))
            )
            new_carry = [
                self._join_carry(a, b) for a, b in zip(carry_t, out_t)
            ]
            if new_carry == carry_t:
                break
            carry_t = new_carry
        pred_t, _ = self._run_jaxpr(
            cond.jaxpr,
            in_t[:cn] + carry_t,
            [None] * (cn + len(carry_t)),
        )
        if any(t is not None for t in pred_t):
            m = self._mix(eqn, in_t, "sample-dependent while trip count")
            return [m] * len(eqn.outvars)
        return carry_t

    def _cond(self, eqn: JaxprEqn, in_t: list, in_tap: list) -> list:
        branches = eqn.params["branches"]
        pred_t = in_t[0]
        op_t = in_t[1:]
        op_tap = in_tap[1:]
        if pred_t is not None:
            m = self._mix(eqn, in_t, "sample-dependent branch predicate")
            return [m] * len(eqn.outvars)
        outs: list = [None] * len(eqn.outvars)
        for br in branches:
            b_out, _ = self._run_jaxpr(br.jaxpr, list(op_t), list(op_tap))
            outs = [self._join_carry(a, b) for a, b in zip(outs, b_out)]
        return outs


_HANDLED = object()
