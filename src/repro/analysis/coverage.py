"""Gradient-path coverage: every trainable leaf's cotangent crosses a tap.

``validate_coverage`` (src/repro/core/clipping.py:166) checks the *declared*
map — each param leaf appears in some TapMeta's ``param_path``/``bias_path``.
This module checks the complement against the actual computation graph: in
the traced jaxpr, does each claimed leaf's gradient really flow through the
eqn where its tap's zero array is added, and does any unclaimed leaf reach
the loss at all?

Method: reverse liveness over the forward jaxpr.  The cotangent of a var is
nonzero only if the var (transitively) feeds the loss, so gradient paths are
exactly the data-dependence paths restricted to inexact (float/complex)
dtypes — integer/bool vars have no tangent space, which is what lets router
argmax/top_k index paths (real data dependence, zero cotangent) not count
as gradient bypasses.

Cut sets: a tap intercepts the cotangent at its add eqn's output.  When that
output has a *single* use and the use preserves cotangent determination
(add/sub — the captured dL/dw equals dL/dv; cast, transpose, reshape —
linear bijections; a scan xs operand — the per-step body cotangent), the
downstream var's cotangent is determined by the captured one too, so it
joins the cut set.  This chain is what covers recurrent late taps: xlstm
adds the tap to the scan *input stream* (``src/repro/nn/xlstm.py``) and the
true pre-activation ``s = pre_t + h @ wr`` only exists inside the scan body.

Tap kinds need no rules of their own here: a ``table`` tap (BEiT's relative
position bias) adds its zeros after the gather and the broadcast over the
batch, so the table's one route to the loss runs through that add, as a
``bias`` tap's (the CLS token) runs through its broadcast.

Per-claim passes are deliberate: one global all-cuts pass would let an
untapped middle layer hide behind a downstream tap's cut, so each tap's
claimed leaves are tested against that tap's cuts alone.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.analysis.taint import ClosedJaxpr, Jaxpr, JaxprEqn, Var, call_body  # noqa: F401

# single-use eqns through which a captured cotangent stays determined
_CHAIN_PRIMS = frozenset(
    {"add", "sub", "convert_element_type", "transpose", "reshape"}
)


def _grad_carrying(v) -> bool:
    dtype = getattr(getattr(v, "aval", None), "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.inexact)


class ForwardUses:
    """Forward-use index over a jaxpr and all sub-jaxprs.

    ``ident`` edges are var->var hops whose cotangent relation is the
    identity (call-boundary plumbing, scan xs slicing, scan ys stacking);
    ``eqn_uses`` are ordinary consuming eqns; ``stop_uses`` counts uses the
    cut chain must not cross (scan consts/carries, cond/while operands,
    loss/act outputs).
    """

    def __init__(self, jaxpr: Jaxpr):
        self.eqn_uses: dict[Var, list[JaxprEqn]] = {}
        self.ident: dict[Var, list[Var]] = {}
        self.stop_uses: dict[Var, int] = {}
        self._walk(jaxpr)
        for v in jaxpr.outvars:
            if isinstance(v, Var):
                self._stop(v)

    def _stop(self, v: Var) -> None:
        self.stop_uses[v] = self.stop_uses.get(v, 0) + 1

    def _ident(self, a, b) -> None:
        if isinstance(a, Var) and isinstance(b, Var):
            self.ident.setdefault(a, []).append(b)

    def _walk(self, jaxpr: Jaxpr) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                body = eqn.params["jaxpr"].jaxpr
                for pos, a in enumerate(eqn.invars):
                    if not isinstance(a, Var):
                        continue
                    if pos >= nc + ncar:
                        self._ident(a, body.invars[pos])
                    else:
                        self._stop(a)
                for i, bv in enumerate(body.outvars):
                    if not isinstance(bv, Var):
                        continue
                    if i >= ncar:
                        self._ident(bv, eqn.outvars[i])
                    else:
                        self._stop(bv)
                self._walk(body)
            elif (body := call_body(eqn)) is not None:
                for a, bv in zip(eqn.invars, body.invars):
                    self._ident(a, bv)
                for bv, ov in zip(body.outvars, eqn.outvars):
                    self._ident(bv, ov)
                self._walk(body)
            elif prim == "cond":
                for a in eqn.invars:
                    if isinstance(a, Var):
                        self._stop(a)
                for br in eqn.params["branches"]:
                    for bv in br.jaxpr.outvars:
                        if isinstance(bv, Var):
                            self._stop(bv)
                    self._walk(br.jaxpr)
            elif prim == "while":
                for a in eqn.invars:
                    if isinstance(a, Var):
                        self._stop(a)
                for key in ("cond_jaxpr", "body_jaxpr"):
                    body = eqn.params[key].jaxpr
                    for bv in body.outvars:
                        if isinstance(bv, Var):
                            self._stop(bv)
                    self._walk(body)
            else:
                for a in eqn.invars:
                    if isinstance(a, Var):
                        self.eqn_uses.setdefault(a, []).append(eqn)

    def extend_cuts(self, seed: Var) -> frozenset:
        """The seed plus every downstream var whose cotangent the tap
        determines (single-use chains through _CHAIN_PRIMS and ident hops)."""
        cuts = {seed}
        v = seed
        while True:
            eqns = self.eqn_uses.get(v, [])
            idents = self.ident.get(v, [])
            total = len(eqns) + len(idents) + self.stop_uses.get(v, 0)
            if total != 1:
                break
            if idents:
                v = idents[0]
                cuts.add(v)
                continue
            if not eqns:
                break
            eqn = eqns[0]
            if eqn.primitive.name not in _CHAIN_PRIMS or len(eqn.outvars) != 1:
                break
            v = eqn.outvars[0]
            cuts.add(v)
        return frozenset(cuts)


def live_invars(
    jaxpr: Jaxpr, out_live: list, cuts: frozenset
) -> list:
    """Which invars can carry a nonzero cotangent from the live outputs,
    with every var in ``cuts`` treated as an interception point."""
    live: set[Var] = set()

    def mark(v) -> None:
        if isinstance(v, Var) and v not in cuts and _grad_carrying(v):
            live.add(v)

    def mark_eqn_invars(eqn: JaxprEqn, in_live=None) -> None:
        if in_live is None:
            for a in eqn.invars:
                mark(a)
        else:
            for a, flag in zip(eqn.invars, in_live):
                if flag:
                    mark(a)

    for v, flag in zip(jaxpr.outvars, out_live):
        if flag:
            mark(v)
    for eqn in reversed(jaxpr.eqns):
        outs_live = [isinstance(v, Var) and v in live for v in eqn.outvars]
        if not any(outs_live):
            continue
        prim = eqn.primitive.name
        if prim == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = eqn.params["jaxpr"].jaxpr
            cur_out = list(outs_live)
            while True:
                in_live = live_invars(body, cur_out, cuts)
                changed = False
                for i in range(ncar):
                    if in_live[nc + i] and not cur_out[i]:
                        cur_out[i] = True
                        changed = True
                if not changed:
                    break
            mark_eqn_invars(eqn, in_live)
        elif (body := call_body(eqn)) is not None:
            in_live = live_invars(body, outs_live, cuts)
            mark_eqn_invars(eqn, in_live)
        elif prim == "cond":
            agg = [False] * (len(eqn.invars) - 1)
            for br in eqn.params["branches"]:
                bl = live_invars(br.jaxpr, outs_live, cuts)
                agg = [a or b for a, b in zip(agg, bl)]
            mark_eqn_invars(eqn, [False] + agg)
        else:
            # includes `while` (conservative: everything feeds the carry)
            mark_eqn_invars(eqn)
    return [isinstance(v, Var) and v in live for v in jaxpr.invars]


@dataclasses.dataclass
class CoverageReport:
    """Graph-level coverage facts; the audit layer turns these into findings."""

    # tap -> claimed param paths whose gradient has a route around the tap
    bypassed: dict
    # unclaimed, non-frozen param paths that reach the loss (privacy bug)
    uncovered_live: list
    # unclaimed param paths that never reach the loss (dead weight — warn)
    uncovered_dead: list
    # taps declared in meta with no add eqn found in the graph
    unthreaded: list


def coverage_report(
    closed: ClosedJaxpr,
    param_invars: dict,
    losses_out_index: int,
    sites: list,
    meta: dict,
    frozen_prefixes: tuple = (),
) -> CoverageReport:
    """``param_invars``: param-leaf path -> top-level invar index.
    ``sites``: TapSites from the taint pass (their add-eqn outputs seed the
    cut sets).  ``meta``: tap name -> TapMeta (the declared claims).
    """
    jaxpr = closed.jaxpr
    uses = ForwardUses(jaxpr)
    out_live = [i == losses_out_index for i in range(len(jaxpr.outvars))]

    cuts_by_tap: dict = {}
    for site in sites:
        seed = site.eqn.outvars[0]
        cuts_by_tap.setdefault(site.tap, set()).update(uses.extend_cuts(seed))

    claims: dict = {}
    for name, m in meta.items():
        paths = [m.param_path] + ([m.bias_path] if m.bias_path else [])
        claims[name] = [
            p
            for p in paths
            if p in param_invars
            and not any(p.startswith(fp) for fp in frozen_prefixes)
        ]
    claimed_paths = {p for paths in claims.values() for p in paths}

    base_live = live_invars(jaxpr, out_live, frozenset())
    uncovered_live, uncovered_dead = [], []
    for path, idx in sorted(param_invars.items()):
        if path in claimed_paths:
            continue
        if any(path.startswith(fp) for fp in frozen_prefixes):
            continue
        (uncovered_live if base_live[idx] else uncovered_dead).append(path)

    bypassed: dict = {}
    unthreaded = []
    for name, paths in sorted(claims.items()):
        if name not in cuts_by_tap:
            unthreaded.append(name)
            continue
        if not paths:
            continue
        live = live_invars(jaxpr, out_live, frozenset(cuts_by_tap[name]))
        leaks = [p for p in paths if live[param_invars[p]]]
        if leaks:
            bypassed[name] = leaks
    return CoverageReport(
        bypassed=bypassed,
        uncovered_live=uncovered_live,
        uncovered_dead=uncovered_dead,
        unthreaded=unthreaded,
    )
