"""Per-layer-group clipping — Stevens et al., arXiv:2202.05089 territory.

One threshold per *group* of parameters instead of one global R: group ``g``
clips its own slice of the per-sample gradient to ``R_g``, so a layer with
structurally large gradients (an lm_head, the first conv) cannot eat the
whole clipping budget of the rest of the network.

Groups are param-path prefixes (longest match wins; a ``""`` catch-all is
appended automatically so every leaf belongs to exactly one group).  The
thresholds satisfy ``sum_g R_g^2 = R^2`` (equal split by default), which
bounds one sample's total clipped contribution by

    || concat_g C_{i,g} g_{i,g} ||  <=  sqrt(sum_g R_g^2)  =  R,

so the noise calibration is exactly the global-R one and the privacy
accounting is unchanged — the policy only re-shapes *where* the budget goes.

Cost per executor family (the factors are per (group, sample)):

- book-keeping (``bk_mixed``): free — each tap's bank is
  contracted against its own group's factors, same einsums;
- vmap oracle: free — per-leaf scaling;
- second backward (``mixed_ghost``): one extra backward *per group* (the pullback
  cotangent is per-sample, not per-param) — correct everywhere, but prefer
  the book-keeping engine when G is large.

Constraint: a tap's weight and bias share one per-sample norm, so a group
boundary must not split them (the executors validate this at trace time).

State: ``{"step": int32, "thresholds": (G,) float32}`` — checkpointed with
the train state, so custom threshold splits survive save/restore.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.functions import get_clip_fn
from repro.policies.base import ClipPolicy, GroupedFactors, group_index


class PerLayerPolicy(ClipPolicy):
    name = "per_layer"
    grouped = True

    def __init__(
        self,
        groups: Sequence[str] = (),
        clip_norm: float = 1.0,
        clip_fn: str = "abadi",
        weights: Optional[Sequence[float]] = None,
    ):
        gs = tuple(str(g) for g in groups)
        if "" not in gs:
            gs = gs + ("",)  # catch-all: every leaf belongs somewhere
        if len(set(gs)) != len(gs):
            raise ValueError(f"duplicate layer-group prefixes in {gs!r}")
        self.groups = gs
        self.clip_norm = float(clip_norm)
        self.clip_fn_name = clip_fn
        self._clip_fn = get_clip_fn(clip_fn)
        if weights is None:
            w = [1.0] * len(gs)
        else:
            w = [float(x) for x in weights]
            if len(w) != len(gs) or any(x <= 0 for x in w):
                raise ValueError(
                    f"need one positive weight per group ({len(gs)} incl. the "
                    f"catch-all), got {weights!r}"
                )
        # R_g = R * sqrt(w_g / sum(w)): sum_g R_g^2 == R^2 by construction
        z = math.sqrt(sum(w))
        self._thresholds0 = tuple(
            self.clip_norm * math.sqrt(x) / z for x in w
        )

    def init_state(self) -> dict[str, jax.Array]:
        return {
            "step": jnp.zeros((), jnp.int32),
            "thresholds": jnp.asarray(self._thresholds0, jnp.float32),
        }

    def group_of(self, path: str) -> int:
        return group_index(self.groups, path)

    def clip_factors(
        self,
        norms: jax.Array,
        state: dict[str, jax.Array],
        *,
        path_norms2: Optional[dict[str, jax.Array]] = None,
    ) -> GroupedFactors:
        if path_norms2 is None:
            raise ValueError(
                "per_layer policy needs per-path norm contributions; the "
                "executor must surface path_norms2 (grouped policies only "
                "run on modes that compute per-tap norms)"
            )
        b = norms.shape[0]
        g_norms2 = [jnp.zeros((b,), jnp.float32) for _ in self.groups]
        for path, n2 in sorted(path_norms2.items()):
            gi = self.group_of(path)
            g_norms2[gi] = g_norms2[gi] + n2.astype(jnp.float32)
        th = state["thresholds"]
        factors = jnp.stack(
            [
                self._clip_fn(jnp.sqrt(n2), th[gi])
                for gi, n2 in enumerate(g_norms2)
            ]
        )
        return GroupedFactors(groups=self.groups, factors=factors)

    def sensitivity(self, state: dict[str, jax.Array]) -> jax.Array:
        # sqrt(sum R_g^2) — equals clip_norm for the built-in splits, but
        # reading the state keeps restored custom thresholds honest
        return jnp.sqrt(jnp.sum(jnp.square(state["thresholds"])))

    def fingerprint(self) -> str:
        th = ",".join(f"{t:g}" for t in self._thresholds0)
        return (
            f"per_layer:groups={'|'.join(self.groups)},R={self.clip_norm:g},"
            f"th={th},fn={self.clip_fn_name}"
        )
