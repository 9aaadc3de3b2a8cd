"""The comparison that decides ``correct`` for a training cell.

Set-up drives the compiled step from the seed through its first steps; the
plain reference follows the same steps.  The numbers, of which each cell
compares those its workload file gives a limit:

- ``loss_gap``: the largest relative gap of a step's mean loss;
- ``loss0_gap``: the relative gap of the first step's mean loss (the
  forward pass at the seed's weights);
- ``norm_gap``: the largest relative gap of a step's mean and largest
  per-sample gradient norm, as the clipping stage computed them;
- ``sample_norm_gap``: the largest relative gap of one sample's gradient
  norm at the first step, sample by sample (where the program reports each);
- ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer got it (program: Adam's first moment after one
  step over (1 - b1));
- ``sum_gap``: the worst leaf's gap between the norms of the first step's
  clipped-gradient sum, with the noise the step drew taken out (program:
  the batch size times that gradient, less the noise drawn again from the
  step's key), so that the clipping, not the noise, is compared;
- ``change_gap``: the worst leaf's gap between the norms of the parameters'
  change over the steps.

A leaf's gap is |norm_program - norm_reference| over the larger of the
reference's norm of that leaf and of the median leaf, since some leaves
barely move.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone under Adam and are left out of
``change_gap``.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "loss0_gap", "norm_gap", "sample_norm_gap", "grad_gap", "sum_gap",
           "change_gap")
STILL_LEAF = 1e-3  # share of the median leaf's gradient under which a leaf is not compared
NOT_FINITE = 1e30  # what a NaN or infinite gap reads as


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else NOT_FINITE


def _worst_leaf(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray, names: list[str]):
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    gaps = np.where(np.isfinite(gaps), gaps, NOT_FINITE)
    gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return _finite(gaps[i]), names[i]


def training_numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """(every number, the leaf that set each leaf-wise number)."""
    if list(prog["names"]) != list(ref["names"]):
        raise ValueError("program and reference name their leaves differently")
    names = list(ref["names"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gaps = np.abs(lp - lr) / np.abs(lr)
    norms_p, norms_r = np.asarray(prog["norms"]), np.asarray(ref["norms"])
    norm_gap = np.max(np.abs(norms_p - norms_r) / np.maximum(np.abs(norms_r), 1e-30))
    everything = np.ones(len(names), bool)
    grad_gap, grad_leaf = _worst_leaf(prog["grad"], ref["grad"], everything, names)
    sum_gap, sum_leaf = _worst_leaf(prog["sum"], ref["sum"], everything, names)
    moving = ref["grad"] >= STILL_LEAF * np.median(ref["grad"])
    change_gap, change_leaf = _worst_leaf(prog["change"], ref["change"], moving, names)
    numbers = {"loss_gap": _finite(np.max(loss_gaps)), "loss0_gap": _finite(loss_gaps[0]),
               "norm_gap": _finite(norm_gap), "grad_gap": grad_gap, "sum_gap": sum_gap,
               "change_gap": change_gap}
    if "sample_norms" in prog and "sample_norms" in ref:
        sp, sr = np.asarray(prog["sample_norms"]), np.asarray(ref["sample_norms"])
        numbers["sample_norm_gap"] = (  # a sample missing on one side is no match
            _finite(np.max(np.abs(sp - sr) / np.maximum(sr, 1e-30)))
            if sp.shape == sr.shape else NOT_FINITE)
    where = {"grad_gap": grad_leaf, "sum_gap": sum_leaf, "change_gap": change_leaf,
             "still_leaves": [n for n, m in zip(names, moving) if not m]}
    return numbers, where


def checks(numbers: dict, limits: dict) -> dict:
    """Each number the cell compares, beside its limit."""
    return {k: {"value": numbers.get(k, NOT_FINITE), "limit": limits[k]}
            for k in NUMBERS if k in limits}


def passed(checked: dict) -> bool:
    return bool(checked) and all(c["value"] <= c["limit"] for c in checked.values())
