"""The comparisons that decide `correct`, and how they are printed.

Each number compared has its own limit (bench/limits/<cell>.json); a run
is correct when every number is at or under its limit and nothing failed.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

# a leaf whose first reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of the change compared
GRAD_FLOOR = 1e-3


def rel_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Largest |prog - ref| / |ref| over the pairs."""
    return max(abs(a - b) / abs(b) for a, b in zip(prog, ref))


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """Per node and leaf, the gap between the program's norm and the
    reference's, over the larger of the reference's norm of that leaf and
    of the node's median leaf; 0 where `keep` masks a leaf out."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = np.median(ref, axis=-1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    return gap if keep is None else np.where(keep, gap, 0.0)


def worst_leaf_gap(prog, ref, keep=None) -> float:
    """The worst of `leaf_gaps` over nodes and leaves."""
    return float(leaf_gaps(prog, ref, keep).max())


def moving_leaves(grad_norms) -> np.ndarray:
    """Leaves whose first reference gradient is at least GRAD_FLOOR of the
    node's median leaf gradient."""
    g = np.asarray(grad_norms, np.float64)
    return g >= GRAD_FLOOR * np.median(g, axis=-1, keepdims=True)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """Loss of each superstep, momentum after the first (the first
    gradients as the optimizer holds them), and the parameters' change
    after the last."""
    return {
        "loss_gap": rel_gap(prog["losses"], ref["losses"]),
        "grad_gap": worst_leaf_gap(prog["mom_norms"], ref["mom_norms"]),
        "change_gap": worst_leaf_gap(prog["change_norms"],
                                     ref["change_norms"],
                                     moving_leaves(ref["grad_norms"])),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          failed: int) -> tuple:
    """(correct, checks) with checks {name: {"value", "limit"}}; a number
    that is not finite fails."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks
