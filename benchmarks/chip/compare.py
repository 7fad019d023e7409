"""The numbers that decide ``correct``, and their limits.

``gaps(program, reference)`` compares two readings of the same first
steps (see ``trainjob``):

- ``loss_gap``: the largest relative gap between a step's losses;
  ``loss_gap_first`` the first step's, taken before any update;
- ``grad_gap``: over the trained leaves, the largest gap between the
  norms of the first gradient, relative to the reference's norm of that
  leaf or of the median leaf, whichever is larger; ``grad_gap_median``
  the median leaf's gap, which a router's near-tied choices move less;
- ``change_gap``: the same for the norm of each leaf's change over the
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
  ``change_gap_median`` the median of those leaves' gaps.

A cell's workload file sets a limit per number; ``judge`` pairs each
number with its limit.  A number without a limit is reported, not judged.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

RESTING = 1e-3      # a leaf whose gradient is under this share of the median's


def _leaf_gaps(prog: List[float], ref: List[float], keep: np.ndarray) -> np.ndarray:
    p, r = np.asarray(prog, np.float64)[keep], np.asarray(ref, np.float64)[keep]
    if not r.size:
        return np.zeros(1)
    floor = np.maximum(r, np.median(r))
    return np.abs(p - r) / np.maximum(floor, 1e-30)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    if prog["paths"] != ref["paths"]:
        raise ValueError("program and reference readings cover different leaves")
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    grads = np.asarray(ref["grad"], np.float64)
    moving = grads >= RESTING * np.median(grads)
    grad = _leaf_gaps(prog["grad"], ref["grad"], np.ones_like(moving))
    change = _leaf_gaps(prog["change"], ref["change"], moving)
    loss = np.abs(lp - lr) / np.abs(lr)
    return {
        "loss_gap": float(np.max(loss)),
        "loss_gap_first": float(loss[0]),
        "grad_gap": float(np.max(grad)),
        "grad_gap_median": float(np.median(grad)),
        "change_gap": float(np.max(change)),
        "change_gap_median": float(np.median(change)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, Optional[float]]
          ) -> Dict[str, Dict[str, Any]]:
    """→ {name: {"value", "limit"}} for every number; limit None = reported."""
    return {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}


def passed(checks: Dict[str, Dict[str, Any]]) -> bool:
    return all(c["limit"] is None or (np.isfinite(c["value"]) and c["value"] <= c["limit"])
               for c in checks.values())
