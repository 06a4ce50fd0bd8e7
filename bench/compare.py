"""The comparison that decides ``correct``: what the timed path produced in
its first updates against the plain reference, one number per check.

  loss_gap       worst relative gap of the update's loss (on its gradient
                 batch) over the compared updates
  grad_norm_gap  relative gap of the first update's gradient norm
  change_gap     worst leaf's gap between the norms of the parameters'
                 change over the compared updates, against the
                 reference's norm of that leaf's change or the median
                 leaf's, whichever is larger; leaves whose first reference
                 gradient is under a thousandth of the median leaf's are
                 left out (they move by rounding alone)
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_norm_gap", "change_gap")


def _leaves(tree):
    return {f"{k}.{j}": np.asarray(v, np.float64)
            for k, sub in sorted(tree.items()) for j, v in sorted(sub.items())}


def _rel(a, b):
    gap = abs(a - b) / max(abs(b), 1e-30)
    return gap if math.isfinite(gap) else math.inf


def readings(prog, ref, initial):
    """prog / ref: {"loss": [..], "grad_norm": [..], "params": tree,
    "grad_leaf_norms": {leaf: norm} (ref only)}; initial: the weights both
    started from.  Returns {name: value}; a non-finite value reads inf."""
    n = len(ref["loss"])
    loss_gap = max(_rel(p, r) for p, r in zip(prog["loss"][:n], ref["loss"]))
    grad_gap = _rel(prog["grad_norm"][0], ref["grad_norm"][0])
    p0 = _leaves(initial)
    dp = {k: np.linalg.norm(v - p0[k]) for k, v in _leaves(prog["params"]).items()}
    dr = {k: np.linalg.norm(v - p0[k]) for k, v in _leaves(ref["params"]).items()}
    g = ref["grad_leaf_norms"]
    g_med = float(np.median(list(g.values())))
    kept = [k for k in dr if g[k] >= 1e-3 * g_med]
    d_med = float(np.median([dr[k] for k in kept]))
    if max(dr[k] for k in kept) == 0.0:
        # no update accepted by the reference: the program must not move
        change = 0.0 if all(dp[k] == 0.0 for k in kept) else 1.0
    else:
        change = max(abs(dp[k] - dr[k]) / max(dr[k], d_med) for k in kept)
    out = {"loss_gap": loss_gap,
           "grad_norm_gap": grad_gap,
           "change_gap": float(change)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(values, limits):
    """(correct, {name: {"value": v, "limit": l}}) over the numbers the
    cell's limits hold."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NAMES
              if k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
