"""The numbers that decide ``correct``, each held to its limit.

From the program and from the plain reference, over the same three steps
from the same weights and batches:

- ``loss``: the largest relative gap of a step's loss;
- ``update1``: over the leaves, the largest gap between the program's and
  the reference's norm of the leaf's first update (the momentum after step
  1: LR times the gradient where LARS does not scale the leaf);
- ``change3``: the same for the norm of each leaf's change after three
  steps, leaving out leaves whose reference gradient stays under a
  thousandth of the median leaf's at every step (they move by weight
  decay and rounding alone).

A leaf's gap is measured against the larger of the reference's norm of
that leaf and of the median leaf, so an all-but-zero leaf does not blow up
the ratio.
"""

from __future__ import annotations

import numpy as np

TINY_GRAD = 1e-3


def _leaf_gap(p, r, keep=None):
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    scale = np.maximum(np.abs(r), np.median(np.abs(r)))
    scale = np.where(scale > 0, scale, 1.0)
    return float(np.max(np.abs(p - r) / scale))


def moving(ref) -> np.ndarray:
    """Leaves that the reference's gradient moves at some step."""
    g = np.max(np.asarray(ref["grad"], np.float64), axis=0)
    return g >= TINY_GRAD * np.median(g)


def readings(prog, ref) -> dict:
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss = float("inf")
    else:
        loss = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    return {"loss": loss,
            "update1": _leaf_gap(prog["update1"], ref["update1"]),
            "change3": _leaf_gap(prog["change3"], ref["change3"],
                                 moving(ref))}


def worst(prog, ref, names) -> dict:
    """Which leaf gives each per-leaf number, with its gap, and the gap of
    the median leaf: for the look at a number that swings."""
    out = {}
    for k, keep in (("update1", None), ("change3", moving(ref))):
        p, r = np.asarray(prog[k], np.float64), np.asarray(ref[k], np.float64)
        scale = np.maximum(np.abs(r), np.median(np.abs(r)))
        gap = np.abs(p - r) / np.where(scale > 0, scale, 1.0)
        if keep is not None:
            gap = np.where(keep, gap, 0.0)
        i = int(np.argmax(gap))
        out[k + "_worst"] = [names[i], float(gap[i])]
        out[k + "_median_gap"] = float(np.median(gap))
    return out


def checks(prog, ref, limits) -> dict:
    got = readings(prog, ref)
    return {k: {"value": got[k], "limit": float(limits[k])}
            for k in sorted(limits)}


def passed(checks) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
