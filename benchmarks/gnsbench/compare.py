"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes.

Norm gaps are taken leaf by leaf and reported for the worst leaf: the gap
between the two norms, over the reference's norm of that leaf or of the
median leaf, whichever is larger.  Leaves whose reference gradient is under
a thousandth of the median leaf's are left out of the gradient and update
gaps: Adam moves them by round-off alone.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def leaves(params) -> list[np.ndarray]:
    """A layer list of dicts of arrays, flattened in a fixed order."""
    return [np.asarray(p[k], dtype=np.float64) for p in params
            for k in sorted(p)]


def norm_gap(prog: list, ref: list, keep: list) -> float:
    rn = [float(np.linalg.norm(r)) for r in ref]
    med = float(np.median([rn[i] for i in keep]))
    return max(abs(float(np.linalg.norm(prog[i])) - rn[i]) / max(rn[i], med)
               for i in keep)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (one per checked step),
    ``grad0`` (the first gradient), ``params0`` and ``params`` (before the
    first and after the last checked step), as layer lists."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g_ref = leaves(ref["grad0"])
    gn = [float(np.linalg.norm(g)) for g in g_ref]
    med = float(np.median(gn))
    keep = [i for i, n in enumerate(gn) if n >= NEGLIGIBLE_GRAD * med]
    d_prog = [a - b for a, b in zip(leaves(prog["params"]),
                                    leaves(prog["params0"]))]
    d_ref = [a - b for a, b in zip(leaves(ref["params"]),
                                   leaves(ref["params0"]))]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": norm_gap(leaves(prog["grad0"]), g_ref, keep),
        "update_gap": norm_gap(d_prog, d_ref, keep),
    }


def logit_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between two logit arrays, over the reference's largest
    magnitude (at least 1)."""
    prog = np.asarray(prog, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.max(np.abs(prog - ref)) / max(np.max(np.abs(ref)), 1.0))
