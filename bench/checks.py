"""Numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference gives."""

from __future__ import annotations

import numpy as np


def rel_gap(value: float, ref: float) -> float:
    return abs(value - ref) / abs(ref)


def worst_leaf_gap(prog, ref, keep=None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's.  ``keep`` masks the leaves compared."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    denom = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref)[keep] / denom[keep]))


def moving_leaves(ref_first_grad) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's: the others move under AdamW by round-off alone."""
    g = np.asarray(ref_first_grad, np.float64)
    return g > 1e-3 * np.median(g)
