"""Numbers that decide ``correct``: gaps between what the timed path produced
and what the plain reference computes for the same keys."""
from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_leaves(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix.rstrip("/"): np.asarray(tree, np.float64)}


def leaf_norm_gaps(got: dict, want: dict, skip: set[str] = frozenset()) -> dict[str, float]:
    """Per leaf, the gap between its norm in ``got`` and in ``want``,
    measured against the larger of the reference leaf's norm and the median
    leaf's norm (some leaves are all but zero)."""
    g, w = _leaves(got), _leaves(want)
    norms = {k: float(np.linalg.norm(v)) for k, v in w.items()}
    med = float(np.median(list(norms.values())))
    return {
        k: abs(float(np.linalg.norm(g[k])) - wn) / max(wn, med, 1e-30)
        for k, wn in norms.items()
        if k not in skip
    }


def worst_leaf_norm_gap(got: dict, want: dict, skip: set[str] = frozenset()) -> float:
    return max(leaf_norm_gaps(got, want, skip).values())


def median_leaf_norm_gap(got: dict, want: dict, skip: set[str] = frozenset()) -> float:
    return float(np.median(list(leaf_norm_gaps(got, want, skip).values())))


def quiet_leaves(grads: dict, frac: float = 1e-3) -> set[str]:
    """Leaves whose reference gradient (Adam's first moment) is under
    ``frac`` of the median leaf's: they move by round-off alone under Adam."""
    g = {k: float(np.linalg.norm(v)) for k, v in _leaves(grads).items()}
    med = float(np.median(list(g.values())))
    return {k for k, v in g.items() if v < frac * med}


def tree_sub(a: dict, b: dict) -> dict:
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def rel_gap(got: float, want: float, floor: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), floor)


def env_sum_mismatch(got: np.ndarray, want: np.ndarray, rtol: float) -> np.ndarray:
    """Per-env disagreement of day sums ``(..., [reward, energy, arrived,
    rejected])``: counts exactly, reward and energy beyond ``rtol`` of
    max(|reference|, 1)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    counts = np.any(got[..., 2:] != want[..., 2:], axis=-1)
    floats = np.any(np.abs(got[..., :2] - want[..., :2]) > rtol * np.maximum(np.abs(want[..., :2]), 1.0), axis=-1)
    return counts | floats
