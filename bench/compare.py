"""The numbers that decide ``correct``, each held against a limit of its
cell (``bench/limits/<cell>.json``).

Both sides report the same quantities of the first iterations: the mean
loss of each, the leaf norms (L, R) of the first gradient, S_k at each
sync, and the leaf norms (L, R) of each replica's parameter change after
the last.  Norms are compared by the worst leaf: the gap between the two
norms, over the larger of the reference's norm of that leaf and its
median leaf norm.  Leaves whose reference gradient is under a thousandth of
the median leaf's move by round-off alone under Adam, and are left out of
the change."""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss", "grad", "s_k", "update", "schedule")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return math.inf
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _worst_leaf(got, ref, keep=None) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    floor = np.maximum(ref, np.median(ref))
    gap = np.abs(got - ref) / np.maximum(floor, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap)) if gap.size else 0.0


def numbers(got: dict, ref: dict) -> dict:
    """Gaps of ``got`` (the program, or a control) from the reference."""
    g = np.asarray(ref["grad_norms"], np.float64)
    moved = g >= 1e-3 * np.median(g)
    out = {
        "loss": _rel(got["losses"], ref["losses"]),
        "grad": _worst_leaf(got["grad_norms"], ref["grad_norms"]),
        "s_k": _rel(got["s_k"], ref["s_k"]),
        "update": _worst_leaf(got["update_norms"], ref["update_norms"],
                              moved),
    }
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def judge(values: dict, limits: dict):
    """(correct, [(name, value, limit)]) in the order of ``NUMBERS``."""
    rows = [(k, float(values[k]), float(limits[k])) for k in NUMBERS
            if k in values]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    missing = [k for k in NUMBERS if k not in values]
    return ok and not missing, rows
