"""The output check: what the program showed against the reference.

Two numbers come out of each comparison, and of a run the worst of each:

* ``exact_mismatches``: cells that have to match exactly and do not (keys,
  counts, minima and maxima, shown rows, column names), plus one for every
  column that is missing, extra, out of order or of another length;
* ``stat_rel_err``: the largest relative error of a cell the engine computes
  only to rounding (``Result.approx``), against the reference's float64.

An exact cell of a float32 column (``describe`` stores float32) matches when
it equals the reference value rounded to float32.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from .reference import Result

TINY = 1e-12


def _same(g, r) -> bool:
    if g is None or r is None:
        return g is None and r is None
    if isinstance(g, str) or isinstance(r, str):
        return g == r
    g, r = float(g), float(r)
    return g == r or (np.isnan(g) and np.isnan(r))


def compare(got: Dict[str, np.ndarray], ref: Result) -> Tuple[int, float]:
    """``(exact_mismatches, stat_rel_err)`` of one shown result."""
    mismatches, worst = 0, 0.0
    if list(got) != list(ref.table):
        mismatches += len(set(got) ^ set(ref.table)) or 1
    for name, rvals in ref.table.items():
        if name not in got:
            continue
        gvals = np.asarray(got[name])
        if len(gvals) != len(rvals):
            mismatches += 1
            continue
        approx = ref.approx[name]
        exact_vals = rvals
        if gvals.dtype.kind == "f" and rvals.dtype.kind == "f":
            exact_vals = rvals.astype(gvals.dtype)
        for g, r, rx, a in zip(gvals.tolist(), rvals.tolist(), exact_vals.tolist(),
                               approx.tolist()):
            if not a or g is None or r is None or isinstance(g, str) \
                    or not np.isfinite(r):
                mismatches += not _same(g, rx)
                continue
            err = abs(g - r) / max(abs(r), TINY)
            if np.isnan(err):
                mismatches += 1
            else:
                worst = max(worst, err)
    return mismatches, worst


def within(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number at or under its limit."""
    return all(numbers[k] <= limits[k] for k in limits)
