"""Metric readers: ``<metric>.py`` holds ``read(run)`` for the metric of
that name, and for every per-layer metric that adds a cell suffix to it
(``queue_wait_p90_s.think``).  ``run`` is a ``harness.RunRecord``; a reader
that finds nothing to read returns None, and the metric is left out."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; None without values."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def kernel_ms_per_interaction(run, pattern: str) -> Optional[float]:
    """Device milliseconds of the kernel's trace events per interaction
    completed in the window."""
    if run.trace is None or not run.shown:
        return None
    seconds = run.trace.seconds_matching(pattern)
    return None if seconds is None else seconds * 1e3 / len(run.shown)
