"""Median wait from an interaction's due time to its exact result shown."""
from . import percentile


def read(run):
    return percentile([s.latency_s for s in run.shown], 50)
