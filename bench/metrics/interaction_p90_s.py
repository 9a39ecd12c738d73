"""90th percentile of the wait from due to exact result shown, over every
interaction of the window: the highest percentile with ten or more samples
beyond it at the window's interaction counts."""
from . import percentile


def read(run):
    return percentile([s.latency_s for s in run.shown], 90)
