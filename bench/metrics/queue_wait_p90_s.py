"""90th percentile of the wait from due to the engine starting the
interaction: the harness's clock less the engine's own
``InteractionRecord.latency_s`` (authoring, interning, the wait for the
engine's lock and for the background worker to acknowledge its pause)."""
from . import percentile


def read(run):
    return percentile([s.queue_wait_s for s in run.shown if s.error is None], 90)
