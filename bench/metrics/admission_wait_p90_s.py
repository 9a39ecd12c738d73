"""90th percentile, over the window's interactions, of the engine's own
admission wait: the interaction's ``engine.pause_ack`` (the background
worker acknowledging its pause) plus ``engine.lock_wait`` (the wait for the
engine's lock), from the program's spans.  A wait outside the engine, such
as one the harness adds around ``submit``, is not in it."""
from .. import spans as S
from . import percentile

ADMISSION = ("engine.pause_ack", "engine.lock_wait")


def read(run):
    window = S.window(run)
    waits = {s.rid: 0 for s in window if s.name == "engine.display"}
    for s in window:
        if s.name in ADMISSION and s.rid in waits:
            waits[s.rid] += s.ns
    return percentile([ns * S.NS for ns in waits.values()], 90)
