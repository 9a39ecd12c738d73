"""Milliseconds per interaction of the executor's combine steps (the
outermost ``exec.combine`` spans: merging per-partition partials into the
shown result), from the program's spans."""
from .. import spans as S


def read(run):
    return S.per_interaction_ms(
        run, (s.ns for s in S.outermost(S.window(run), "exec.combine")))
