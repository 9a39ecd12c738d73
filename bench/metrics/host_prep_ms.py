"""Milliseconds per interaction of host preparation for kernel calls (the
self time of ``dispatch.prep`` spans: dtype conversion, keep masks,
stacking, padding and plans), from the program's spans."""
from .. import spans as S


def read(run):
    return S.self_ms(run, "dispatch.prep")
