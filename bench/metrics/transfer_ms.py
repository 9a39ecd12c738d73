"""Milliseconds per interaction of host-device transfers (``dispatch.upload``
of host arrays, ``dispatch.readback`` of results after the wait for the
device), from the program's spans."""
from .. import spans as S


def read(run):
    return S.total_ms(run, S.TRANSFERS)
