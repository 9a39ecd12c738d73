"""Milliseconds per interaction the host waits for the device to finish a
kernel before reading its result (``dispatch.wait`` spans), from the
program's spans."""
from .. import spans as S


def read(run):
    return S.total_ms(run, ("dispatch.wait",))
