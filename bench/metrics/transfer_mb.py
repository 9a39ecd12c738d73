"""Megabytes per interaction moved between host and device: the ``bytes``
of the ``dispatch.upload`` and ``dispatch.readback`` spans."""
from .. import spans as S


def read(run):
    window = S.window(run)
    if not window:
        return None
    moved = sum(s.attrs.get("bytes", 0) for s in window if s.name in S.TRANSFERS)
    return moved * 1e-6 / len(run.shown)
