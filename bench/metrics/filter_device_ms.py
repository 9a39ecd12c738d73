"""Device milliseconds per completed interaction of the whole filter: the
``filter_compact`` Pallas call and the XLA scatter that stitches its rows
into place, with the sort XLA lowers that scatter to from the 2^22 bucket
up.

The program puts all three in the ``filter_compact`` scope, but on a TPU the
scope reaches only the op metadata (``tf_op``), which the trace reduction
does not read: an op is known by its HLO text.  The Pallas call carries the
scope as its name; the stitch is known by its signature, a custom fusion
into ``f32[n + 1]`` (the compacted rows and one dump slot) from ``s32[n]``
row positions, and the sort by its operands, ``(s32[n], f32[n])``."""
import re

STITCH = re.compile(r"= f32\[(\d+)\]\S* fusion\(s32\[(\d+)\]")
SORT = re.compile(r"= \(s32\[(\d+)\]\S*, f32\[(\d+)\]\S*\) sort\(")


def is_filter_op(name: str, detail: str = "") -> bool:
    if "filter_compact" in name or "filter_compact" in detail:
        return True
    m = STITCH.search(name)
    if m and "kind=kCustom" in name and int(m.group(1)) == int(m.group(2)) + 1:
        return True
    m = SORT.search(name)
    return bool(m) and m.group(1) == m.group(2)


def read(run):
    if run.trace is None or not run.shown:
        return None
    hits = [s for name, s in run.trace.op_s.items()
            if is_filter_op(name, run.trace.op_detail.get(name, ""))]
    return sum(hits) * 1e3 / len(run.shown) if hits else None
