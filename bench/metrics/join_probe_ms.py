"""Device milliseconds per completed interaction of the join probe: the
``join_probe`` Pallas call.

The program puts the probe in the ``join_probe`` scope, but on a TPU the
scope reaches only the op metadata, which the trace reduction does not read;
the Pallas call carries the scope as its name.  The host puts the left keys
in order, so no sort runs on the device.  The small fusions around the call
(tile bounds, the block directory, pads) are not counted."""


def is_probe_op(name: str, detail: str = "") -> bool:
    return "join_probe" in name or "join_probe" in detail


def probe_seconds(trace):
    """Device seconds of the probe's ops in the trace; None without any."""
    hits = [s for name, s in trace.op_s.items()
            if is_probe_op(name, trace.op_detail.get(name, ""))]
    return sum(hits) if hits else None


def read(run):
    if run.trace is None or not run.shown:
        return None
    seconds = probe_seconds(run.trace)
    return None if seconds is None else seconds * 1e3 / len(run.shown)
