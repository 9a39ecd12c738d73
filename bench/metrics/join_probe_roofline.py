"""The join probe's share of the HBM roofline, in percent: the bytes any
probe must move, over the device's peak HBM bandwidth
(``bench/peaks.json``), over the probe's device seconds
(``join_probe_ms.probe_seconds``).

Bytes per call, from the ``dispatch.call`` spans of family ``join`` (rows
and ``right_rows``): 4 bytes a left key read, 4 a right key read, and 5 a
left key written back (an int32 position and a one-byte hit).  The probe
compares rather than streams, so the share reads far below 100."""
import json
from pathlib import Path

from .. import spans as S
from .join_probe_ms import probe_seconds

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def probe_bytes(rows: int, right_rows: int) -> int:
    return 4 * rows + 4 * right_rows + 5 * rows


def read(run):
    if run.trace is None:
        return None
    calls = [s for s in S.window(run) if s.name == "dispatch.call"
             and s.attrs.get("family") == "join" and "right_rows" in s.attrs]
    seconds = probe_seconds(run.trace)
    if not calls or not seconds:
        return None
    import jax

    peaks = json.loads(PEAKS.read_text())["devices"].get(jax.devices()[0].device_kind)
    if peaks is None:
        return None
    moved = sum(probe_bytes(s.attrs["rows"], s.attrs["right_rows"]) for s in calls)
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / seconds
