"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The harness traces its measured window with ``jax.profiler`` and wraps the
window, each interaction and each think wait in ``TraceAnnotation`` spans
(``bench_window``, ``interact:<template>``, ``think_wait``).  From the
trace this module takes:

* ``busy_s``: the union of the intervals in which an operation runs on a
  device, inside the window, averaged over the devices;
* ``window_s``: the length of the ``bench_window`` span;
* ``op_s``: device seconds by operation name (for the kernels' readers and
  the breakdown), and ``op_detail``: each name's string stats, where the
  compiler put the kernel's own name;
* ``gaps``: the longest idle gaps of the first device, each labelled by
  the innermost harness span the host was in at the gap's middle.

A device operation is an event of a line named ``XLA Ops`` on a plane named
``/device:<kind>:<n>`` (not the host CPU).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("interact:", "think_wait")
OPS_LINE = "XLA Ops"
GAPS_KEPT = 10


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    op_s: Dict[str, float] = field(default_factory=dict)
    op_detail: Dict[str, str] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    devices: int = 0

    def seconds_matching(self, pattern: str) -> Optional[float]:
        """Device seconds of the operations whose name or stats contain
        ``pattern``; None where no operation does."""
        hits = [s for name, s in self.op_s.items()
                if pattern in name or pattern in self.op_detail.get(name, "")]
        return sum(hits) if hits else None

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def events_from_profile(profile) -> List[Event]:
    """Flatten a ``jax.profiler.ProfileData``."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                detail = ""
                if is_device_plane(plane.name):
                    detail = " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns), detail))
    return out


def load(log_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return events_from_profile(ProfileData.from_file(paths[-1]))


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def summarize(events: List[Event]) -> TraceSummary:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = spans[0].start_ns, spans[0].end_ns
    ops = [e for e in events if is_device_plane(e.plane) and e.line == OPS_LINE
           and e.end_ns > w0 and e.start_ns < w1]
    planes = sorted({e.plane for e in ops})
    op_s: Dict[str, float] = {}
    op_detail: Dict[str, str] = {}
    for e in ops:
        op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns * 1e-9
        if e.detail and e.name not in op_detail:
            op_detail[e.name] = e.detail
    busy = {p: _clip(_union((e.start_ns, e.end_ns) for e in ops if e.plane == p), w0, w1)
            for p in planes}
    busy_s = (sum(b - a for iv in busy.values() for a, b in iv) / len(planes) * 1e-9
              if planes else 0.0)
    host = [e for e in events if not is_device_plane(e.plane)
            and e.name.startswith(HOST_SPANS)]
    gaps = []
    if planes:
        edges = [w0] + [x for a, b in busy[planes[0]] for x in (a, b)] + [w1]
        idle = sorted(((a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a),
                      key=lambda g: g[0] - g[1])
        for a, b in idle[:GAPS_KEPT]:
            mid = (a + b) / 2
            inside = [h for h in host if h.start_ns <= mid < h.end_ns]
            label = min(inside, key=lambda h: h.dur_ns).name if inside else "other"
            gaps.append((label, (b - a) * 1e-9))
    return TraceSummary(busy_s=busy_s, window_s=(w1 - w0) * 1e-9, op_s=op_s,
                        op_detail=op_detail, gaps=gaps, devices=len(planes))
