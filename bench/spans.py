"""The program's own spans of a run's measured window.

The program records every span it opens in a bounded ring
(``repro.obs.records()``: name, parent, request id, thread, start and end on
``time.perf_counter_ns``, self time, attributes such as bytes).  The window
is ``[first Shown.due, last Shown.shown]`` on the same clock; of the spans
inside it, those of the interacting threads are kept: the threads that
opened an ``engine.display`` span there.  The background worker's spans are
left out, as are the warm-up's.

A program without ``repro.obs`` records no spans; every reader of this
module then finds nothing and returns None.
"""
from __future__ import annotations

import importlib
from typing import Dict, Iterable, List, Optional

NS = 1e-9


def program_spans() -> list:
    """Every span the program still holds in its ring, oldest first."""
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return []
    return obs.records()


def cut(shown, spans: Iterable) -> list:
    """The interacting threads' spans within the window of ``shown``."""
    if not shown:
        return []
    lo = min(s.due for s in shown) / NS
    hi = max(s.shown for s in shown) / NS
    inside = [s for s in spans if s.t0_ns >= lo and s.t1_ns <= hi]
    threads = {s.thread for s in inside if s.name == "engine.display"}
    return [s for s in inside if s.thread in threads]


def window(run) -> list:
    """``cut`` of the run's interactions over the program's spans, taken
    once per run."""
    spans = run.__dict__.get("_program_spans")
    if spans is None:
        spans = run.__dict__["_program_spans"] = cut(run.shown, program_spans())
    return spans


def outermost(spans: List, name: str) -> list:
    """Spans called ``name`` with no ancestor of that name."""
    by_sid: Dict[int, object] = {s.sid: s for s in spans}

    def nested(s) -> bool:
        p = by_sid.get(s.parent)
        while p is not None:
            if p.name == name:
                return True
            p = by_sid.get(p.parent)
        return False

    return [s for s in spans if s.name == name and not nested(s)]


def per_interaction_ms(run, values_ns: Iterable[int]) -> Optional[float]:
    """Milliseconds summed over the window, per shown interaction; None when
    the window holds no program spans."""
    if not window(run):
        return None
    return sum(values_ns) * 1e-6 / len(run.shown)


def self_ms(run, prefix: str) -> Optional[float]:
    """Self time of the spans whose name starts with ``prefix``, per shown
    interaction."""
    return per_interaction_ms(
        run, (s.self_ns for s in window(run) if s.name.startswith(prefix)))


def total_ms(run, names) -> Optional[float]:
    """Whole time of the spans named in ``names``, per shown interaction."""
    return per_interaction_ms(run, (s.ns for s in window(run) if s.name in names))


TRANSFERS = ("dispatch.upload", "dispatch.readback")
