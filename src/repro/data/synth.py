"""Deterministic multi-session interaction traces for multi-tenant serving."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


# -- multi-session interaction traces (multi-tenant serving) -------------------
#
# The traffic-replay corpus for `benchmarks/bench_serve.py` and the
# trace-determinism tests: N concurrent sessions, each issuing a Poisson
# process of interactions (exponential inter-arrival = the session's think
# times), with Zipf-popular query templates so cross-tenant dedup has honest
# hit structure (popular templates collide across sessions, parameterised
# variants don't).  Fully determined by the seed — same spec, same trace,
# byte for byte.


@dataclass(frozen=True)
class TraceEvent:
    """One interaction: ``session`` runs query ``(template, param)`` at
    virtual time ``at`` (seconds since replay start)."""

    at: float
    session: int
    template: int
    param: int  # 0 = the template's canonical form; >0 = parameterised variant


@dataclass(frozen=True)
class TraceSpec:
    n_sessions: int = 100
    n_events_per_session: int = 5
    mean_think_s: float = 10.0  # exponential inter-arrival mean (think time)
    n_templates: int = 8
    zipf_a: float = 1.5  # template popularity skew
    param_cardinality: int = 3  # distinct non-zero params per template
    param_frac: float = 0.25  # fraction of events using a non-zero param
    seed: int = 0


def poisson_trace(spec: TraceSpec) -> list[TraceEvent]:
    """Seeded multi-session Poisson interaction trace, globally time-ordered.

    Each session is an independent Poisson process started at its own
    exponential offset (sessions ramp up, they don't all fire at t=0).
    Ties in ``at`` are broken by session index so the total order — and hence
    any replay schedule derived from it — is deterministic."""
    rng = np.random.default_rng(spec.seed)
    events: list[TraceEvent] = []
    for s in range(spec.n_sessions):
        t = float(rng.exponential(spec.mean_think_s))
        for _ in range(spec.n_events_per_session):
            template = min(
                int(rng.zipf(spec.zipf_a)) - 1, spec.n_templates - 1
            )
            param = (
                int(rng.integers(1, spec.param_cardinality + 1))
                if float(rng.random()) < spec.param_frac
                else 0
            )
            events.append(
                TraceEvent(
                    at=round(t, 6), session=s, template=template, param=param
                )
            )
            t += float(rng.exponential(spec.mean_think_s))
    events.sort(key=lambda e: (e.at, e.session))
    return events
