"""Traffic replay: cross-tenant opportunistic serving vs isolated round-robin.

The multi-tenant claim under test — one user's think window is another user's
compute — replayed over a seeded multi-session Poisson trace
(`repro.data.synth.poisson_trace`: exponential inter-arrival think times,
Zipf-popular query templates) in two configurations of the *same* simulated
machine capacity:

* **shared**   — one engine, one `MultiTenantServer`: every think gap goes to
  the cross-tenant scheduler (Eq-1 summed over all tenants' demand), programs
  hash-cons into one DAG (identical queries → one materialisation), and the
  cache is shared under per-tenant fair-share accounting.
* **isolated** — one engine *per session*, each submitted only its own
  programs; every think gap is time-sliced round-robin, `gap / n_sessions`
  to each session's private queue.  No dedup, no cross-tenant allocation —
  the per-session status quo on the same hardware budget.

Reported: p50/p95/mean interactive latency per mode, the p95 speedup, the
program-level dedup rate and interaction cache-hit rate in shared mode, and
``plan_deterministic`` — the shared replay is run twice and must produce a
byte-identical schedule log (background pick order + interaction hit/miss
sequence).

Run:  PYTHONPATH=src python benchmarks/bench_serve.py [--sessions 120]
      (--smoke for the tiny CI wiring check; no JSON written)
"""
from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, "src")

from repro.core.engine import Engine
from repro.data.synth import TraceEvent, TraceSpec, poisson_trace
from repro.serve.multitenant import (
    MultiTenantServer,
    register_synthetic_op,
    synthetic_trace_program,
)


def _tenant(session: int) -> str:
    return f"s{session}"


def _make_engine(budget_bytes: int) -> Engine:
    # speculation off: boosts depend on engine.add-time hooks interned
    # programs bypass, and determinism is a reported invariant here
    eng = Engine(mode="sim", budget_bytes=budget_bytes, speculation=False)
    register_synthetic_op(eng)
    return eng


def replay_shared(
    events: list[TraceEvent], budget_bytes: int, record_schedule: bool = True
) -> dict:
    """One engine, cross-tenant scheduling, think gaps from the trace."""
    eng = _make_engine(budget_bytes)
    srv = MultiTenantServer(eng, record_schedule=record_schedule)
    programs: dict = {}  # (session, event_index) -> shared root node
    next_idx: dict = {}  # session -> how many events already interacted

    def submit_next(session: int, upcoming: TraceEvent) -> None:
        d, root = synthetic_trace_program(upcoming.template, upcoming.param)
        prog = srv.submit(_tenant(session), [root])
        programs[(session, next_idx.get(session, 0))] = prog.roots[0]

    # anticipation: each session's first query is known at connect time
    # (both modes get identical anticipation semantics, so the comparison
    # isolates scheduling + dedup, not foresight)
    per_session: dict = {}
    for e in events:
        per_session.setdefault(e.session, []).append(e)
    for s, evs in per_session.items():
        d, root = synthetic_trace_program(evs[0].template, evs[0].param)
        prog = srv.submit(_tenant(s), [root])
        programs[(s, 0)] = prog.roots[0]

    hits = misses = 0
    prev_at = 0.0
    prev_session = None
    for e in events:
        gap = e.at - prev_at
        if gap > 0 and prev_session is not None:
            srv.think(_tenant(prev_session), gap)
        k = next_idx.get(e.session, 0)
        root = programs[(e.session, k)]
        if root.nid in eng.cache:
            hits += 1
        else:
            misses += 1
        srv.interact(_tenant(e.session), root)
        next_idx[e.session] = k + 1
        # the user types their next query as they go: anticipate it now
        evs = per_session[e.session]
        if k + 1 < len(evs):
            nxt = evs[k + 1]
            d, nroot = synthetic_trace_program(nxt.template, nxt.param)
            prog = srv.submit(_tenant(e.session), [nroot])
            programs[(e.session, k + 1)] = prog.roots[0]
        prev_at, prev_session = e.at, e.session
    lat = [r.latency_s for r in eng.metrics.interactions]
    return {
        "latencies": lat,
        "interaction_hits": hits,
        "interaction_misses": misses,
        "dedup_rate": srv.dedup_rate(),
        "schedule": srv.schedule_fingerprint() if record_schedule else None,
        "stats": srv.stats(),
    }


def replay_isolated(events: list[TraceEvent], budget_bytes: int) -> dict:
    """One engine per session, think gaps time-sliced round-robin."""
    per_session: dict = {}
    for e in events:
        per_session.setdefault(e.session, []).append(e)
    n = len(per_session)
    engines: dict = {}
    servers: dict = {}
    programs: dict = {}
    next_idx: dict = {}
    for s, evs in per_session.items():
        eng = _make_engine(budget_bytes // max(n, 1))
        srv = MultiTenantServer(eng)
        engines[s], servers[s] = eng, srv
        d, root = synthetic_trace_program(evs[0].template, evs[0].param)
        prog = srv.submit(_tenant(s), [root])
        programs[(s, 0)] = prog.roots[0]

    hits = misses = 0
    prev_at = 0.0
    for e in events:
        gap = e.at - prev_at
        if gap > 0:
            # round-robin: every session's queue gets an equal slice of the
            # machine during the gap, no matter whose think time it is
            slice_s = gap / n
            for s in per_session:
                servers[s].think(_tenant(s), slice_s)
        k = next_idx.get(e.session, 0)
        root = programs[(e.session, k)]
        if root.nid in engines[e.session].cache:
            hits += 1
        else:
            misses += 1
        servers[e.session].interact(_tenant(e.session), root)
        next_idx[e.session] = k + 1
        evs = per_session[e.session]
        if k + 1 < len(evs):
            nxt = evs[k + 1]
            d, nroot = synthetic_trace_program(nxt.template, nxt.param)
            prog = servers[e.session].submit(_tenant(e.session), [nroot])
            programs[(e.session, k + 1)] = prog.roots[0]
        prev_at = e.at
    lat = [
        r.latency_s
        for s in sorted(per_session)
        for r in engines[s].metrics.interactions
    ]
    return {"latencies": lat, "interaction_hits": hits,
            "interaction_misses": misses}


def _pct(sorted_lat: list, q: float) -> float:
    if not sorted_lat:
        return 0.0
    return sorted_lat[min(int(q * (len(sorted_lat) - 1)), len(sorted_lat) - 1)]


def _latency_summary(latencies: list) -> dict:
    lat = sorted(latencies)
    return {
        "n_interactions": len(lat),
        "p50_s": round(_pct(lat, 0.50), 6),
        "p95_s": round(_pct(lat, 0.95), 6),
        "mean_s": round(sum(lat) / max(len(lat), 1), 6),
        "max_s": round(lat[-1] if lat else 0.0, 6),
    }


def run(spec: TraceSpec, budget_bytes: int = 64 << 20) -> dict:
    events = poisson_trace(spec)
    shared = replay_shared(events, budget_bytes)
    shared2 = replay_shared(events, budget_bytes)  # determinism replay
    isolated = replay_isolated(events, budget_bytes)
    sh = _latency_summary(shared["latencies"])
    iso = _latency_summary(isolated["latencies"])
    n_interactions = sh["n_interactions"]
    report = {
        "trace": {
            "n_sessions": spec.n_sessions,
            "n_events_per_session": spec.n_events_per_session,
            "mean_think_s": spec.mean_think_s,
            "n_templates": spec.n_templates,
            "zipf_a": spec.zipf_a,
            "param_cardinality": spec.param_cardinality,
            "param_frac": spec.param_frac,
            "seed": spec.seed,
            "n_events": len(events),
        },
        "shared": {
            **sh,
            "interaction_hits": shared["interaction_hits"],
            "interaction_misses": shared["interaction_misses"],
            "interaction_hit_rate": round(
                shared["interaction_hits"] / max(n_interactions, 1), 4
            ),
        },
        "isolated": {
            **iso,
            "interaction_hits": isolated["interaction_hits"],
            "interaction_misses": isolated["interaction_misses"],
        },
        # None = shared percentile is 0 (fully warm): the ratio is unbounded
        "speedup_p50": _speedup(iso["p50_s"], sh["p50_s"]),
        "speedup_p95": _speedup(iso["p95_s"], sh["p95_s"]),
        "dedup_hit_rate": round(shared["dedup_rate"], 4),
        "plan_deterministic": shared["schedule"] == shared2["schedule"],
        "cache_fairness": _fairness_summary(shared["stats"]["cache"]),
    }
    return report


def _speedup(iso_s: float, shared_s: float):
    return round(iso_s / shared_s, 3) if shared_s > 0 else None


def _fairness_summary(cache_stats: dict) -> dict:
    by_tenant = cache_stats["tenant_bytes"]
    return {
        "n_tenants": len(by_tenant),
        "fair_share_bytes": round(cache_stats["fair_share_bytes"], 1),
        "max_tenant_bytes": max(by_tenant.values(), default=0),
        "min_tenant_bytes": min(by_tenant.values(), default=0),
        "fairness_evictions": cache_stats["fairness_evictions"],
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sessions", type=int, default=120)
    ap.add_argument("--events", type=int, default=6,
                    help="interactions per session")
    ap.add_argument("--mean-think", type=float, default=4.0)
    ap.add_argument("--templates", type=int, default=16)
    ap.add_argument("--param-cardinality", type=int, default=8)
    ap.add_argument("--param-frac", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI wiring check (no JSON written)")
    args = ap.parse_args()
    if args.smoke:
        spec = TraceSpec(n_sessions=10, n_events_per_session=3,
                         mean_think_s=5.0, seed=args.seed)
        report = run(spec)
        assert report["plan_deterministic"], "shared replay schedule diverged"
        assert report["shared"]["n_interactions"] == 30
        assert (
            report["shared"]["p95_s"] <= report["isolated"]["p95_s"]
        ), "cross-tenant scheduling lost to isolated round-robin"
        print("SMOKE OK:", json.dumps(
            {k: report[k] for k in ("speedup_p95", "dedup_hit_rate",
                                    "plan_deterministic")}))
        return
    spec = TraceSpec(n_sessions=args.sessions,
                     n_events_per_session=args.events,
                     mean_think_s=args.mean_think,
                     n_templates=args.templates,
                     param_cardinality=args.param_cardinality,
                     param_frac=args.param_frac,
                     seed=args.seed)
    report = run(spec)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    print(
        f"p95: shared={report['shared']['p95_s']}s "
        f"isolated={report['isolated']['p95_s']}s "
        f"({report['speedup_p95']}x); "
        f"dedup={report['dedup_hit_rate']} "
        f"hit_rate={report['shared']['interaction_hit_rate']} "
        f"deterministic={report['plan_deterministic']}"
    )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
