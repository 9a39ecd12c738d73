"""Fault-injection benchmark: interactivity and correctness under chaos.

The paper's premise is that think-time speculation is *free*.  This benchmark
prices that claim under failure: a real-clock session (background worker on,
xla kernel backend) drives a fixed interaction script while the chaos harness
(:mod:`repro.core.faults`) injects kernel-dispatch failures at 0%, 1%, and 10%
rates — plus background unit crashes in ``--smoke`` — and measures what the
user actually experiences:

* **interactive latency** percentiles (p50 / p95 / max) per fault rate,
* **background throughput** (partition units/s pushed through the worker),
* **results_exact** — every interactive result must be *bit-identical* to a
  fault-free numpy reference session.  The script deliberately uses only the
  bit-exact op family (filter, full sort, head/tail, value_counts, dropna);
  f32-approximate ops (describe, groupby means) run in the background workload
  to generate fault pressure but are never part of the exactness check,
* **worker_alive** — the background worker must survive the full run at every
  rate (the silent-death regression this PR's crash isolation removes),

together with the fault-domain observability counters: injected-fault tallies,
absorbed background faults, quarantine state, and the per-(op, backend)
circuit-breaker board.

Run:  PYTHONPATH=src python benchmarks/bench_faults.py [--rates 0,0.01,0.1]
      (--smoke for the CI chaos wiring check: tiny rows, nonzero fault rate)
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

from repro.core.faults import FaultPlan, FaultSpec
from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame import backend as BK
from repro.frame.partitioner import uniform_partitions
from repro.frame.table import pydict_equal

N_CATEGORIES = 64


def make_session(nrows: int, nparts: int, backend: str,
                 plan: FaultPlan | None) -> tuple:
    cat = Catalog()
    cat.register(
        TableSpec(
            "fact",
            nrows=nrows,
            cols=(
                ColSpec("x", low=0.0, high=10.0),
                ColSpec("y", null_frac=0.2),
                ColSpec("z"),
                ColSpec("k", kind="cat", n_categories=N_CATEGORIES),
            ),
            io_seconds=0.0,
            seed=7,
        )
    )
    s = Session(
        catalog=cat, mode="real", kernel_backend=backend, speculation=False,
        fault_plan=plan,
    )
    df = s.read_table("fact")
    df.node.kwargs = dict(df.node.kwargs)
    df.node.kwargs["partition_bounds"] = uniform_partitions(nrows, nparts)
    return s, df


def enqueue_background(s: Session, df) -> None:
    """Non-critical pressure: every op family dispatches kernels in the
    background, so injected kernel faults hit all breaker keys."""
    df.describe()
    df.groupby("k").agg({"x": "mean", "y": "sum", "z": "max"})
    df["k"].value_counts()
    df.sort_values("y")
    df[df["z"] > 0.5]
    df.dropna()


def interaction_script(s: Session, df, think_s: float) -> list:
    """The fixed interactive session; returns the shown results (pydicts).
    Bit-exact op family only — these are what results_exact compares."""
    outs = []

    def show(x):
        v = s.show(x)
        outs.append(v.to_pydict() if hasattr(v, "to_pydict") else v)
        time.sleep(think_s)  # think time: the worker runs (and faults) here

    flt = df[df["x"] > 3.0]
    show(flt.sort_values("x").head(20))
    show(df["k"].value_counts())
    show(df.head(10))
    show(df.dropna().head(10))
    show(df.tail(10))
    show(flt.sort_values("y", ascending=False).head(15))
    return outs


def _percentiles(latencies: list) -> dict:
    if not latencies:
        return {"p50_ms": None, "p95_ms": None, "max_ms": None}
    xs = sorted(latencies)

    def q(p):
        return xs[min(int(p * len(xs)), len(xs) - 1)]

    return {
        "p50_ms": round(q(0.50) * 1e3, 3),
        "p95_ms": round(q(0.95) * 1e3, 3),
        "max_ms": round(xs[-1] * 1e3, 3),
    }


def run_rate(nrows: int, nparts: int, backend: str, rate: float,
             think_s: float, seed: int, exec_unit_rate: float = 0.0) -> dict:
    """One full scripted session at a given injected kernel-failure rate."""
    BK.reset_breakers()  # breaker state is process-global
    specs = []
    if rate > 0:
        specs.append(FaultSpec("kernel", mode="raise", rate=rate))
    if exec_unit_rate > 0:
        specs.append(FaultSpec("exec.unit", mode="raise", rate=exec_unit_rate))
    plan = FaultPlan(specs, seed=seed) if specs else None
    s, df = make_session(nrows, nparts, backend, plan)
    eng = s.engine
    eng.scheduler.quarantine_base_s = 0.05  # keep retries inside the run
    table = eng.value_of(df.node)
    BK.warm_device_cache(table)
    enqueue_background(s, df)

    stats = eng.executor.stats
    u0 = stats.units_run
    eng.start_background()
    t0 = time.monotonic()
    try:
        results = interaction_script(s, df, think_s)
        worker_alive = eng._worker.alive
    finally:
        eng.stop_background()
    elapsed = time.monotonic() - t0
    units = stats.units_run - u0

    report = {
        "fault_rate": rate,
        "exec_unit_rate": exec_unit_rate,
        "interactive_latency": _percentiles(
            [r.latency_s for r in eng.metrics.interactions]
        ),
        "n_interactions": len(eng.metrics.interactions),
        "background_units": units,
        "background_units_per_s": round(units / max(elapsed, 1e-9), 2),
        "worker_alive": worker_alive,
        "worker_stalls": eng.metrics.worker_stalls,
        "background_faults_absorbed": eng.metrics.n_background_faults,
        "corrupt_results_dropped": eng.metrics.corrupt_results_dropped,
        "quarantines": eng.metrics.quarantines,
        "quarantined_now": len(eng.scheduler.quarantined),
        "faults_injected": plan.summary() if plan is not None else None,
        "breakers": {
            k: v for k, v in BK.breaker_board().snapshot().items()
            if v["failures"] or v["fallbacks"]
        },
    }
    return report, results


def run(nrows: int, nparts: int, backend: str, rates: list, think_s: float,
        seed: int, exec_unit_rate: float = 0.0) -> dict:
    # the correctness oracle: fault-free, numpy backend, worker off
    BK.reset_breakers()
    s_ref, df_ref = make_session(nrows, nparts, "numpy", plan=None)
    ref = interaction_script(s_ref, df_ref, think_s=0.0)

    # throwaway fault-free pass: jit compilation of every (op, shape-bucket)
    # executable happens here, off the measured runs' clocks (the process-wide
    # compile cache serves all later sessions) — otherwise the first measured
    # rate pays multi-second compile stalls the others don't
    run_rate(nrows, nparts, backend, 0.0, min(think_s, 0.05), seed)

    per_rate = []
    for rate in rates:
        report, results = run_rate(
            nrows, nparts, backend, rate, think_s, seed,
            exec_unit_rate=exec_unit_rate if rate > 0 else 0.0,
        )
        report["results_exact"] = len(results) == len(ref) and all(
            pydict_equal(a, b) for a, b in zip(results, ref)
        )
        per_rate.append(report)

    return {
        "nrows": nrows,
        "nparts": nparts,
        "backend": backend,
        "think_s": think_s,
        "seed": seed,
        "rates": per_rate,
        "all_exact": all(r["results_exact"] for r in per_rate),
        "all_workers_alive": all(r["worker_alive"] for r in per_rate),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nrows", type=int, default=500_000)
    ap.add_argument("--nparts", type=int, default=64)
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--rates", default="0,0.01,0.1",
                    help="comma-separated injected kernel-failure rates")
    ap.add_argument("--think", type=float, default=0.3,
                    help="think time between interactions (wall seconds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_faults.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-rows CI chaos check at a nonzero fault rate "
                         "(no JSON written)")
    args = ap.parse_args()
    if args.smoke:
        # chaos rate 0.3: batched dispatches draw once per *batch*, so a tiny
        # smoke run only reaches a few dozen prospective injection points —
        # 0.3 makes a zero-fire run (which would fail the injected>0 gate)
        # vanishingly unlikely while the exactness invariant still holds
        report = run(20_000, 8, args.backend, rates=[0.0, 0.3],
                     think_s=0.05, seed=args.seed, exec_unit_rate=0.3)
        assert report["all_workers_alive"], "background worker died under faults"
        assert report["all_exact"], "interactive results diverged under faults"
        chaos = report["rates"][-1]
        injected = sum(
            (chaos["faults_injected"] or {}).get("fired", {}).values()
        )
        assert injected > 0, "chaos smoke injected no faults"
        print("SMOKE OK:", json.dumps({
            "all_exact": report["all_exact"],
            "all_workers_alive": report["all_workers_alive"],
            "faults_injected": injected,
            "faults_absorbed": chaos["background_faults_absorbed"],
        }))
        return
    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    report = run(args.nrows, args.nparts, args.backend, rates,
                 args.think, args.seed)
    assert report["all_workers_alive"], "background worker died under faults"
    assert report["all_exact"], "interactive results diverged under faults"
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    for r in report["rates"]:
        lat = r["interactive_latency"]
        print(
            f"rate={r['fault_rate']:<5} p50={lat['p50_ms']}ms "
            f"p95={lat['p95_ms']}ms units/s={r['background_units_per_s']} "
            f"absorbed={r['background_faults_absorbed']} "
            f"exact={r['results_exact']} alive={r['worker_alive']}"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
