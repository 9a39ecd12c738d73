"""Progressive interaction latency: time-to-first-bounded-estimate vs exact.

The tentpole claim of the progressive interaction path: a blocking
interaction on a cold node returns a statistically bounded estimate after
executing only a small sample-first seed of partitions, then upgrades in
place to the bit-for-bit exact answer.  This benchmark pins the latency gap
on the xla kernel backend at 1M rows x 128 evenly-split partitions:

* **t_exact** — wall time of the ordinary blocking interaction
  (``session.show``): all partitions + combine before anything returns;
* **t_first** — wall time of ``session.interact(..., progressive=True)``
  returning a usable :class:`BoundedEstimate` (seed = total/16 partitions in
  bit-reversal sample-first order);
* **t_upgrade** — additional wall time for the progressive handle to reach
  the exact answer via refinement.

Both paths run unbatched (one kernel dispatch per partition unit) in the
same session configuration, so the ratio isolates the *scheduling* change —
how much work stands between the user and a bounded answer — rather than
dispatch fusion effects.  Invariants checked and recorded alongside:

* the completed progressive result is bit-for-bit equal to the exact path;
* estimate coverage is monotone and reaches 1.0;
* the background scheduler's greedy plan order is identical to the
  brute-force ``reference_pick`` oracle (the exact path is untouched).

Run:  PYTHONPATH=src python benchmarks/bench_progressive.py [--nrows 1000000]
      (--smoke for the tiny CI wiring check; asserts, writes no JSON)
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame import backend as BK
from repro.frame.partitioner import uniform_partitions
from repro.frame.table import pydict_equal

N_CATEGORIES = 64


def make_session(nrows: int, nparts: int, backend: str):
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
    # planner=False pins every unit to the forced kernel tier: the adaptive
    # backend planner re-decides per dispatch from *measured* timings, so two
    # sessions with different execution histories can serve the same unit on
    # different backends (f32 kernel vs f64 numpy) — a ~1e-7 wobble that
    # breaks the bit-for-bit comparison this benchmark pins down
    s = Session(
        catalog=cat, mode="real", kernel_backend=backend, batching=False,
        speculation=False, planner=False,
    )
    df = s.read_table("fact")
    df.node.kwargs = dict(df.node.kwargs)
    df.node.kwargs["partition_bounds"] = uniform_partitions(nrows, nparts)
    return s, df


def prepare(nrows: int, nparts: int, backend: str):
    """Materialise + device-warm the source table off the clock, so the timed
    section measures the blocking operator itself, not the scan."""
    s, df = make_session(nrows, nparts, backend)
    table = s.engine.value_of(df.node)
    BK.warm_device_cache(table)
    return s, df


QUERIES = ("describe", "groupby_mean", "value_counts")


def _query(df, q):
    if q == "describe":
        return df.describe()
    if q == "groupby_mean":
        return df.groupby("k").mean()
    return df["k"].value_counts()


def check_plan_order(s: Session) -> bool:
    """Incremental scheduler vs its brute-force oracle: identical greedy order."""
    eng = s.engine
    done = set(eng.cache.executed_ids())
    plan = [n.nid for n in eng.scheduler.plan(set(done))]
    ref: list = []
    ref_done = set(done)
    while True:
        nxt = eng.scheduler.reference_pick(ref_done)
        if nxt is None:
            break
        ref.append(nxt.nid)
        ref_done.add(nxt.nid)
    return plan == ref


def _stage(s: Session, df, q):
    """Build the query and materialise its *parents* off the clock (e.g. the
    projection feeding value_counts).  Both paths pay parent materialisation
    identically; the timed section isolates the blocking operator itself —
    the partition units + combine the progressive path restructures."""
    h = _query(df, q)
    for p in h.node.parents:
        s.engine.value_of(p)
    return h


def bench_query(nrows: int, nparts: int, backend: str, q: str) -> dict:
    # exact path: cold blocking interaction on a fresh prepared session
    s_e, df_e = prepare(nrows, nparts, backend)
    h_e = _stage(s_e, df_e, q)
    t0 = time.monotonic()
    exact = s_e.show(h_e)
    t_exact = time.monotonic() - t0

    # progressive path: same query, fresh session, same seed data
    s_p, df_p = prepare(nrows, nparts, backend)
    h_p = _stage(s_p, df_p, q)
    t0 = time.monotonic()
    pr = s_p.interact(h_p, progressive=True)
    first = pr.estimate()
    t_first = time.monotonic() - t0

    covs = [first.coverage]
    t0 = time.monotonic()
    for est in pr:
        covs.append(est.coverage)
        if est.exact:
            final = est.value
            break
    t_upgrade = time.monotonic() - t0

    same = pydict_equal(final.to_pydict(), exact.to_pydict())
    return {
        "query": q,
        "t_exact_s": round(t_exact, 4),
        "t_first_estimate_s": round(t_first, 4),
        "t_upgrade_s": round(t_upgrade, 4),
        "speedup_first_vs_exact": round(t_exact / max(t_first, 1e-9), 2),
        "first_coverage": round(first.coverage, 4),
        "first_n_intervals": len(first.intervals),
        "coverage_monotone": all(b >= a for a, b in zip(covs, covs[1:])),
        "final_coverage": covs[-1],
        "final_bit_for_bit": same,
        "plan_order_unchanged": check_plan_order(s_p),
    }


def run(nrows: int, nparts: int, backend: str, repeats: int) -> dict:
    # warm jit compiles off the clock (process-global cache): one full pass
    # of every query on a small warmup session
    s_w, df_w = prepare(min(nrows, 20_000), min(nparts, 8), backend)
    for q in QUERIES:
        s_w.show(_query(df_w, q))
        pr = s_w.interact(_query(df_w, q), progressive=True)
        pr.upgrade()

    queries = {}
    for q in QUERIES:
        runs = [bench_query(nrows, nparts, backend, q) for _ in range(repeats)]
        # best-of: the steady-state latency floor of each path
        best = min(runs, key=lambda r: r["t_first_estimate_s"])
        best["t_exact_s"] = min(r["t_exact_s"] for r in runs)
        best["speedup_first_vs_exact"] = round(
            best["t_exact_s"] / max(best["t_first_estimate_s"], 1e-9), 2
        )
        best["all_bit_for_bit"] = all(r["final_bit_for_bit"] for r in runs)
        best["all_plan_order_unchanged"] = all(
            r["plan_order_unchanged"] for r in runs
        )
        queries[q] = best
    return {
        "nrows": nrows,
        "nparts": nparts,
        "backend": backend,
        "repeats": repeats,
        "seed_fraction": "1/16",
        "queries": queries,
        "min_speedup_first_vs_exact": min(
            v["speedup_first_vs_exact"] for v in queries.values()
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nrows", type=int, default=1_000_000)
    ap.add_argument("--nparts", type=int, default=128)
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_progressive.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-rows CI wiring check (no JSON written)")
    args = ap.parse_args()
    if args.smoke:
        report = run(20_000, 8, args.backend, repeats=1)
        for q, r in report["queries"].items():
            assert r["first_coverage"] < 1.0, \
                f"{q}: first estimate waited for full execution"
            assert r["coverage_monotone"], f"{q}: coverage not monotone"
            assert r["final_coverage"] == 1.0, f"{q}: coverage never reached 1.0"
            assert r["final_bit_for_bit"], f"{q}: completed result != exact"
            assert r["plan_order_unchanged"], f"{q}: scheduler plan order changed"
        print("SMOKE OK:", json.dumps({
            q: {k: r[k] for k in ("first_coverage", "final_bit_for_bit",
                                  "plan_order_unchanged")}
            for q, r in report["queries"].items()
        }))
        return
    report = run(args.nrows, args.nparts, args.backend, args.repeats)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    for q, r in report["queries"].items():
        print(
            f"{q}: first={r['t_first_estimate_s']}s exact={r['t_exact_s']}s "
            f"({r['speedup_first_vs_exact']}x) bit_for_bit={r['final_bit_for_bit']} "
            f"plan_order={r['plan_order_unchanged']}"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
