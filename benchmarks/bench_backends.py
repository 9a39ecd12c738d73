"""Columnar kernel backend comparison on the paper's blocking-operator partials.

Measures the per-partition *partial* computations (the think-time preemption
quanta of paper §5.1) under each CPU-capable frame backend:

* ``numpy``     — the scalar host reference in `repro.frame.blocking`,
* ``xla``       — the jit'd jnp kernel math (`repro.kernels.ref`),
* ``interpret`` — the Pallas kernels in interpret mode (correctness path;
                  orders of magnitude slower on CPU, so it runs at a reduced
                  row count recorded alongside its timing).

Writes ``BENCH_backends.json`` and demonstrates the cost-model calibration
workflow: every measurement is fed to ``CostModel.add_sample`` and the fitted
per-(op, backend) unit costs are included in the report, ready to drive
virtual-clock simulations with backend-faithful costs.

Run:  PYTHONPATH=src python benchmarks/bench_backends.py [--nrows 1000000]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Callable, Dict

sys.path.insert(0, "src")

import numpy as np

from repro.core.costmodel import CostModel
from repro.frame import backend as BK
from repro.frame import from_pydict
from repro.frame.planner import Planner
from repro.frame.table import Partition

N_CATEGORIES = 64
N_JOIN_KEYS = 1024  # broadcast dim-table size for the join probe
# the paper's canonical blocking interaction: df.groupby(k).mean() (Fig. 2)
AGGS = (
    ("x", "x", "mean"),
    ("y", "y", "mean"),
    ("z", "z", "mean"),
)


def make_partition(nrows: int, seed: int = 0) -> Partition:
    rng = np.random.default_rng(seed)
    y = rng.uniform(0.0, 10.0, nrows)
    y[rng.random(nrows) < 0.2] = np.nan
    cats = np.array([f"c{i:03d}" for i in range(N_CATEGORIES)])
    # f32 columns: the storage dtype an accelerated engine would pick, and the
    # only float dtype the compaction kernel moves losslessly
    table = from_pydict(
        {
            "x": rng.normal(5.0, 2.0, nrows).astype(np.float32),
            "y": y.astype(np.float32),
            "z": rng.exponential(1.0, nrows).astype(np.float32),
            "k": cats[rng.integers(0, N_CATEGORIES, nrows)],
            # fact-table foreign key; 20% of the id space misses the dim table
            "id": rng.integers(0, N_JOIN_KEYS + N_JOIN_KEYS // 4, nrows),
        },
        npartitions=1,
    )
    return table.partitions[0]


def make_dim(seed: int = 1):
    rng = np.random.default_rng(seed)
    return from_pydict(
        {
            "id": np.arange(N_JOIN_KEYS, dtype=np.int64),
            "w": rng.normal(0.0, 1.0, N_JOIN_KEYS).astype(np.float32),
        }
    )


DIM = make_dim()


# --- workloads: op name -> (cost-model op class, fn(part, backend)) ----------


def _describe(part, bk):
    # pinned column set: keeps the row comparable across runs even as the
    # bench table grows columns for other workloads
    return BK.partial_stats(part, cols=("x", "y", "z"), backend=bk)


def _groupby(part, bk):
    return BK.partial_groupby(part, "k", AGGS, backend=bk)


def _value_counts(part, bk):
    return BK.partial_value_counts(part, "k", backend=bk)


def _topk_sort(part, bk):
    return BK.partial_sort(part, "x", True, 32, backend=bk)


def _full_sort(part, bk):
    return BK.partial_sort(part, "x", True, None, backend=bk)


def _join_inner(part, bk):
    return BK.join_partition(part, DIM, "id", "inner", backend=bk)


def _filter_select(part, bk):
    keep = np.asarray(part.columns["x"].data) > 5.0
    return BK.select_rows(part, keep, backend=bk)


WORKLOADS: Dict[str, tuple] = {
    "describe_partial": ("describe", _describe),
    "groupby_partial": ("groupby_agg", _groupby),
    "value_counts_partial": ("value_counts", _value_counts),
    # the two sort regimes have opposite backend verdicts (12× win vs 5×
    # loss) and calibrate under split planning keys, never one curve
    "topk_sort_partial": ("sort_values:topk", _topk_sort),
    "full_sort_partial": ("sort_values:full", _full_sort),
    "join_partial": ("join", _join_inner),
    "filter_select": ("filter", _filter_select),
}

# workload name -> the planner key its dispatch plans under
PLANNER_WORKLOADS = {
    "describe_partial": "describe",
    "groupby_partial": "groupby_agg",
    "value_counts_partial": "value_counts",
    "topk_sort_partial": "sort_values:topk",
    "full_sort_partial": "sort_values:full",
    "filter_select": "filter",
}


def planner_workloads(report: dict, cold: Planner, calibrated_cm: CostModel) -> dict:
    """Per-workload planner verdicts over the measured forced-backend rows.

    ``planned_backend`` is the cold-start choice (priors only — what the
    very first session does); ``calibrated_backend`` re-plans from this
    run's fitted costs (what a warmed session does).  ``planner_seconds``
    is the chosen backend's measured median — the planner's own overhead is
    a dict lookup and two multiplies, below timer resolution — and
    ``ratio_vs_best_single`` is how close that lands to the best single
    backend (1.0 = the planner picked the winner)."""
    calib = Planner(calibrated_cm, use_priors=False)
    out: dict = {}
    for name, key in PLANNER_WORKLOADS.items():
        entry = report["workloads"].get(name)
        if entry is None or "xla" not in entry or "numpy" not in entry:
            continue
        rows = entry["xla"]["rows"]
        chosen = cold.choose(key, rows, "xla")
        planner_s = entry[chosen]["seconds"]
        best_s = min(entry[bk]["seconds"] for bk in ("numpy", "xla"))
        out[name] = {
            "key": key,
            "planned_backend": chosen,
            "calibrated_backend": calib.choose(key, rows, "xla"),
            "planner_seconds": planner_s,
            "ratio_vs_best_single": round(best_s / max(planner_s, 1e-12), 4),
        }
        print(f"{name:>22s}  planner->{chosen:>6s}  "
              f"{planner_s * 1e3:9.3f} ms  "
              f"({out[name]['ratio_vs_best_single']:.3f}x of best single)",
              flush=True)
    return out


def run_fusion(nrows: int, warmup: int, repeats: int, planner: Planner) -> dict:
    """Fused filter→op composites vs the equivalent two-dispatch plan.

    The unfused side is the *honest* alternative the planner would run:
    ``select_rows`` on numpy (its verdict for the filter stage) feeding the
    xla partial.  Results are bit-identical by the fusion parity contract
    (``tests/test_fused.py``); this phase times them."""
    part = make_partition(nrows, seed=5)
    keep = np.asarray(part.columns["x"].data) > 5.0
    chains = {
        "fused:filter|describe": (
            lambda: BK.fused_stats_partition(
                part, keep, cols=("x", "y", "z"), backend="xla"
            ),
            lambda: BK.partial_stats(
                BK.select_rows(part, keep, backend="numpy"),
                cols=("x", "y", "z"), backend="xla",
            ),
        ),
        "fused:filter|groupby_agg": (
            lambda: BK.fused_groupby_partition(part, keep, "k", AGGS, backend="xla"),
            lambda: BK.partial_groupby(
                BK.select_rows(part, keep, backend="numpy"), "k", AGGS, backend="xla"
            ),
        ),
        "fused:filter|sort_values:topk": (
            lambda: BK.fused_topk_partition(part, keep, "x", True, 32, backend="xla"),
            lambda: BK.partial_sort(
                BK.select_rows(part, keep, backend="numpy"), "x", True, 32,
                backend="xla",
            ),
        ),
    }
    out: dict = {}
    for key, (fused_fn, unfused_fn) in chains.items():
        op2 = key.split("|", 1)[1]
        fuses = planner.choose_fusion(key, "xla", part.nrows, ["filter", op2])
        for _ in range(warmup):
            fused_fn()
            unfused_fn()
        ft, ut = [], []
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = fused_fn()
            ft.append(time.perf_counter() - t0)
            assert r is not None, f"{key}: fused kernel declined"
            t0 = time.perf_counter()
            unfused_fn()
            ut.append(time.perf_counter() - t0)
        fs, us = float(np.median(ft)), float(np.median(ut))
        out[key] = {
            "rows": part.nrows,
            "planner_fuses": fuses,
            "fused_seconds": fs,
            "unfused_seconds": us,
            "speedup_fused_vs_unfused": round(us / max(fs, 1e-12), 3),
        }
        print(f"{key:>30s}  fused {fs * 1e3:9.3f} ms  "
              f"unfused {us * 1e3:9.3f} ms  "
              f"{out[key]['speedup_fused_vs_unfused']:6.3f}x", flush=True)
    return out


def run(nrows: int, interpret_nrows: int, warmup: int, repeats: int,
        skip_interpret: bool = False) -> dict:
    backends = ["numpy", "xla"] + ([] if skip_interpret else ["interpret"])
    parts = {
        "numpy": make_partition(nrows),
        "xla": make_partition(nrows),
        "interpret": make_partition(interpret_nrows),
    }
    cm = CostModel()
    report: dict = {
        "nrows": nrows,
        "interpret_nrows": interpret_nrows,
        "warmup": warmup,
        "repeats": repeats,
        "workloads": {},
    }
    for name, (op, fn) in WORKLOADS.items():
        # warm every backend first (absorbs jit compiles), then interleave the
        # timed rounds across backends so slow system drift (shared-CPU
        # throttling) cannot bias one backend's median
        for bk in backends:
            for _ in range(warmup):
                fn(parts[bk], bk)
        times: Dict[str, list] = {bk: [] for bk in backends}
        for _ in range(repeats):
            for bk in backends:
                t0 = time.perf_counter()
                fn(parts[bk], bk)
                times[bk].append(time.perf_counter() - t0)
        entry: dict = {}
        for bk in backends:
            secs = float(np.median(times[bk]))
            entry[bk] = {"rows": parts[bk].nrows, "seconds": secs}
            cm.add_sample(op, bk, parts[bk].nrows, secs)
            print(f"{name:>22s}  {bk:>9s}  {parts[bk].nrows:>9d} rows  "
                  f"{secs * 1e3:9.3f} ms", flush=True)
        if "xla" in entry:
            entry["speedup_xla_vs_numpy"] = round(
                entry["numpy"]["seconds"] / max(entry["xla"]["seconds"], 1e-12), 3
            )
        report["workloads"][name] = entry
    fitted = cm.calibrate()
    report["calibration_s_per_row"] = {
        f"{op}|{bk}": cost for (op, bk), cost in sorted(fitted.items())
    }
    # -- planner phase: cold-start verdicts, calibrated re-plans, fusion ------
    cold = Planner(CostModel())  # fresh model: decisions come from the priors
    wl = planner_workloads(report, cold, cm)
    fusion = run_fusion(nrows, warmup, max(repeats, 2), cold)
    report["planner"] = {
        "workloads": wl,
        "fusion": fusion,
        # prior-based decision counters: pure arithmetic over the committed
        # priors, so identical on every machine — the drift gate pins them
        "decisions": cold.cost_model.planner_report(),
    }
    return report


def check_drift(report: dict, baseline_path: str, rel_tol: float) -> dict:
    """Cost-model drift alert: compare this run's fitted unit costs against
    the committed baseline's ``calibration_s_per_row`` and return the keys
    whose cost moved more than ``rel_tol``× either way.  CI runs this on the
    smoke fit with a generous tolerance — the target is calibration
    *regressions* (a fit collapsing to the floor, a kernel going an order of
    magnitude slower), not machine-to-machine noise.

    The planner's prior-based decision counters are compared *exactly*: they
    are deterministic arithmetic over the committed priors, so any mismatch
    means the planner's verdicts changed — a behaviour change that must show
    up in a diff of the committed baseline, never silently."""
    cm = CostModel()
    for key, cost in report["calibration_s_per_row"].items():
        op, _, bk = key.rpartition("|")  # fused op keys contain "|"
        cm._backend_unit_cost[(op, bk)] = float(cost)
    with open(baseline_path) as f:
        baseline = json.load(f)
    drift = cm.drift_report(
        baseline.get("calibration_s_per_row", {}), rel_tol=rel_tol
    )
    bad = {k: v for k, v in drift.items() if v["status"] == "drift"}
    base_dec = baseline.get("planner", {}).get("decisions", {})
    cur_dec = report.get("planner", {}).get("decisions", {})
    for k in sorted(set(base_dec) | set(cur_dec)):
        if base_dec.get(k, 0) != cur_dec.get(k, 0):
            bad[f"planner_decision:{k}"] = {
                "status": "decision_flip",
                "baseline": base_dec.get(k, 0),
                "current": cur_dec.get(k, 0),
            }
    return bad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nrows", type=int, default=1_000_000)
    ap.add_argument("--interpret-nrows", type=int, default=32_768,
                    help="row count for the (slow) Pallas interpret backend")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--skip-interpret", action="store_true")
    ap.add_argument("--out", default="BENCH_backends.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-rows CI wiring check (no JSON written)")
    ap.add_argument("--check-drift", metavar="BASELINE_JSON", default=None,
                    help="fail if fitted unit costs drifted > --drift-tol x "
                         "from the baseline's calibration_s_per_row")
    ap.add_argument("--drift-tol", type=float, default=50.0,
                    help="relative drift tolerance (either direction)")
    args = ap.parse_args()
    if args.smoke:
        report = run(20_000, 4_096, warmup=1, repeats=1)
        assert report["workloads"], "no workloads ran"
        assert report["calibration_s_per_row"], "calibration produced no fits"
        planner = report.get("planner", {})
        assert planner.get("workloads"), "planner section missing"
        assert planner.get("decisions"), "planner recorded no decisions"
        # the headline demotion: planner-chosen value_counts must beat the
        # forced-xla dispatch it exists to avoid
        vc = planner["workloads"]["value_counts_partial"]
        xla_s = report["workloads"]["value_counts_partial"]["xla"]["seconds"]
        assert vc["planner_seconds"] < xla_s, (
            f"planner value_counts {vc['planner_seconds']:.6f}s not faster "
            f"than forced xla {xla_s:.6f}s"
        )
        print("SMOKE OK:", len(report["workloads"]), "workloads,",
              len(report["calibration_s_per_row"]), "fitted costs,",
              len(planner["workloads"]), "planner verdicts")
        if args.check_drift:
            drifted = check_drift(report, args.check_drift, args.drift_tol)
            if drifted:
                print("CALIBRATION DRIFT:", json.dumps(drifted, indent=2))
                sys.exit(1)
            print(f"DRIFT OK: within {args.drift_tol}x of {args.check_drift}")
        return
    report = run(args.nrows, args.interpret_nrows, args.warmup, args.repeats,
                 skip_interpret=args.skip_interpret)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"\nwrote {args.out}")
    for probe in ("describe_partial", "groupby_partial"):
        sp = report["workloads"][probe].get("speedup_xla_vs_numpy")
        print(f"{probe}: xla is {sp}x vs numpy at {report['nrows']} rows")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
