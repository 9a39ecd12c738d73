"""Device-sharded partition execution: collective combine vs host merge.

The data-mesh path (``frame/dist.py``) runs ONE shard_map over every
partition of a blocking operator and lowers the combine to collectives
inside the jit, replacing P per-partition kernel dispatches plus the
host-side merge loop.  On emulated host devices (single core) the win is
dispatch amortisation, not parallelism — one collective dispatch carries a
whole table.  This benchmark pins that down against the xla host path the
sharded kernels replicate bit-for-bit:

* **combine** — describe / mean / groupby_agg / value_counts / top-k sort at
  1M rows x 128 partitions: per-partition xla partials + host merge vs one
  collective dispatch, bit-equality checked on every trial's results;
* **join build scaling** — right sides above the broadcast byte threshold
  take the partition-parallel build (sort sharded across ``data``, probe
  local); build time vs the broadcast host build across right-side sizes,
  full join output bit-for-bit;
* **plan_order_unchanged** — the incremental scheduler's greedy plan with
  sharded dispatch live equals the brute-force ``reference_pick`` oracle.

A two-size fit of the sharded timings (``prior_fit``) feeds the planner's
cold-start (op, "sharded") priors (``frame/planner.py``).

Run:  PYTHONPATH=src python benchmarks/bench_dist.py [--nrows 1000000]
      (--smoke for the tiny CI wiring check: bit-equality + nonzero
      collective dispatch counters at 50k rows x 16 partitions)
"""
from __future__ import annotations

import os

# must precede any (transitive) jax import
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("REPRO_JOIN_BROADCAST_MAX", str(1 << 20))

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame import backend as BK
from repro.frame import blocking as B
from repro.frame import dist
from repro.frame.table import PTable, from_pydict, pydict_equal

N_CATEGORIES = 64
TOPK = 32
AGGS = (("x", "x", "mean"), ("y", "y", "sum"))
TRIALS = 5


def make_table(nrows: int, nparts: int, seed: int = 7) -> PTable:
    rng = np.random.default_rng(seed)
    y = rng.normal(3.0, 2.0, nrows)
    y[rng.random(nrows) < 0.2] = np.nan
    cats = np.array([f"c{i:03d}" for i in range(N_CATEGORIES)])
    return from_pydict(
        {
            "x": rng.uniform(0.0, 10.0, nrows),
            "y": y,
            "k": cats[rng.integers(0, N_CATEGORIES, nrows)],
        },
        npartitions=nparts,
    )


def _clear(table: PTable, *keys: str) -> None:
    for k in keys:
        table.__dict__.pop(k, None)
    for p in table.partitions:
        p.__dict__.pop("_dev_stats", None)


def stats_eq(a, b) -> bool:
    if set(a) != set(b):
        return False
    return all(
        tuple(np.float64(x) for x in (a[c].n, a[c].mean, a[c].m2, a[c].mn, a[c].mx))
        == tuple(np.float64(x) for x in (b[c].n, b[c].mean, b[c].m2, b[c].mn, b[c].mx))
        for c in a
    )


def vc_eq(a, b) -> bool:
    return pydict_equal(a.to_pydict(), b.to_pydict())


def gb_eq(a, b) -> bool:
    return pydict_equal(a.to_pydict(), b.to_pydict())


# --------------------------------------------------------------------------- #
# combine: host (P partials + merge) vs sharded (one collective dispatch)      #
# --------------------------------------------------------------------------- #

def _host_stats(table):
    return B.merge_stats(
        [BK.partial_stats(p, backend="xla") for p in table.partitions]
    )


def _combine_cases(table):
    dictionary = table.partitions[0].columns["k"].dictionary
    return {
        "describe": (
            _host_stats,
            lambda t: BK.sharded_stats(t),
            stats_eq,
        ),
        "mean": (
            lambda t: {c: s.mean for c, s in _host_stats(t).items()},
            lambda t: {c: s.mean for c, s in BK.sharded_stats(t).items()},
            lambda a, b: set(a) == set(b)
            and all(np.float64(a[c]) == np.float64(b[c]) for c in a),
        ),
        "groupby_agg": (
            lambda t: B.merge_groupby(
                [
                    BK.partial_groupby(p, "k", AGGS, None, backend="xla")
                    for p in t.partitions
                ],
                "k", AGGS, dictionary, None,
            ),
            lambda t: B.merge_groupby(
                [BK.sharded_groupby(t, "k", AGGS)], "k", AGGS, dictionary, None
            ),
            gb_eq,
        ),
        "value_counts": (
            lambda t: B.merge_value_counts(
                [
                    BK.partial_value_counts(p, "k", backend="xla")
                    for p in t.partitions
                ],
                dictionary, "k",
            ),
            lambda t: B.merge_value_counts(
                [BK.sharded_value_counts(t, "k")], dictionary, "k"
            ),
            vc_eq,
        ),
        "topk": (
            lambda t: B.merge_sort(
                [
                    BK.partial_sort(p, "x", True, TOPK, backend="xla")
                    for p in t.partitions
                ],
                "x", True, TOPK,
            ),
            lambda t: B.merge_sort(
                BK.sharded_topk(t, "x", True, TOPK), "x", True, TOPK
            ),
            lambda a, b: pydict_equal(a.to_pydict(), b.to_pydict()),
        ),
    }


def bench_combine(nrows: int, nparts: int, trials: int = TRIALS) -> dict:
    table = make_table(nrows, nparts)
    out: dict = {}
    for op, (host_fn, sharded_fn, eq) in _combine_cases(table).items():
        # warm both paths: compile, device uploads, plan caches
        host_fn(table)
        if sharded_fn(table) is None:
            raise RuntimeError(f"sharded {op} declined at {nparts} partitions")
        host_ts, sh_ts, bit_equal = [], [], True
        for _ in range(trials):
            _clear(table, "_sharded_raws")
            t0 = time.perf_counter()
            h = host_fn(table)
            host_ts.append(time.perf_counter() - t0)
            _clear(table, "_sharded_raws")
            t0 = time.perf_counter()
            s = sharded_fn(table)
            sh_ts.append(time.perf_counter() - t0)
            bit_equal = bit_equal and eq(h, s)
        host_s = float(np.median(host_ts))
        sharded_s = float(np.median(sh_ts))
        out[op] = {
            "host_xla_s": host_s,
            "sharded_s": sharded_s,
            "speedup": host_s / sharded_s,
            "bit_equal": bool(bit_equal),
        }
    return out


# --------------------------------------------------------------------------- #
# join: partition-parallel build vs broadcast host build                       #
# --------------------------------------------------------------------------- #

def make_join_tables(left_rows: int, right_rows: int, nparts: int):
    rng = np.random.default_rng(11)
    left = from_pydict(
        {
            "j": rng.integers(0, 2 * right_rows, left_rows).astype(np.int64),
            "x": rng.uniform(0.0, 1.0, left_rows),
        },
        npartitions=nparts,
    )
    keys = rng.permutation(right_rows).astype(np.int64)
    right = from_pydict(
        {"j": keys, "w": rng.uniform(0.0, 1.0, right_rows)},
        npartitions=max(2, nparts // 8),
    )
    return left, right


def _join_all(left: PTable, right: PTable) -> PTable:
    return PTable(
        [
            BK.join_partition(p, right, "j", "left", backend="xla")
            for p in left.partitions
        ]
    )


def bench_join(left_rows: int, nparts: int, right_sizes, trials: int = TRIALS) -> dict:
    sizes = []
    for right_rows in right_sizes:
        left, right = make_join_tables(left_rows, right_rows, nparts)
        over = right_rows * 4 > BK.JOIN_BROADCAST_MAX_BYTES
        # host reference: mesh off -> broadcast build regardless of size
        dist.set_mode("off")
        BK._join_build_cached(right, "j")  # warm
        host_ts = []
        for _ in range(trials):
            right.__dict__.pop("_join_build", None)
            t0 = time.perf_counter()
            BK._join_build_cached(right, "j")
            host_ts.append(time.perf_counter() - t0)
        ref = _join_all(left, right)
        dist.set_mode("auto")
        sh_ts, sharded_engaged = [], False
        if over:
            right.__dict__.pop("_join_build", None)
            BK._sharded_join_build_cached(right, "j")  # warm
            for _ in range(trials):
                right.__dict__.pop("_sharded_join", None)
                t0 = time.perf_counter()
                built = BK._sharded_join_build_cached(right, "j")
                sh_ts.append(time.perf_counter() - t0)
                sharded_engaged = sharded_engaged or built is not None
            got = _join_all(left, right)
        else:
            got = _join_all(left, right)
        host_s = float(np.median(host_ts))
        entry = {
            "right_rows": right_rows,
            "above_broadcast_threshold": bool(over),
            "host_build_s": host_s,
            "bit_equal": pydict_equal(got.to_pydict(), ref.to_pydict()),
        }
        if over:
            entry["sharded_build_s"] = float(np.median(sh_ts))
            entry["build_speedup"] = host_s / entry["sharded_build_s"]
            entry["sharded_engaged"] = bool(sharded_engaged)
        sizes.append(entry)
    return {
        "broadcast_max_bytes": BK.JOIN_BROADCAST_MAX_BYTES,
        "left_rows": left_rows,
        "sizes": sizes,
    }


# --------------------------------------------------------------------------- #
# plan-order invariance with sharded dispatch live                             #
# --------------------------------------------------------------------------- #

def check_plan_order_sharded(nrows: int, nparts: int) -> tuple:
    cat = Catalog()
    cat.register(
        TableSpec(
            "fact",
            nrows=nrows,
            cols=(
                ColSpec("x", low=0.0, high=10.0),
                ColSpec("y", null_frac=0.2),
                ColSpec("k", kind="cat", n_categories=N_CATEGORIES),
            ),
            io_seconds=0.0,
            seed=7,
        )
    )
    dist.set_mode("on")
    dist.reset_dispatch_counts()
    try:
        s = Session(catalog=cat, mode="real")
        df = s.read_table("fact")
        s.interact(df.describe())
        s.interact(df["k"].value_counts())
        s.interact(df.groupby("k").agg({"x": "mean"}))
        s.interact(df.sort_values("x").head(10))
        df.mean()  # leave background work for the plan walk
        df.groupby("k").agg({"y": "sum"})
        eng = s.engine
        done = set(eng.cache.executed_ids())
        plan = [n.nid for n in eng.scheduler.plan(set(done))]
        ref, ref_done = [], set(done)
        while True:
            nxt = eng.scheduler.reference_pick(ref_done)
            if nxt is None:
                break
            ref.append(nxt.nid)
            ref_done.add(nxt.nid)
        counts = dict(dist.dispatch_counts())
    finally:
        dist.set_mode("auto")
    return plan == ref, counts


def fit_priors(small: dict, big: dict, rows_small: int, rows_big: int) -> dict:
    """Two-point linear fit of the sharded timings: est(rows) = a*rows + b."""
    fit = {}
    for op in big:
        t1, t2 = small[op]["sharded_s"], big[op]["sharded_s"]
        a = max((t2 - t1) / (rows_big - rows_small), 0.0)
        b = max(t1 - a * rows_small, 1e-6)
        fit[op] = [float(a), float(b)]
    return fit


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nrows", type=int, default=1_000_000)
    ap.add_argument("--nparts", type=int, default=128)
    ap.add_argument("--trials", type=int, default=TRIALS)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI wiring check (50k x 16)")
    args = ap.parse_args()

    if dist.device_count() < 8:
        print(f"FATAL: need 8 emulated devices, have {dist.device_count()} "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        sys.exit(1)

    if args.smoke:
        combine = bench_combine(50_000, 16, trials=2)
        join = bench_join(50_000, 16, right_sizes=(400_000,), trials=2)
        plan_ok, counts = check_plan_order_sharded(50_000, 16)
        assert all(r["bit_equal"] for r in combine.values()), combine
        assert all(r["bit_equal"] for r in join["sizes"]), join
        assert all(r.get("sharded_engaged", True) for r in join["sizes"]), join
        assert plan_ok, "scheduler plan order changed under sharded dispatch"
        assert sum(counts.values()) > 0, "no collective dispatches recorded"
        for fam in ("stats", "value_counts", "groupby", "topk"):
            assert counts.get(fam, 0) > 0, f"no sharded {fam} dispatch: {counts}"
        print("SMOKE OK:", json.dumps({
            "devices": dist.device_count(),
            "dispatch_counts": counts,
            "plan_order_unchanged": plan_ok,
        }))
        return

    rows_small, parts_small = max(args.nrows // 4, 10_000), max(args.nparts // 4, 8)
    combine_small = bench_combine(rows_small, parts_small, trials=args.trials)
    combine = bench_combine(args.nrows, args.nparts, trials=args.trials)
    join = bench_join(
        args.nrows, args.nparts,
        right_sizes=(65_536, 524_288, 1_048_576),
        trials=args.trials,
    )
    plan_ok, counts = check_plan_order_sharded(200_000, 32)

    wins = sum(1 for r in combine.values() if r["speedup"] > 1.0)
    report = {
        "config": {
            "nrows": args.nrows,
            "nparts": args.nparts,
            "devices": dist.device_count(),
            "trials": args.trials,
            "host_reference": "xla",
        },
        "combine": combine,
        "combine_small": {"nrows": rows_small, "nparts": parts_small,
                          **combine_small},
        "combine_wins": wins,
        "join": join,
        "plan_order_unchanged": plan_ok,
        "dispatch_counts": counts,
        "prior_fit": fit_priors(combine_small, combine, rows_small, args.nrows),
    }
    assert all(r["bit_equal"] for r in combine.values()), "combine parity broke"
    assert wins >= 3, f"sharded combine won only {wins}/5 ops"
    assert all(r["bit_equal"] for r in join["sizes"]), "join parity broke"
    assert all(
        r.get("sharded_engaged", True) for r in join["sizes"]
    ), "sharded join build never engaged above threshold"
    assert plan_ok, "scheduler plan order changed under sharded dispatch"

    with open("BENCH_dist.json", "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(
        f"\ncombine wins={wins}/5  "
        + "  ".join(f"{op}={r['speedup']:.2f}x" for op, r in combine.items())
        + f"  plan_order_unchanged={plan_ok}"
    )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
