"""Drive the opportunistic dataframe engine once on a TPU, end to end.

One process, one run of the paper's interaction loop through the normal
``Session`` entry points, on the real clock, over a catalog table of 2^25
rows generated from ``--seed``:

* ``events``: a float column, a float column with 20% nulls, a 64-category
  column and an int join key in [0, 65536);
* ``keys``: a 65,536-row key dimension table (below the broadcast threshold).

The interactions — describe, groupby mean, value_counts, filter→describe, a
join with the dimension table, a think window in which background units
run, sort→head (top-k pushdown) and a progressive interaction upgraded to
exact — run once per kernel backend (``pallas``, then ``xla``); each
interaction reports its latency and the part of it JAX spent tracing and
compiling.  Every answer is checked
against a numpy reference session on the same seed: joins, sorts and row
selection bit for bit, reductions to the f32-accumulation tolerance.  Any
breaker failure or fallback, any numpy-served kernel dispatch, any
background fault and any raised phase fails the run.

``--chips 4`` runs only the sharded phase: the same table over the 4-device
``data`` mesh (describe, mean, groupby, value_counts, top-k and the
partition-parallel join build) against the host ``xla`` path in the same
process, bit for bit.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No accelerator, or no repository around the script: exit non-zero, no
result line.

    python chip_smoke.py [--seed 0] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
FACT_ROWS = 1 << 25
DIM_ROWS = 1 << 16
# the four-chip phase joins a dimension table above the 8 MiB broadcast
# threshold (4-byte keys), so the partition-parallel build engages on size
SHARDED_DIM_ROWS = 1 << 22
TOPK = 10
# long enough for the background compiles of the pending nodes to finish
# (the filter batch's scatter takes seconds to compile at the 2^23 bucket):
# stopping the worker mid-compile would count as a worker stall
THINK_S = 30.0
KERNEL_FAMILIES = ("stats", "groupby", "value_counts", "topk", "filter", "join")
SHARDED_FAMILIES = ("stats", "groupby", "value_counts", "topk", "join_build")
# relative tolerances of f32 accumulation against the f64 numpy reference
# (tests/test_backend_parity.py); count, min and max are exact after the
# describe table's f32 storage
DESCRIBE_RTOL = {"count": 0.0, "mean": 1e-4, "std": 1e-3, "min": 0.0, "max": 0.0}
REDUCE_RTOL = 1e-4


def catalog(seed: int, dim_rows: int = DIM_ROWS):
    from repro.frame import Catalog, ColSpec, TableSpec

    cat = Catalog()
    cat.register(TableSpec(
        "events", nrows=FACT_ROWS, seed=seed,
        cols=(
            ColSpec("x", low=0.0, high=100.0),
            ColSpec("y", null_frac=0.2),
            ColSpec("cat", kind="cat", n_categories=64),
            ColSpec("key", kind="int", low=0, high=DIM_ROWS),
        ),
    ))
    cat.register(TableSpec(
        "keys", nrows=dim_rows, seed=seed + 1,
        cols=(ColSpec("key", kind="key"), ColSpec("w")),
    ))
    return cat


def partition_rows():
    """Row counts of the events table's partitions, as ``read_table`` plans
    them (no I/O cost is declared: the planner's default estimate)."""
    from repro.core.thinktime import ThinkTimeModel
    from repro.frame.partitioner import plan_partitions

    bounds = plan_partitions(FACT_ROWS, FACT_ROWS * 2e-7, ThinkTimeModel())
    return [b - a for a, b in bounds]


def partition_buckets():
    """Kernel shape buckets of the events table's partitions."""
    from repro.kernels.ops import pad_len

    return sorted({pad_len(n) for n in partition_rows()})


# --------------------------------------------------------------------------- #
# result comparison                                                            #
# --------------------------------------------------------------------------- #


def digest(table) -> str:
    """Row-exact fingerprint: validity, payload of valid rows, dictionary."""
    merged = table.concat()
    h = hashlib.sha256()
    for name in merged.order:
        c = merged.columns[name]
        valid = np.asarray(c.valid_mask())
        data = np.asarray(c.data)
        h.update(name.encode())
        h.update(np.packbits(valid).tobytes())
        h.update(np.where(valid, data, np.zeros((), data.dtype)).tobytes())
        if c.dictionary is not None:
            h.update("\x00".join(map(str, c.dictionary)).encode())
    return h.hexdigest()


def _close(a, b, rtol) -> bool:
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        return False
    if a.dtype.kind not in "fc" or rtol == 0.0:
        if a.dtype.kind == "f":
            return bool(np.array_equal(a, b, equal_nan=True))
        return bool(np.array_equal(a, b))
    return bool(np.allclose(a, b, rtol=rtol, atol=0.0, equal_nan=True))


def check_describe(got, ref) -> bool:
    g, r = got.to_pydict(), ref.to_pydict()
    if set(g) != set(r) or list(g["stat"]) != list(r["stat"]):
        return False
    return all(
        _close(g[c][i], r[c][i], DESCRIBE_RTOL[stat])
        for c in g if c != "stat"
        for i, stat in enumerate(g["stat"])
    )


def check_reduction(got, ref) -> bool:
    g, r = got.to_pydict(), ref.to_pydict()
    return set(g) == set(r) and all(_close(g[c], r[c], REDUCE_RTOL) for c in g)


def check_exact(got, ref_digest: str) -> bool:
    return digest(got) == ref_digest


CHECKS = {
    "describe": check_describe,
    "groupby_mean": check_reduction,
    "value_counts": check_exact,
    "topk": check_exact,
    "filter_rows": check_exact,
    "filter_describe": check_describe,
    "join": check_exact,
    "think_groupby_max": check_reduction,
    "think_describe": check_describe,
    "progressive_count": check_exact,
}


# --------------------------------------------------------------------------- #
# the interaction loop                                                         #
# --------------------------------------------------------------------------- #


def interactions(backend: str, seed: int, clock, think: bool = True):
    """Run the loop once in a fresh session; returns (results, latencies,
    compile seconds within each latency, session metrics)."""
    from repro.frame import Session

    s = Session(
        catalog=catalog(seed), mode="real", kernel_backend=backend,
        planner=False, budget_bytes=24 << 30,
    )
    out, lat, comp = {}, {}, {}

    def timed(name, fn):
        t0, c0 = time.perf_counter(), clock.seconds
        out[name] = fn()
        lat[name] = time.perf_counter() - t0
        comp[name] = clock.seconds - c0

    df = s.read_table("events")
    dim = s.read_table("keys")
    timed("describe", lambda: s.show(df.describe()))
    timed("groupby_mean", lambda: s.show(df.groupby("cat").mean()))
    timed("value_counts", lambda: s.show(df["cat"].value_counts()))
    filt = df[df["x"] > 50.0]
    timed("filter_describe", lambda: s.show(filt.describe()))
    out["filter_rows"] = filt.collect()
    timed("join", lambda: s.show(df.join(dim, on="key")))
    # specified but not shown: the think window's background work
    pending = {
        "think_groupby_max": df.groupby("cat").max(),
        "think_describe": df[df["y"] < 0.25].describe(),
    }
    eng = s.engine
    harvested = 0
    if think:
        units0, c0 = eng.executor.stats.units_run, clock.seconds
        eng.start_background()
        time.sleep(THINK_S)
        eng.stop_background()
        harvested = eng.executor.stats.units_run - units0
        comp["think_window"] = clock.seconds - c0
    for name, frame in pending.items():
        timed(name, lambda f=frame: s.show(f))
    # after the think window: the head-of-sort pushdown leaves the full sort
    # pending, and compiling the exact full sort for the TPU takes minutes
    # at these partition sizes (PERF.md), which no think window here absorbs
    timed("topk", lambda: s.show(df.sort_values("x", ascending=False).head(TOPK)))
    t0 = time.perf_counter()
    pr = s.interact(df.groupby("cat").count(), progressive=True)
    est = pr.estimate()
    lat["progressive_first_estimate"] = time.perf_counter() - t0
    out["progressive_count"] = pr.upgrade()
    lat["progressive_count"] = time.perf_counter() - t0
    metrics = dict(
        eng.metrics.summary(),
        think_units_run=harvested,
        progressive_first_coverage=round(est.coverage, 4),
    )
    return out, lat, comp, metrics


def phase_faults(metrics) -> list:
    keys = ("n_background_faults", "worker_stalls", "quarantines",
            "corrupt_results_dropped")
    return [f"{k}={metrics[k]}" for k in keys if metrics[k]]


def dispatch_faults(backend: str, families) -> list:
    """Breaker failures/fallbacks, numpy-served dispatches, unserved families."""
    from repro.frame import backend as BK

    bad = []
    for key, st in BK.breaker_board().snapshot().items():
        if st["failures"] or st["fallbacks"]:
            bad.append(f"breaker {key}: {st}")
    served = BK.served_counts()
    for (op, bk), n in sorted(served.items()):
        if bk == "numpy":
            bad.append(f"{op} served by numpy x{n}")
    for fam in families:
        if not served.get((fam, backend)):
            bad.append(f"{fam} never dispatched on {backend}")
    return bad


def device_peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def emit(tag: str, payload: dict) -> None:
    print(f"{tag} {json.dumps(payload, sort_keys=True, default=str)}", flush=True)


def run_single(args, dev) -> list:
    from repro.frame import backend as BK

    from repro.obs import CompileClock, split_since

    failures = []
    clock = CompileClock()
    t0 = time.perf_counter()
    ref, ref_lat, _, _ = interactions("numpy", args.seed, clock, think=False)
    ref = {name: digest(ref[name]) if check is check_exact else ref[name]
           for name, check in CHECKS.items()}
    emit("reference", {"backend": "numpy", "seconds": time.perf_counter() - t0,
                       "latency_s": ref_lat})
    for backend in ("pallas", "xla"):
        BK.reset_breakers()
        BK.reset_served_counts()
        t0, split0 = time.perf_counter(), clock.split()
        got, lat, comp, metrics = interactions(backend, args.seed, clock)
        split = split_since(clock, split0)
        bad = [f"{name} differs from numpy" for name, check in CHECKS.items()
               if not check(got[name], ref[name])]
        bad += phase_faults(metrics) + dispatch_faults(backend, KERNEL_FAMILIES)
        emit(backend, {
            "seconds": time.perf_counter() - t0,
            "latency_s": lat,
            "compile_s": comp,
            "compiles": split["compile_n"],
            "compile_split": split,
            "served": {f"{op}|{bk}": n
                       for (op, bk), n in sorted(BK.served_counts().items())},
            "metrics": metrics,
            "device_peak_bytes": device_peak_bytes(dev),
            "failures": bad,
        })
        failures += [f"{backend}: {b}" for b in bad]
        del got
        gc.collect()
    return failures


def sharded_session(cat):
    from repro.frame import Session

    # a batch may fill the predicted think window (default: a tenth of it):
    # at 2^25 rows a tenth is below one partition unit's estimate, and the
    # drain would then run unit by unit, never as one sharded batch
    return Session(catalog=cat, mode="real", kernel_backend="xla",
                   planner=False, budget_bytes=24 << 30, batch_loss_frac=1.0)


def sharded_workload(s):
    df = s.read_table("events")
    dim = s.read_table("keys")
    out = {"describe": s.show(df.describe())}
    # background work through the executor's batches (a sharded UnitBatch for
    # the describe family), drained while the think-time model still expects
    # a think window long enough to batch in; the head-of-sort pushdown comes
    # after, so no full sort is pending (PERF.md)
    background = {"describe_xy": df[["x", "y"]].describe(),
                  "groupby_min": df.groupby("cat").min()}
    s.drain()
    out.update({name: s.show(f) for name, f in background.items()})
    out.update({
        "mean": s.show(df.mean()),
        "groupby": s.show(df.groupby("cat").agg({"x": "mean", "y": "sum"})),
        "value_counts": s.show(df["cat"].value_counts()),
        "join": s.show(df.join(dim, on="key")),
        "topk": s.show(df.sort_values("x").head(TOPK)),
    })
    return out


def run_sharded(args, devs) -> list:
    from repro.frame import backend as BK
    from repro.frame import dist

    failures = []
    if len(devs) != 4:
        return [f"--chips 4 needs 4 devices, found {len(devs)}"]
    cat = catalog(args.seed, dim_rows=SHARDED_DIM_ROWS)
    BK.reset_breakers()
    BK.reset_served_counts()
    dist.reset_dispatch_counts()
    t0 = time.perf_counter()
    with dist.use_sharded("on"):
        s = sharded_session(cat)
        got = sharded_workload(s)
        stats = s.engine.executor.stats
    t_sharded = time.perf_counter() - t0
    counts = dist.dispatch_counts()
    if dist.device_count() != 4:
        failures.append(f"data mesh has {dist.device_count()} devices")
    if stats.sharded_batches <= 0:
        failures.append("no sharded executor batch ran")
    for fam in SHARDED_FAMILIES:
        if not counts.get(fam):
            failures.append(f"sharded {fam} never dispatched")
    del s
    gc.collect()
    t0 = time.perf_counter()
    with dist.use_sharded("off"):
        host = sharded_workload(sharded_session(cat))
    t_host = time.perf_counter() - t0
    failures += dispatch_faults("sharded", ())
    for name in got:
        if digest(got[name]) != digest(host[name]):
            failures.append(f"sharded {name} differs from the host xla path")
    emit("sharded", {
        "seconds": t_sharded, "host_seconds": t_host,
        "dispatch_counts": counts,
        "sharded_batches": stats.sharded_batches,
        "units_sharded": stats.units_sharded,
        "device_peak_bytes": [device_peak_bytes(d) for d in devs],
        "failures": failures,
    })
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devs[0].platform})", file=sys.stderr)
        return 2
    emit("setup", {"compile_cache": enable_compile_cache(), "rows": FACT_ROWS,
                   "seed": args.seed, "buckets": partition_buckets()})
    t0 = time.perf_counter()
    failures = run_sharded(args, devs) if args.chips == 4 else run_single(args, devs[0])
    emit("total", {"seconds": time.perf_counter() - t0})
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
