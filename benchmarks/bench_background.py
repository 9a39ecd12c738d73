"""Background think-window throughput: serial vs batched+async execution.

The paper's think-time gains are bounded by how much non-critical work the
background loop pushes through before the next interaction.  This benchmark
pins that number down on the xla kernel backend: a real-clock engine over an
evenly-partitioned synthetic table, a queue of non-critical blocking operators
(describe / groupby / value_counts / sorts / filters), and a fixed wall-clock
think window driven through the scheduler loop twice —

* **serial**  — ``batching=False``: one kernel dispatch per partition unit,
  blocking on each result (the pre-batching executor),
* **batched** — ``batching=True``: fused multi-partition ``UnitBatch``
  dispatches sized from the think-time model, pipelined via JAX async
  dispatch (next batch launched before the previous one's results land).

Reported: partition units completed and nodes finished inside the window,
units/s, and the batched/serial throughput ratio.  Two invariants are checked
and recorded alongside: the scheduler's greedy ``plan()`` order is identical
to a brute-force (non-memoised, non-incremental) reference, and every batched
operator result is bit-for-bit equal to its unbatched counterpart.

Run:  PYTHONPATH=src python benchmarks/bench_background.py [--nrows 1000000]
      (--smoke for the tiny CI wiring check)
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


def make_session(nrows: int, nparts: int, backend: str, batching: bool,
                 cost_model_path=None) -> tuple:
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
        catalog=cat, mode="real", kernel_backend=backend, batching=batching,
        speculation=False, cost_model_path=cost_model_path,
    )
    df = s.read_table("fact")
    # even split: the production sharding layout (the hazard-shaped layout is
    # for interactive scans; batches group by shape bucket either way)
    df.node.kwargs = dict(df.node.kwargs)
    df.node.kwargs["partition_bounds"] = uniform_partitions(nrows, nparts)
    return s, df


def enqueue_workload(s: Session, df) -> list:
    """Non-critical blocking operators over the materialised table (enough
    queue depth that the think window never drains it)."""
    eng = s.engine
    nodes = []
    nodes.append(df.describe().node)
    nodes.append(df.groupby("k").agg({"x": "mean", "y": "sum", "z": "max"}).node)
    nodes.append(df.groupby("k").agg({"x": "sum"}).node)
    nodes.append(df.groupby("k").agg({"y": "mean", "z": "min"}).node)
    nodes.append(df["k"].value_counts().node)
    nodes.append(df.sort_values("x").node)
    for col in ("x", "y", "z"):
        nodes.append(
            eng.add(
                "sort_values", parents=[df.node],
                kwargs={"by": col, "ascending": False, "limit": 32},
                est_rows=df.node.est_rows,
            )
        )
    for thresh in (2.0, 5.0, 8.0):
        nodes.append(df[df["x"] > thresh].node)
    nodes.append(df.dropna().node)
    return nodes


def run_window(s: Session, window_s: float, batching: bool) -> dict:
    """Drive the scheduler loop for a fixed wall-clock think window."""
    eng = s.engine
    stats = eng.executor.stats
    u0, n0, b0, ub0 = (
        stats.units_run, stats.nodes_completed, stats.batches_run,
        stats.units_batched,
    )
    deadline = time.monotonic() + window_s
    preempt = lambda: time.monotonic() >= deadline  # noqa: E731
    from repro.core.executor import Preempted

    t0 = time.monotonic()
    while time.monotonic() < deadline:
        node = eng.scheduler.pick(eng.cache.executed_ids())
        if node is None:
            break
        impl = eng.registry[node.op]
        inputs = (
            [eng.cache.get(p) for p in node.parents] if impl.needs_inputs else []
        )
        try:
            value = eng.executor.execute(
                node, inputs, eng.partials, preempt_check=preempt,
                batch_budget_s=eng._batch_budget_s() if batching else None,
            )
            eng.cache.put(node, value)
        except Preempted:
            break
    elapsed = time.monotonic() - t0
    units = stats.units_run - u0
    return {
        "window_s": window_s,
        "elapsed_s": round(elapsed, 4),
        "units": units,
        "nodes_completed": stats.nodes_completed - n0,
        "units_per_s": round(units / max(elapsed, 1e-9), 2),
        "batches": stats.batches_run - b0,
        "units_batched": stats.units_batched - ub0,
        "queue_drained": eng.scheduler.pick(eng.cache.executed_ids()) is None,
    }


def prepare(nrows: int, nparts: int, backend: str, batching: bool,
            cost_model_path=None):
    s, df = make_session(nrows, nparts, backend, batching, cost_model_path)
    table = s.engine.value_of(df.node)  # materialise outside the window
    # steady-state regime: columns live device-resident between think-time
    # quanta (the accelerated engine's data model) — upload them off the clock
    # so the window measures dispatch+compute, not one-time transfers
    BK.warm_device_cache(table)
    nodes = enqueue_workload(s, df)
    return s, df, nodes


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


def check_bit_for_bit(nrows: int, nparts: int, backend: str) -> bool:
    """Every workload operator: batched result == unbatched result, exactly."""
    s_a, df_a, nodes_a = prepare(nrows, nparts, backend, batching=True)
    s_b, df_b, nodes_b = prepare(nrows, nparts, backend, batching=False)
    s_a.drain()
    s_b.drain()
    if s_a.engine.executor.stats.units_batched == 0:
        return False  # the batched run must actually have batched something
    for na, nb in zip(nodes_a, nodes_b):
        va = s_a.engine.value_of(na)
        vb = s_b.engine.value_of(nb)
        if not pydict_equal(va.to_pydict(), vb.to_pydict()):
            return False
    return True


def run(nrows: int, nparts: int, window_s: float, backend: str,
        repeats: int) -> dict:
    # warm both code paths (jit compiles, device column caches) off the clock,
    # and persist the calibrated unit costs so the timed sessions size their
    # batches from measured throughput instead of the static defaults — the
    # cross-session persistence workflow a long-lived deployment would use
    import tempfile

    with tempfile.TemporaryDirectory(prefix="bench_background_") as tmp:
        return _run_with_cost_path(
            nrows, nparts, window_s, backend, repeats,
            f"{tmp}/costs.json",
        )


def _run_with_cost_path(nrows: int, nparts: int, window_s: float, backend: str,
                        repeats: int, cost_path: str) -> dict:
    for batching in (False, True):
        s, _, _ = prepare(nrows, nparts, backend, batching,
                          cost_model_path=cost_path)
        s.drain()
        s.engine.save_cost_model()

    serial_runs, batched_runs = [], []
    for _ in range(repeats):
        s, _, _ = prepare(nrows, nparts, backend, batching=False,
                          cost_model_path=cost_path)
        serial_runs.append(run_window(s, window_s, batching=False))
        s, _, _ = prepare(nrows, nparts, backend, batching=True,
                          cost_model_path=cost_path)
        batched_runs.append(run_window(s, window_s, batching=True))

    def best(runs):  # max units: the steady-state capability of the loop
        return max(runs, key=lambda r: r["units"])

    serial, batched = best(serial_runs), best(batched_runs)
    s_last, _, _ = prepare(nrows, nparts, backend, batching=True,
                           cost_model_path=cost_path)
    report = {
        "nrows": nrows,
        "nparts": nparts,
        "backend": backend,
        "window_s": window_s,
        "repeats": repeats,
        "serial": serial,
        "batched": batched,
        # rate-normalised: a deadline-straddling batch (and its combine) runs
        # to completion past the window, so raw unit counts cover unequal
        # elapsed times — units/s credits exactly the time actually spent
        "speedup_units_per_window": round(
            batched["units_per_s"] / max(serial["units_per_s"], 1e-9), 3
        ),
        "plan_order_unchanged": check_plan_order(s_last),
        "batched_bit_for_bit": check_bit_for_bit(nrows, nparts, backend),
        "calibration_s_per_row": {
            f"{op}|{bk}": cost
            for (op, bk), cost in sorted(s_last.engine.cost_model.calibrate().items())
        },
    }
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nrows", type=int, default=1_000_000)
    ap.add_argument("--nparts", type=int, default=128)
    ap.add_argument("--window", type=float, default=2.0,
                    help="think window (wall seconds)")
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_background.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-rows CI wiring check (no JSON written)")
    args = ap.parse_args()
    if args.smoke:
        report = run(20_000, 8, 0.2, args.backend, repeats=1)
        assert report["batched"]["units"] > 0, "batched window ran no units"
        assert report["plan_order_unchanged"], "scheduler plan order changed"
        assert report["batched_bit_for_bit"], "batched results diverged"
        print("SMOKE OK:", json.dumps(
            {k: report[k] for k in ("speedup_units_per_window",
                                    "plan_order_unchanged",
                                    "batched_bit_for_bit")}))
        return
    report = run(args.nrows, args.nparts, args.window, args.backend,
                 args.repeats)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    print(
        f"units/s: serial={report['serial']['units_per_s']} "
        f"batched={report['batched']['units_per_s']} "
        f"({report['speedup_units_per_window']}x); "
        f"plan_order_unchanged={report['plan_order_unchanged']} "
        f"bit_for_bit={report['batched_bit_for_bit']}"
    )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
