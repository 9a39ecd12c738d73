"""Backend parity: the kernel-dispatch frame backends (xla / interpret) must
agree with the scalar numpy reference on every blocking partial, including
null-masked columns — and the scheduler's memoised graph walks must stay
coherent under DAG growth and cache eviction.

The accelerated backends accumulate in float32, so numeric agreement is to
~1e-4 relative; structural results (keys, row selections, orderings, counts)
must match exactly.
"""
import importlib

import numpy as np
import pytest

from repro.core import CostModel, DAG, Scheduler
from repro.frame import Session, from_pydict
from repro.frame import backend as BK
from repro.frame import blocking as B

CPU_BACKENDS = ["numpy", "xla", "interpret"]
KERNEL_BACKENDS = ["xla", "interpret"]

AGGS = (
    ("s", "x", "sum"),
    ("m", "y", "mean"),
    ("c", "y", "count"),
    ("mn", "x", "min"),
    ("mx", "x", "max"),
)


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(42)
    n = 6_000
    y = rng.uniform(0, 10, n)
    y[rng.random(n) < 0.3] = np.nan  # masked column
    return from_pydict(
        {
            "x": rng.normal(5, 2, n),
            "y": y,
            "k": rng.choice(np.array(["a", "b", "c", "d", "e", "f"]), n),
            "i": rng.integers(0, 50, n),
            "f32": rng.normal(0, 1, n).astype(np.float32),
            "big": rng.integers(2**40, 2**41, n),  # > f32's exact-int range
        },
        npartitions=4,
    )


def _stats_close(a, b):
    assert a.n == b.n
    np.testing.assert_allclose(b.mean, a.mean, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(b.std, a.std, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(b.mn, a.mn, rtol=1e-5)
    np.testing.assert_allclose(b.mx, a.mx, rtol=1e-5)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_describe_stats_parity(table, backend):
    for part in table.partitions:
        ref = B.partial_stats(part)
        got = BK.partial_stats(part, backend=backend)
        assert set(got) == set(ref)
        for name in ref:
            _stats_close(ref[name], got[name])
    # merged across partitions (the combine path)
    merged_ref = B.merge_stats([B.partial_stats(p) for p in table.partitions])
    merged_got = B.merge_stats(
        [BK.partial_stats(p, backend=backend) for p in table.partitions]
    )
    for name in merged_ref:
        _stats_close(merged_ref[name], merged_got[name])


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_groupby_agg_parity(table, backend):
    dictionary = table.partitions[0].columns["k"].dictionary
    ref_parts = [B.partial_groupby(p, "k", AGGS) for p in table.partitions]
    got_parts = [
        BK.partial_groupby(p, "k", AGGS, backend=backend) for p in table.partitions
    ]
    for r, g in zip(ref_parts, got_parts):
        np.testing.assert_array_equal(g["keys"], r["keys"])
    ref = B.merge_groupby(ref_parts, "k", AGGS, dictionary).to_pydict()
    got = B.merge_groupby(got_parts, "k", AGGS, dictionary).to_pydict()
    np.testing.assert_array_equal(got["k"], ref["k"])
    for col in ("s", "m", "c", "mn", "mx"):
        np.testing.assert_allclose(
            np.asarray(got[col], np.float64),
            np.asarray(ref[col], np.float64),
            rtol=1e-4,
            err_msg=col,
        )


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_value_counts_parity(table, backend):
    for part in table.partitions:
        rv, rc = B.partial_value_counts(part, "k")
        gv, gc = BK.partial_value_counts(part, "k", backend=backend)
        np.testing.assert_array_equal(gv, rv)
        np.testing.assert_array_equal(gc, rc)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("by,ascending", [("x", True), ("x", False), ("y", True)])
def test_topk_sort_parity(table, backend, by, ascending):
    k = 12
    for part in table.partitions:
        ref_part, ref_samples = B.partial_sort(part, by, ascending, k)
        got_part, got_samples = BK.partial_sort(part, by, ascending, k, backend=backend)
        assert got_part.nrows == ref_part.nrows == k
        # exact row selection and order (threshold trick must be lossless)
        for col in part.order:
            np.testing.assert_array_equal(
                got_part.columns[col].data, ref_part.columns[col].data, err_msg=col
            )
        np.testing.assert_allclose(got_samples, ref_samples)


def _partitions_equal(got, ref):
    """Bit-for-bit: same column order, same bytes, same validity."""
    assert got.order == ref.order
    for col in ref.order:
        gc, rc = got.columns[col], ref.columns[col]
        assert gc.data.dtype == rc.data.dtype, col
        np.testing.assert_array_equal(gc.data, rc.data, err_msg=col)
        np.testing.assert_array_equal(gc.valid_mask(), rc.valid_mask(), err_msg=col)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize(
    "by,ascending",
    [("x", True), ("x", False), ("y", True), ("y", False), ("k", True), ("big", True)],
)
def test_full_sort_parity(table, backend, by, ascending):
    """Full (non-limit) sort must agree bit-for-bit with numpy's stable f64
    argsort — float keys, null-masked keys (nulls last), string keys (sorted
    dictionary codes), and int64 beyond f32's range — through both the
    per-partition partial and the sample-sort merge."""
    refs = [B.partial_sort(p, by, ascending, None) for p in table.partitions]
    gots = [
        BK.partial_sort(p, by, ascending, None, backend=backend)
        for p in table.partitions
    ]
    for (rp, rs), (gp, gs) in zip(refs, gots):
        _partitions_equal(gp, rp)
        np.testing.assert_array_equal(gs, rs)
    mref = B.merge_sort(refs, by, ascending, None).concat()
    mgot = BK.merge_sort(gots, by, ascending, None, backend=backend).concat()
    _partitions_equal(mgot, mref)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_full_sort_fallbacks_match(backend):
    """Keys outside the exact-split envelope (unmasked NaN; magnitudes that
    overflow f32's hi component; underflowing magnitudes whose residuals land
    below the f32 subnormal grid and collapse to ties) defer to numpy —
    results still match."""
    from repro.frame.table import Column, Partition

    for raw in (
        np.array([5.0, np.nan, 1.0, 3.0, 2.0, np.nan, 0.5]),
        np.array([1e39, -2e39, 3.0, 1e39 / 2, 0.0]),
        np.array([3e-60, 1e-60, 2e-60, -1e-50, 5e-39]),
        np.array([1e-40, -1e-40, 0.0, 2e-44, 3e-44]),
    ):
        part = Partition({"x": Column(data=raw)})
        ref, _ = B.partial_sort(part, "x", True, None)
        got, _ = BK.partial_sort(part, "x", True, None, backend=backend)
        _partitions_equal(got, ref)


# --------------------------------------------------------------------------- #
# join                                                                         #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def dim_table():
    rng = np.random.default_rng(7)
    w = rng.normal(0, 1, 40)
    w[::5] = np.nan  # null right values: gathered nulls stay null
    return from_pydict(
        {
            "i": np.arange(40),  # matches ~80% of table's "i" in [0, 50)
            "w": w,
            "label": np.array([f"n{j}" for j in range(40)]),
        }
    )


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_parity(table, dim_table, backend, how):
    """Inner and left broadcast joins agree bit-for-bit with the numpy
    reference: row selection, gathered right values, and the null masks for
    left-join misses and null right-side values."""
    for part in table.partitions:
        ref = B.join_partition(part, dim_table, "i", how)
        got = BK.join_partition(part, dim_table, "i", how, backend=backend)
        _partitions_equal(got, ref)
        if how == "left":
            # keys 40..49 miss the dim table: the gathered columns are null
            miss = np.asarray(part.columns["i"].data) >= 40
            assert miss.any()
            assert not got.columns["w"].valid_mask()[miss].any()


@pytest.mark.parametrize("backend", CPU_BACKENDS)
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("case", ["all_hit", "misses", "null_keys"])
def test_chunked_join_assembly_parity(monkeypatch, backend, how, case):
    """From ``2 · ORDER_CHUNK`` rows the probe's band order and the join's
    assembly run in chunks on a pool of threads: the joined partition equals
    the one-chunk numpy join's bit for bit, with misses (dropped by inner,
    null in left), null left keys, null right values and a string right
    column, and an inner join that hits every row keeps the left columns."""
    from repro.frame.table import Column, Partition
    JP = importlib.import_module("repro.kernels.join_probe")  # the package exports the function

    rng = np.random.default_rng(11)
    n, m = 10_000, 600
    keys = rng.integers(0, m if case == "all_hit" else m + m // 4, n)
    valid = rng.random(n) > 0.1 if case == "null_keys" else None
    left = Partition({"i": Column(keys, valid), "x": Column(rng.normal(size=n))})
    w = rng.normal(size=m)
    w[::7] = np.nan
    right = from_pydict({
        "i": rng.permutation(m),  # the build's row order is not the keys'
        "w": w,
        "label": np.array([f"p{j}" for j in range(m)]),
    })
    ref = B.join_partition(left, right, "i", how)
    monkeypatch.setattr(JP, "ORDER_CHUNK", 1024)
    assert len(JP.order_cuts(n)) - 1 == 4
    got = BK.join_partition(left, right, "i", how, backend=backend)
    _partitions_equal(got, ref)
    assert got.columns["label"].dictionary is right.partitions[0].columns["label"].dictionary
    assert (got.nrows == n) == (how == "left" or case == "all_hit")
    if how == "inner" and case == "all_hit":
        assert got.columns["x"] is left.columns["x"]


def test_chunked_join_assembly_under_concurrent_callers(monkeypatch):
    """Callers on several threads share the ordering's pool: each chunked
    assembly still equals its one-chunk reference, with the interpreter
    switching threads every few microseconds."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from repro.frame.table import Column, Partition

    JP = importlib.import_module("repro.kernels.join_probe")
    rng = np.random.default_rng(5)
    n, m = 8_000, 300
    right = from_pydict({"i": rng.permutation(m), "w": rng.normal(size=m)})
    lefts = [Partition({"i": Column(rng.integers(0, m + 50, n)),
                        "x": Column(rng.normal(size=n))}) for _ in range(12)]
    refs = [B.join_partition(p, right, "i", how)
            for p, how in zip(lefts, ["inner", "left"] * 6)]
    monkeypatch.setattr(JP, "ORDER_CHUNK", 1024)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(8) as callers:
            futs = [callers.submit(B.join_partition, p, right, "i", how)
                    for p, how in zip(lefts, ["inner", "left"] * 6)]
            got = [f.result(timeout=60) for f in futs]
    finally:
        sys.setswitchinterval(interval)
    for g, r in zip(got, refs):
        _partitions_equal(g, r)


@pytest.mark.parametrize("backend", CPU_BACKENDS)
@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_empty_right(table, backend, how):
    """Empty right table: inner drops every row, left nulls every gathered
    column (regression: the probe used to index into an empty array)."""
    empty = from_pydict({"i": np.array([], np.int64), "w": np.array([])})
    part = table.partitions[0]
    out = BK.join_partition(part, empty, "i", how, backend=backend)
    assert out.order == list(part.order) + ["w"]
    if how == "inner":
        assert out.nrows == 0
    else:
        assert out.nrows == part.nrows
        assert not out.columns["w"].valid_mask().any()
        np.testing.assert_array_equal(
            out.columns["i"].data, part.columns["i"].data
        )


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_join_string_keys_fall_back(backend):
    """String join keys take the numpy path (dictionary codes are per-table,
    so cross-table equality needs decoded strings) — and still match."""
    left = from_pydict(
        {"k": np.array(["a", "b", "z", "b"]), "x": np.arange(4.0)}
    )
    right = from_pydict(
        {"k": np.array(["b", "a", "c"]), "v": np.array([10.0, 20.0, 30.0])}
    )
    for how in ("inner", "left"):
        ref = B.join_partition(left.partitions[0], right, "k", how)
        got = BK.join_partition(left.partitions[0], right, "k", how, backend=backend)
        _partitions_equal(got, ref)
    # decoded values are right: "z" misses, "b" maps to 10
    out = BK.join_partition(left.partitions[0], right, "k", "left", backend=backend)
    got_v = out.columns["v"].to_numpy()
    np.testing.assert_array_equal(got_v[[0, 1, 3]], [20.0, 10.0, 10.0])
    assert np.isnan(got_v[2])


@pytest.mark.parametrize("backend", CPU_BACKENDS)
def test_join_null_keys_never_match(backend):
    """Null join keys never match (pandas semantics) — on the left they miss
    (dropped by inner, nulled by left join); on the right they are excluded
    from the build and do not trip the uniqueness check."""
    from repro.frame.table import Column, Partition
    from repro.frame.table import PTable

    left = Partition(
        {
            "i": Column(
                data=np.array([0, 1, 2, 1], np.int64),
                mask=np.array([True, False, True, True]),
            ),
            "x": Column(data=np.arange(4.0)),
        }
    )
    right = PTable(
        [
            Partition(
                {
                    "i": Column(
                        data=np.array([0, 1, 1], np.int64),
                        mask=np.array([True, True, False]),  # dup is null
                    ),
                    "w": Column(data=np.array([5.0, 6.0, 7.0])),
                }
            )
        ]
    )
    # left row 1 (null key) and row 2 (key 2, absent from right) both miss
    inner = BK.join_partition(left, right, "i", "inner", backend=backend)
    np.testing.assert_array_equal(inner.columns["x"].data, [0.0, 3.0])
    np.testing.assert_array_equal(inner.columns["w"].data, [5.0, 6.0])
    lj = BK.join_partition(left, right, "i", "left", backend=backend)
    np.testing.assert_array_equal(lj.columns["w"].valid_mask(),
                                  [True, False, False, True])


@pytest.mark.parametrize("backend", CPU_BACKENDS)
def test_join_duplicate_right_keys_raise(table, backend):
    dup = from_pydict({"i": np.array([1, 1, 2]), "w": np.arange(3.0)})
    with pytest.raises(ValueError, match="unique"):
        BK.join_partition(table.partitions[0], dup, "i", "inner", backend=backend)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_filter_compaction_parity(table, backend):
    """Row selection is value-exact on every backend: f32 and dictionary
    codes ride the compaction kernel, lossy dtypes (f64, int64 > 2^24) take
    the numpy gather — either way values must match bit-for-bit."""
    for part in table.partitions:
        keep = np.asarray(part.columns["x"].data) > 5.0
        ref = part.select_rows(keep)
        got = BK.select_rows(part, keep, backend=backend)
        assert got.nrows == ref.nrows == int(keep.sum())
        for col in part.order:
            rc, gc = ref.columns[col], got.columns[col]
            assert gc.data.dtype == rc.data.dtype, col
            np.testing.assert_array_equal(gc.data, rc.data, err_msg=col)
            np.testing.assert_array_equal(gc.valid_mask(), rc.valid_mask(), err_msg=col)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_topk_sort_nan_keys_fall_back(backend):
    """Unmasked NaN sort keys (e.g. a merge_groupby mean output) must not
    poison the top-k threshold — the kernel path defers to numpy."""
    # from_pydict would mask the NaNs; build the column with raw NaN, no mask
    from repro.frame.table import Column, Partition

    raw = Partition(
        {"x": Column(data=np.array([5.0, np.nan, 1.0, 3.0, 2.0, 4.0, np.nan, 0.5]))}
    )
    ref_part, _ = B.partial_sort(raw, "x", False, 3)
    got_part, _ = BK.partial_sort(raw, "x", False, 3, backend=backend)
    assert got_part.nrows == ref_part.nrows == 3
    np.testing.assert_array_equal(got_part.columns["x"].data, ref_part.columns["x"].data)


def test_numpy_fallbacks():
    """Unsupported shapes silently fall back to the scalar path."""
    t = from_pydict({"x": np.arange(10.0), "k": np.array(list("ababababab"))})
    p = t.partitions[0]
    # callable agg: not kernel-eligible
    got = BK.partial_groupby(p, "k", (("u", "x", lambda v: float(np.median(v))),),
                             backend="xla")
    ref = B.partial_groupby(p, "k", (("u", "x", lambda v: float(np.median(v))),))
    np.testing.assert_array_equal(got["keys"], ref["keys"])
    # non-dictionary value_counts: falls back
    gv, gc = BK.partial_value_counts(p, "x", backend="xla")
    rv, rc = B.partial_value_counts(p, "x")
    np.testing.assert_array_equal(gv, rv)
    np.testing.assert_array_equal(gc, rc)
    # limit > TOPK_MAX_K: falls back
    sp, _ = BK.partial_sort(p, "x", True, BK.TOPK_MAX_K + 1, backend="xla")
    rp, _ = B.partial_sort(p, "x", True, BK.TOPK_MAX_K + 1)
    np.testing.assert_array_equal(sp.columns["x"].data, rp.columns["x"].data)


def test_backend_resolution_order(monkeypatch):
    pol = BK.BackendPolicy(engine_default="interpret")
    monkeypatch.delenv(BK.ENV_VAR, raising=False)
    assert pol.resolve() == "interpret"  # engine config
    monkeypatch.setenv(BK.ENV_VAR, "xla")
    assert pol.resolve() == "xla"  # env beats engine config
    with BK.use_backend("numpy"):
        assert pol.resolve() == "numpy"  # global beats env
        assert pol.resolve("xla") == "xla"  # per-call beats everything
    assert pol.resolve() == "xla"
    with pytest.raises(ValueError):
        pol.resolve("cuda")


def _run_program(catalog, backend):
    s = Session(catalog=catalog, mode="sim", kernel_backend=backend)
    df = s.read_table("small")
    dim = s.read_table("dim")
    df = df[df["x"] > 2.0]
    return {
        "describe": s.show(df.describe()).to_pydict(),
        "group": s.show(df.groupby("k").mean()).to_pydict(),
        "vc": s.show(df["k"].value_counts()).to_pydict(),
        "sorted": s.show(df.sort_values("y", ascending=False)).to_pydict(),
        "join": s.show(df.join(dim, on="j")).to_pydict(),
    }


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_end_to_end_session_parity(catalog, backend):
    """Same notebook program through the engine on each CPU-capable backend:
    kernel-dispatch answers match the scalar numpy baseline."""
    ref = _run_program(catalog, "numpy")
    got = _run_program(catalog, backend)
    for q in ref:
        assert set(got[q]) == set(ref[q])
        for col in ref[q]:
            r = np.asarray(ref[q][col])
            g = np.asarray(got[q][col])
            if r.dtype.kind in "OU":  # dictionary-decoded strings
                np.testing.assert_array_equal(g, r, err_msg=f"{q}/{col}")
            else:
                np.testing.assert_allclose(
                    g.astype(np.float64),
                    r.astype(np.float64),
                    rtol=2e-3,
                    atol=1e-5,
                    err_msg=f"{q}/{col}",
                )


def test_join_units_feed_calibration(catalog):
    """Join partials record per-backend samples like every other blocking op,
    so calibrate() can fit a unit cost for the probe path.  Join is planned
    now, and the cold priors route the probe to numpy (the committed bench
    verdict), so pin xla with a global override — which bypasses the planner
    by design — to exercise the kernel probe's sample path."""
    s = Session(catalog=catalog, mode="sim", kernel_backend="xla")
    df = s.read_table("small")
    dim = s.read_table("dim")
    with BK.use_backend("xla"):
        s.show(df.join(dim, on="j"))
    cm = s.engine.cost_model
    assert ("join", "xla") in cm.samples()
    fitted = cm.calibrate()
    assert fitted[("join", "xla")] > 0


def test_unit_times_feed_calibration(catalog):
    """Frame units record measured (op, backend, rows, seconds) samples, and
    calibrate() turns them into per-backend unit costs the estimator uses."""
    s = Session(catalog=catalog, mode="sim", kernel_backend="numpy")
    df = s.read_table("small")
    s.show(df.describe())
    cm = s.engine.cost_model
    samples = cm.samples()
    assert ("describe", "numpy") in samples
    rows = sum(r for r, _ in samples[("describe", "numpy")])
    assert rows == 5_000  # every partition's rows were measured
    fitted = cm.calibrate()
    assert fitted[("describe", "numpy")] > 0
    cm.active_backend = "numpy"
    assert cm.unit_cost("describe") == fitted[("describe", "numpy")]
    # unknown backend falls through to the EWMA/default path
    assert cm.unit_cost("describe", backend="pallas") != fitted[("describe", "numpy")]


# --------------------------------------------------------------------------- #
# scheduler memoisation                                                        #
# --------------------------------------------------------------------------- #


def _chain(dag, n, cost=1.0):
    nodes, prev = [], None
    for i in range(n):
        prev = dag.add(
            "synthetic", parents=[prev] if prev else [], kwargs={"cost_s": cost, "tag": str(i)}
        )
        nodes.append(prev)
    return nodes


def test_scheduler_cache_invalidated_on_dag_growth():
    dag = DAG()
    nodes = _chain(dag, 4)
    sched = Scheduler(dag=dag, cost_model=CostModel(), policy="utility")
    u_before = sched.utility(nodes[0], set())
    assert sched._desc_cache  # memo populated
    # growing the DAG must invalidate: the new descendant adds utility
    tail = dag.add("synthetic", parents=[nodes[-1]], kwargs={"cost_s": 5.0, "tag": "t"})
    u_after = sched.utility(nodes[0], set())
    assert u_after > u_before
    assert tail.nid in {n.nid for n in sched._descendants(nodes[0])}


def test_scheduler_cache_invalidated_on_eviction():
    """Shrinking the executed set (cache eviction) must invalidate the
    delivery-cost memo: evicted nodes cost again."""
    dag = DAG()
    nodes = _chain(dag, 3)
    sched = Scheduler(dag=dag, cost_model=CostModel(), policy="utility")
    done = {n.nid for n in nodes[:2]}
    u_done = sched.utility(nodes[2], done)
    u_evicted = sched.utility(nodes[2], set())  # everything evicted
    assert u_evicted > u_done
    # and back again: memo keyed on the executed set, not stale
    assert sched.utility(nodes[2], done) == u_done


def test_scheduler_pick_results_unchanged_by_memo():
    """Memoised pick() returns the same greedy order as a fresh scheduler."""
    rng = np.random.default_rng(3)
    dag = DAG()
    nodes = []
    for i in range(15):
        parents = (
            list(rng.choice(nodes, size=min(len(nodes), int(rng.integers(0, 3))),
                            replace=False))
            if nodes
            else []
        )
        nodes.append(
            dag.add("synthetic", parents=parents,
                    kwargs={"cost_s": float(rng.uniform(0.5, 2.0)), "tag": str(i)})
        )
    cm = CostModel()
    memo = Scheduler(dag=dag, cost_model=cm, policy="utility")
    order, done = [], set()
    while True:
        nxt = memo.pick(done)
        if nxt is None:
            break
        # a fresh scheduler (cold caches) must agree at every step
        fresh = Scheduler(dag=dag, cost_model=cm, policy="utility")
        assert fresh.pick(done).nid == nxt.nid
        order.append(nxt.nid)
        done.add(nxt.nid)
    assert len(order) == len(dag)


def test_real_mode_background_busy_accrues(catalog):
    """The real-mode worker accounts its busy time (regression: += 0.0)."""
    import time as _time

    s = Session(catalog=catalog, mode="real")
    df = s.read_table("small")
    df.describe()  # specified, never displayed → background work
    s.engine.start_background()
    deadline = _time.monotonic() + 5.0
    while _time.monotonic() < deadline:
        if s.engine.metrics.background_busy_s > 0:
            break
        _time.sleep(0.01)
    s.engine.stop_background()
    assert s.engine.metrics.background_busy_s > 0


# --------------------------------------------------------------------------- #
# a TPC-H-shaped star through the session: line items joined to orders and  #
# customers (Q3's shape) and to parts (Q14's, over every line)               #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def star_catalog():
    from repro.frame import Catalog, ColSpec, TableSpec

    cat = Catalog()
    cat.register(TableSpec("lineitem", 40_000, (
        ColSpec("l_orderkey", "int", low=0, high=10_000),
        ColSpec("l_partkey", "int", low=0, high=2_000),
        ColSpec("l_quantity", "int", low=1, high=51),
        ColSpec("l_extendedprice", "float", low=900.0, high=105_000.0),
        ColSpec("l_discount", "float", low=0.0, high=0.1),
        ColSpec("l_shipdate", "int", low=0, high=2526),
    ), seed=11))
    cat.register(TableSpec("orders", 10_000, (
        ColSpec("o_orderkey", "key"),
        ColSpec("o_custkey", "int", low=0, high=3_000),
        ColSpec("o_orderdate", "int", low=0, high=2406),
        ColSpec("o_totalprice", "float", low=857.71, high=555_285.16),
    ), seed=12))
    cat.register(TableSpec("customer", 3_000, (
        ColSpec("c_custkey", "key"),
        ColSpec("c_mktsegment", "cat", n_categories=5),
        ColSpec("c_acctbal", "float", low=-999.99, high=9_999.99),
    ), seed=13))
    cat.register(TableSpec("part", 2_000, (
        ColSpec("p_partkey", "key"),
        ColSpec("p_brand", "cat", n_categories=25),
        ColSpec("p_size", "int", low=1, high=51),
    ), seed=14))
    return cat


def _star_program(catalog, backend):
    """Q3's shape (a ship-date window joined to orders, an order-date filter,
    joined to customers, revenue by segment) and Q14's over every line
    (joined to parts, by brand): the joined frames and their groupbys."""
    s = Session(catalog=catalog, mode="sim", kernel_backend=backend)
    li = s.read_table("lineitem")
    q3 = li[li["l_shipdate"].between(1000, 1400)]
    q3["rev"] = q3["l_extendedprice"] * 1.05
    q3["o_orderkey"] = q3["l_orderkey"] * 1
    q3 = q3.join(s.read_table("orders"), on="o_orderkey")
    q3 = q3[q3["o_orderdate"] < 1200]
    q3["c_custkey"] = q3["o_custkey"] * 1
    q3 = q3.join(s.read_table("customer"), on="c_custkey")
    q14 = s.read_table("lineitem")
    q14["promo"] = q14["l_extendedprice"] * 0.97
    q14["p_partkey"] = q14["l_partkey"] * 1
    q14 = q14.join(s.read_table("part"), on="p_partkey")
    return {
        "q3_rows": s.show(q3).to_pydict(),
        "q3": s.show(q3.groupby("c_mktsegment").agg(
            {"rev": "sum", "l_discount": "mean"})).to_pydict(),
        "q14_all_rows": s.show(q14).to_pydict(),
        "q14_all": s.show(q14.groupby("p_brand").agg(
            {"promo": "sum", "l_quantity": "mean"})).to_pydict(),
    }


def test_star_session_parity(star_catalog):
    """The star's joins on the pallas kernels (interpret mode) select and
    assemble exactly the rows numpy does, every joined value bit for bit;
    the groupbys over them agree to the kernels' float32 sums."""
    ref = _star_program(star_catalog, "numpy")
    BK.reset_served_counts()
    got = _star_program(star_catalog, "interpret")
    assert BK.served_counts().get(("join", "interpret"), 0) >= 3
    assert len(ref["q14_all_rows"]["p_brand"]) == 40_000  # every key hits
    for q in ("q3_rows", "q14_all_rows"):
        assert list(got[q]) == list(ref[q])
        for col in ref[q]:
            np.testing.assert_array_equal(got[q][col], ref[q][col], err_msg=f"{q}/{col}")
    for q in ("q3", "q14_all"):
        assert list(got[q]) == list(ref[q])
        for col in ref[q]:
            r, g = np.asarray(ref[q][col]), np.asarray(got[q][col])
            if r.dtype.kind in "OU":
                np.testing.assert_array_equal(g, r, err_msg=f"{q}/{col}")
            else:
                np.testing.assert_allclose(g, r, rtol=1e-5, err_msg=f"{q}/{col}")
