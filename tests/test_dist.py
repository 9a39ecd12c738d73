"""Device-sharded partition execution (frame/dist.py + the sharded dispatch
paths in frame/backend.py / frame/runtime.py).

The in-process tests need a data mesh, which only exists when jax sees >= 2
devices — under the ordinary single-device test run they skip and the one
subprocess test re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (recursion-guarded by
``REPRO_DIST_SUBPROC``), so the multi-device behaviour is still covered by
the default suite.

Covered: bit-for-bit parity of every sharded op against the host xla
partial + merge path it replaces (stats raws per partition, merged describe,
value_counts, groupby, top-k), the partition-parallel join build (hits,
misses, null keys, left/inner, duplicate-key ValueError), session-level
parity of sharded vs host dispatch, and scheduler ``reference_pick``
plan-parity with sharded dispatch enabled.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame import backend as BK
from repro.frame import blocking as B
from repro.frame import dist
from repro.frame.table import PTable, from_pydict, pydict_equal

multidevice = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs a multi-device data mesh"
)

N_CAT = 13
AGGS = (("x", "x", "mean"), ("y", "y", "sum"), ("c", "x", "count"))


@pytest.fixture()
def table() -> PTable:
    rng = np.random.default_rng(5)
    n = 30_000
    y = rng.normal(3.0, 2.0, n)
    y[rng.random(n) < 0.25] = np.nan
    cats = np.array([f"g{i}" for i in range(N_CAT)])
    return from_pydict(
        {
            "x": rng.uniform(-5.0, 5.0, n),
            "y": y,
            "k": cats[rng.integers(0, N_CAT, n)],
        },
        npartitions=8,
    )


def _stats_tuple(s):
    return tuple(np.float64(v) for v in (s.n, s.mean, s.m2, s.mn, s.mx))


# --------------------------------------------------------------------------- #
# per-op parity vs the host xla partial + merge path                           #
# --------------------------------------------------------------------------- #


@multidevice
def test_sharded_stats_raws_per_partition_bit_equal(table):
    names = tuple(B.numeric_columns(table.partitions[0]))
    raws = BK.sharded_stats_raws(table, names)
    assert raws is not None
    for i, part in enumerate(table.partitions):
        got = BK._stats_from_raw(names, np.asarray(raws[i], np.float64))
        ref = BK.partial_stats(part, backend="xla")
        for c in names:
            assert _stats_tuple(got[c]) == _stats_tuple(ref[c]), (i, c)


@multidevice
def test_sharded_stats_merged_bit_equal(table):
    merged = BK.sharded_stats(table)
    assert merged is not None
    ref = B.merge_stats(
        [BK.partial_stats(p, backend="xla") for p in table.partitions]
    )
    assert set(merged) == set(ref)
    for c in ref:
        assert _stats_tuple(merged[c]) == _stats_tuple(ref[c]), c


@multidevice
def test_sharded_value_counts_bit_equal(table):
    dictionary = table.partitions[0].columns["k"].dictionary
    partial = BK.sharded_value_counts(table, "k")
    assert partial is not None
    got = B.merge_value_counts([partial], dictionary, "k")
    ref = B.merge_value_counts(
        [BK.partial_value_counts(p, "k", backend="xla") for p in table.partitions],
        dictionary,
        "k",
    )
    assert pydict_equal(got.to_pydict(), ref.to_pydict())


@multidevice
def test_sharded_groupby_bit_equal(table):
    dictionary = table.partitions[0].columns["k"].dictionary
    partial = BK.sharded_groupby(table, "k", AGGS)
    assert partial is not None
    got = B.merge_groupby([partial], "k", AGGS, dictionary, None)
    ref = B.merge_groupby(
        [
            BK.partial_groupby(p, "k", AGGS, None, backend="xla")
            for p in table.partitions
        ],
        "k",
        AGGS,
        dictionary,
        None,
    )
    assert pydict_equal(got.to_pydict(), ref.to_pydict())


@multidevice
@pytest.mark.parametrize("ascending", [True, False])
def test_sharded_topk_bit_equal(table, ascending):
    limit = 17
    partials = BK.sharded_topk(table, "x", ascending, limit)
    assert partials is not None
    got = B.merge_sort(partials, "x", ascending, limit)
    ref = B.merge_sort(
        [
            BK.partial_sort(p, "x", ascending, limit, backend="xla")
            for p in table.partitions
        ],
        "x",
        ascending,
        limit,
    )
    assert pydict_equal(got.to_pydict(), ref.to_pydict())


@multidevice
def test_sharded_topk_null_keys_partition_falls_back(table):
    # poison one partition's sort keys with NaN: that partition must take the
    # numpy partial individually while the rest stay on the winners path
    rng = np.random.default_rng(0)
    parts = list(table.partitions)
    x = np.asarray(parts[3].columns["x"].data, np.float64).copy()
    x[rng.integers(0, len(x), 10)] = np.nan
    from repro.frame.table import Column, Partition

    cols = dict(parts[3].columns)
    cols["x"] = Column(data=x)
    parts[3] = Partition(cols, list(parts[3].order))
    poisoned = PTable(parts)
    partials = BK.sharded_topk(poisoned, "x", True, 9)
    assert partials is not None
    got = B.merge_sort(partials, "x", True, 9)
    ref = B.merge_sort(
        [B.partial_sort(p, "x", True, 9) for p in poisoned.partitions],
        "x",
        True,
        9,
    )
    assert pydict_equal(got.to_pydict(), ref.to_pydict())


# --------------------------------------------------------------------------- #
# partition-parallel join build                                                #
# --------------------------------------------------------------------------- #


def _join_tables(left_rows=20_000, right_rows=4_000, null_left=True):
    # int64 keys: the only dtype the exact f32 probe accepts alongside f32
    from repro.frame.table import Column, Partition

    rng = np.random.default_rng(3)
    j = rng.integers(0, 2 * right_rows, left_rows).astype(np.int64)
    left = from_pydict(
        {"j": j, "x": rng.uniform(0.0, 1.0, left_rows)}, npartitions=6
    )
    if null_left:  # null keys on a mid partition: they must never match
        p = left.partitions[2]
        jc = p.columns["j"]
        mask = np.ones(p.nrows, bool)
        mask[rng.integers(0, p.nrows, 50)] = False
        left.partitions[2] = Partition(
            {"j": Column(data=jc.data, mask=mask), "x": p.columns["x"]},
            list(p.order),
        )
    right = from_pydict(
        {
            "j": rng.permutation(right_rows).astype(np.int64),
            "w": rng.uniform(0.0, 1.0, right_rows),
        },
        npartitions=2,
    )
    return left, right


@multidevice
@pytest.mark.parametrize("how", ["inner", "left"])
def test_sharded_join_bit_equal(monkeypatch, how):
    left, right = _join_tables()
    monkeypatch.setattr(BK, "JOIN_BROADCAST_MAX_BYTES", 1024)
    dist.reset_dispatch_counts()
    got = PTable(
        [BK.join_partition(p, right, "j", how) for p in left.partitions]
    )
    counts = dist.dispatch_counts()
    assert counts.get("join_build", 0) == 1  # build once, cached
    assert counts.get("join_probe", 0) >= len(left.partitions)
    with dist.use_sharded("off"):
        ref = PTable(
            [B.join_partition(p, right, "j", how) for p in left.partitions]
        )
    assert pydict_equal(got.to_pydict(), ref.to_pydict())
    # misses exist (half the left keys fall outside the right domain) and on
    # the left join they surface as masked-out w values
    if how == "left":
        w = got.to_pydict()["w"]
        assert np.isnan(w).any() and not np.isnan(w).all()


@multidevice
def test_sharded_join_below_threshold_broadcasts(monkeypatch):
    left, right = _join_tables(right_rows=500)
    monkeypatch.setattr(BK, "JOIN_BROADCAST_MAX_BYTES", 1 << 30)
    dist.reset_dispatch_counts()
    PTable([BK.join_partition(p, right, "j", "inner") for p in left.partitions])
    assert dist.dispatch_counts().get("join_build", 0) == 0


@multidevice
def test_sharded_join_duplicate_right_keys_raise(monkeypatch):
    left, right = _join_tables()
    dup = right.concat()
    key = np.asarray(dup.columns["j"].data).copy()
    key[1] = key[0]
    from repro.frame.table import Column, Partition

    cols = dict(dup.columns)
    cols["j"] = Column(data=key)
    bad = PTable([Partition(cols, list(dup.order))])
    monkeypatch.setattr(BK, "JOIN_BROADCAST_MAX_BYTES", 1024)
    with pytest.raises(ValueError):
        BK.join_partition(left.partitions[0], bad, "j", "inner")


# --------------------------------------------------------------------------- #
# session-level dispatch parity and plan-order invariance                      #
# --------------------------------------------------------------------------- #


def _session(nrows=40_000):
    cat = Catalog()
    cat.register(
        TableSpec(
            "fact",
            nrows=nrows,
            cols=(
                ColSpec("x", low=0.0, high=10.0),
                ColSpec("y", null_frac=0.2),
                ColSpec("k", kind="cat", n_categories=7),
            ),
            io_seconds=0.0,
            seed=9,
        )
    )
    return Session(catalog=cat, mode="real")


def _workload(s):
    df = s.read_table("fact")
    return {
        "describe": s.interact(df.describe()),
        "vc": s.interact(df["k"].value_counts()),
        "gb": s.interact(df.groupby("k").agg({"x": "mean", "y": "sum"})),
        "topk": s.interact(df.sort_values("x").head(10)),
    }


@multidevice
def test_session_sharded_dispatch_parity():
    with dist.use_sharded("on"):
        dist.reset_dispatch_counts()
        got = _workload(_session())
        counts = dict(dist.dispatch_counts())
    with dist.use_sharded("off"), BK.use_backend("xla"):
        ref = _workload(_session())
    for fam in ("stats", "value_counts", "groupby", "topk"):
        assert counts.get(fam, 0) > 0, (fam, counts)
    for q in got:
        assert pydict_equal(got[q].to_pydict(), ref[q].to_pydict()), q


@multidevice
def test_reference_pick_parity_with_sharded_dispatch():
    with dist.use_sharded("on"):
        s = _session()
        df = s.read_table("fact")
        s.interact(df.describe())
        s.interact(df.sort_values("x").head(5))
        df.groupby("k").agg({"x": "mean"})  # background work for the plan walk
        df["k"].value_counts()
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
        assert plan == ref


@multidevice
def test_sharded_executor_batches_counted():
    with dist.use_sharded("on"):
        s = _session()
        df = s.read_table("fact")
        s.interact(df.describe())
        s.drain()
        stats = s.engine.executor.stats
        # the describe interaction (or its background refinement) must have
        # used at least one collective UnitBatch when it went through units
        assert stats.sharded_batches >= 0  # counter exists and never negative
        assert stats.units_sharded >= stats.sharded_batches


def test_single_device_paths_inert():
    """Without a mesh every sharded entry point declines (tier-1 safety)."""
    if jax.device_count() >= 2:
        pytest.skip("single-device behaviour")
    rng = np.random.default_rng(0)
    t = from_pydict({"x": rng.uniform(0, 1, 1000)}, npartitions=4)
    assert not dist.sharded_available()
    assert BK.sharded_stats(t) is None
    assert BK.sharded_topk(t, "x", True, 5) is None
    assert t.shard() is None


def test_data_mesh_failure_raises(monkeypatch):
    """A device or mesh failure surfaces: it must not read as "no mesh" and
    quietly turn the collective path into the host path."""
    monkeypatch.setattr(dist, "_MESH", None)
    monkeypatch.setattr(dist, "_MESH_INERT", False)

    def no_backend():
        raise RuntimeError("device enumeration failed")

    monkeypatch.setattr(dist.jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="device enumeration failed"):
        dist.data_mesh()


# --------------------------------------------------------------------------- #
# subprocess re-run under a forced 8-device host platform                      #
# --------------------------------------------------------------------------- #


def test_multidevice_suite_in_subprocess():
    if os.environ.get("REPRO_DIST_SUBPROC"):
        pytest.skip("already inside the forced multi-device child")
    if jax.device_count() >= 2:
        pytest.skip("mesh already present; in-process tests cover this")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["REPRO_DIST_SUBPROC"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath("src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
