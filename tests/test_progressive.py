"""Progressive interaction path: bounded estimates, sample-first ordering,
Chan variance merging, and scheduler memo persistence.

Pins the tentpole contract end to end:

* a blocking interaction with ``progressive=True`` returns immediately with a
  bounded estimate (coverage < 1) and upgrades in place;
* coverage is monotone over refinement and the completed result is
  bit-for-bit equal to the non-progressive path (property-tested under
  hypothesis when available);
* confidence intervals contain the exact value at >= the nominal rate over
  seeded trials, and stay accurate on shifted data (mean >> std) thanks to
  the Chan pairwise variance merge in kernels and ``merge_stats``;
* sample-first ordering is a permutation that spreads any prefix across the
  partition range, and the exact path (``reference_pick`` parity) is
  untouched;
* scheduler descendant/delivery memos persist across sessions and are
  invalidated wholesale on DAG-fingerprint mismatch.
"""
import math
import os

import numpy as np
import pytest

from repro.core.scheduler import sample_first_order
from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.frame import blocking as B
from repro.frame.partitioner import uniform_partitions


def _catalog(seed: int = 7, nrows: int = 40_000) -> Catalog:
    cat = Catalog()
    cat.register(
        TableSpec(
            "fact",
            nrows=nrows,
            cols=(
                ColSpec("x", low=0.0, high=10.0),
                ColSpec("y", null_frac=0.2),
                ColSpec("k", kind="cat", n_categories=8),
            ),
            io_seconds=2.0,
            seed=seed,
        )
    )
    return cat


def _tables_equal(a, b) -> bool:
    """Bit-for-bit equality of two PTables (NaN == NaN)."""
    da, db = a.to_pydict(), b.to_pydict()
    if set(da) != set(db):
        return False
    for c in da:
        xa, xb = np.asarray(da[c]), np.asarray(db[c])
        if xa.shape != xb.shape:
            return False
        if xa.dtype.kind in "OU":
            if not (xa == xb).all():
                return False
        elif not np.array_equal(xa, xb, equal_nan=True):
            return False
    return True


def _frame(session, nparts=None):
    df = session.read_table("fact")
    if nparts is not None:
        spec = session.catalog.spec("fact")
        df.node.kwargs["partition_bounds"] = uniform_partitions(spec.nrows, nparts)
    return df


# --------------------------------------------------------------------------- #
# sample-first ordering                                                        #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "missing,total",
    [
        (list(range(16)), 16),
        (list(range(128)), 128),
        ([3, 7, 11, 100], 128),
        (list(range(5)), 7),  # non-power-of-two
        ([0], 1),
        ([], 16),
    ],
)
def test_sample_first_order_is_permutation(missing, total):
    order = sample_first_order(list(missing), total)
    assert sorted(order) == sorted(missing)


def test_sample_first_order_spreads_prefix():
    total = 128
    order = sample_first_order(list(range(total)), total)
    # bit-reversal: the first 8 picks are the 8 strided anchors 0,16,..,112
    assert set(order[:8]) == set(range(0, total, total // 8))
    # any prefix of length k leaves no gap wider than ~2 * total / k
    for k in (4, 8, 16, 32):
        chosen = sorted(order[:k])
        gaps = np.diff(chosen + [chosen[0] + total])
        assert gaps.max() <= 2 * total // k


def test_sample_first_order_exact_path_untouched():
    """Without a registered progress listener the executor keeps natural
    order (`unit_order` only applies to progressive nodes), so background /
    exact execution and reference_pick parity are unaffected."""
    s = Session(catalog=_catalog(), mode="sim")
    df = _frame(s, nparts=8)
    out = s.show(df.describe())
    # oracle parity on a follow-up background pick loop
    eng = s.engine
    df.groupby("k").mean()  # leave a non-critical node for background
    done = eng.cache.executed_ids()
    got = eng.scheduler.pick(done, now=eng.clock.now())
    ref = eng.scheduler.reference_pick(done, now=eng.clock.now())
    assert (got is None) == (ref is None)
    if got is not None:
        assert got.nid == ref.nid
    assert out is not None


# --------------------------------------------------------------------------- #
# Chan variance merge on shifted data (satellite 1)                            #
# --------------------------------------------------------------------------- #


def test_kernel_variance_shifted_data():
    """mean >> std in float32: the old sum-of-squares kernel contract lost
    all variance precision (std off by ~100x); the centered-m2 Chan contract
    keeps it to ~1%."""
    from repro.kernels import ops as K

    rng = np.random.default_rng(0)
    x = (rng.standard_normal(50_000) + 1e6).astype(np.float32)
    m = np.ones_like(x, dtype=bool)
    rows = np.asarray(K.masked_stats_batch(x[None, :], m[None, :]), np.float64)
    cnt, s, m2, mn, mx = rows[0]
    assert cnt == x.size
    std = math.sqrt(m2 / (cnt - 1))
    true_std = float(np.std(x.astype(np.float64), ddof=1))
    assert abs(std - true_std) / true_std < 0.02
    assert abs(s / cnt - 1e6) < 1.0


def test_merge_stats_pairwise_shifted_data():
    rng = np.random.default_rng(1)
    parts = []
    chunks = []
    for i in range(256):
        c = rng.standard_normal(500) + 1e8
        chunks.append(c)
        n = float(c.size)
        mean = float(c.mean())
        parts.append(
            {
                "x": B.ColStats(
                    n, mean, float(((c - mean) ** 2).sum()),
                    float(c.min()), float(c.max()),
                )
            }
        )
    merged = B.merge_stats(parts)["x"]
    allx = np.concatenate(chunks)
    assert abs(merged.std - allx.std(ddof=1)) / allx.std(ddof=1) < 1e-6
    assert merged.n == allx.size


# --------------------------------------------------------------------------- #
# progressive estimates: immediacy, convergence, exactness                     #
# --------------------------------------------------------------------------- #


def test_progressive_describe_first_estimate_is_partial():
    s = Session(catalog=_catalog(), mode="sim")
    df = _frame(s, nparts=16)
    pr = s.interact(df.describe(), progressive=True)
    est = pr.estimate()
    assert 0.0 < est.coverage < 1.0
    assert not est.exact
    assert est.value is not None and "x" in est.intervals
    rec = s.engine.metrics.interactions[-1]
    assert rec.progressive and rec.partial


def test_progressive_converges_to_exact_bitforbit():
    cat = _catalog()
    s = Session(catalog=cat, mode="sim")
    pr = s.interact(_frame(s, nparts=16).describe(), progressive=True)
    covs = []
    final = None
    for est in pr:
        covs.append(est.coverage)
        if est.exact:
            final = est.value
            break
    assert all(b >= a for a, b in zip(covs, covs[1:]))
    assert covs[-1] == 1.0
    s2 = Session(catalog=cat, mode="sim")
    exact = s2.show(_frame(s2, nparts=16).describe())
    assert _tables_equal(final, exact)


@pytest.mark.parametrize("q", ["value_counts", "groupby_mean", "groupby_sum", "mean"])
def test_progressive_upgrade_bitforbit_all_ops(q):
    cat = _catalog()

    def build(sess):
        df = _frame(sess, nparts=16)
        if q == "value_counts":
            return df["k"].value_counts()
        if q == "groupby_mean":
            return df.groupby("k").mean()
        if q == "groupby_sum":
            return df.groupby("k").sum()
        return df.mean()

    s = Session(catalog=cat, mode="sim")
    pr = s.interact(build(s), progressive=True)
    assert pr.estimate().coverage < 1.0
    got = pr.upgrade()
    s2 = Session(catalog=cat, mode="sim")
    exact = s2.show(build(s2))
    assert _tables_equal(got, exact)


def test_progressive_value_counts_estimate_scales():
    """Counts estimated from k of m partitions scale by m/k: the estimated
    total stays within 20% of the true row count at 25% coverage."""
    s = Session(catalog=_catalog(), mode="sim")
    df = _frame(s, nparts=16)
    pr = s.interact(df["k"].value_counts(), progressive=True)
    pr.refine(3)  # 4 of 16 partitions
    est = pr.estimate()
    assert not est.exact
    total_est = int(np.asarray(est.value.to_pydict()["count"]).sum())
    nrows = s.catalog.spec("fact").nrows
    assert abs(total_est - nrows) / nrows < 0.2
    assert len(est.intervals) > 0


def test_progressive_interval_containment_rate():
    """Over seeded trials, the 95% interval on a column mean at partial
    coverage contains the exact mean at >= the nominal rate (cluster-sampled
    CLT with finite-population correction is conservative here)."""
    hits = 0
    trials = 40
    for seed in range(trials):
        cat = _catalog(seed=seed, nrows=8_000)
        s = Session(catalog=cat, mode="sim")
        df = _frame(s, nparts=16)
        pr = s.interact(df.mean(), progressive=True)
        pr.refine(3)  # 4 of 16 partitions
        est = pr.estimate()
        lo, hi = est.intervals["x"]
        exact = float(np.asarray(pr.upgrade().to_pydict()["x"])[0])
        if lo <= exact <= hi:
            hits += 1
    assert hits / trials >= 0.95


def test_background_think_refines_progressive():
    """Think-time background execution streams completed partitions into the
    running combine; draining finishes the node and the handle turns exact."""
    cat = _catalog()
    s = Session(catalog=cat, mode="sim")
    pr = s.interact(_frame(s, nparts=16).describe(), progressive=True)
    assert pr.estimate().coverage < 1.0
    s.drain()
    est = pr.estimate()
    assert est.exact and est.coverage == 1.0
    s2 = Session(catalog=cat, mode="sim")
    assert _tables_equal(est.value, s2.show(_frame(s2, nparts=16).describe()))


def test_progressive_on_cached_node_is_exact_immediately():
    s = Session(catalog=_catalog(), mode="sim")
    df = _frame(s, nparts=16)
    exact = s.show(df.describe())
    pr = s.interact(df.describe(), progressive=True)
    est = pr.estimate()
    assert est.exact and est.coverage == 1.0
    assert _tables_equal(est.value, exact)


# --------------------------------------------------------------------------- #
# hypothesis: convergence property                                             #
# --------------------------------------------------------------------------- #


def test_progressive_convergence_property():
    hyp = pytest.importorskip(
        "hypothesis", reason="dev extra: pip install -r requirements-dev.txt"
    )
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(
        max_examples=10, deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        seed=st.integers(0, 50),
        nparts=st.sampled_from([2, 3, 8, 16]),
        step=st.integers(1, 5),
    )
    def run(seed, nparts, step):
        cat = _catalog(seed=seed, nrows=4_000)
        s = Session(catalog=cat, mode="sim")
        pr = s.interact(_frame(s, nparts=nparts).describe(), progressive=True)
        covs = [pr.estimate().coverage]
        while not pr.estimate().exact:
            pr.refine(step)
            covs.append(pr.estimate().coverage)
        assert all(b >= a for a, b in zip(covs, covs[1:]))
        s2 = Session(catalog=cat, mode="sim")
        exact = s2.show(_frame(s2, nparts=nparts).describe())
        assert _tables_equal(pr.estimate().value, exact)

    run()


# --------------------------------------------------------------------------- #
# scheduler memo persistence (satellite: carried ROADMAP item)                 #
# --------------------------------------------------------------------------- #


def _program(s):
    df = _frame(s, nparts=8)
    flt = df[df["x"] > 5.0]
    flt.describe()
    flt.groupby("k").mean()
    df["k"].value_counts()
    return s


def test_scheduler_memos_roundtrip_with_pick_parity(tmp_path):
    path = str(tmp_path / "memos.json")
    cat = _catalog()
    s1 = Session(catalog=cat, mode="sim", scheduler_memo_path=path)
    _program(s1)
    # one pick populates descendant + delivery memos; save persists them
    s1.engine.scheduler.pick(set(), now=s1.engine.clock.now())
    s1.engine.save_scheduler_memos()
    assert os.path.exists(path)

    # identical program in a fresh session: load installs the memos...
    s2 = Session(catalog=cat, mode="sim", scheduler_memo_path=path)
    _program(s2)
    assert s2.engine.load_scheduler_memos() is True

    # ...and the pick sequence stays identical to the memo-free oracle
    s3 = Session(catalog=cat, mode="sim")
    _program(s3)
    done: set = set()
    for _ in range(50):
        p2 = s2.engine.scheduler.pick(set(done), now=0.0)
        p3 = s3.engine.scheduler.pick(set(done), now=0.0)
        ref = s2.engine.scheduler.reference_pick(set(done), now=0.0)
        assert (p2 is None) == (p3 is None) == (ref is None)
        if p2 is None:
            break
        assert p2.nid == p3.nid == ref.nid
        done.add(p2.nid)


def test_scheduler_memos_rejected_on_dag_mismatch(tmp_path):
    path = str(tmp_path / "memos.json")
    cat = _catalog()
    s1 = Session(catalog=cat, mode="sim", scheduler_memo_path=path)
    _program(s1)
    s1.engine.scheduler.pick(set(), now=0.0)
    s1.engine.save_scheduler_memos()

    # a different program (one extra node) → fingerprint mismatch → rejected
    s2 = Session(catalog=cat, mode="sim", scheduler_memo_path=path)
    _program(s2)
    _frame(s2, nparts=8).dropna()
    assert s2.engine.load_scheduler_memos() is False

    # garbage file → rejected, not raised
    with open(path, "w") as f:
        f.write("{not json")
    assert s2.engine.load_scheduler_memos() is False


def test_scheduler_memos_survive_save_load_of_cost_model(tmp_path):
    """Engine-level wiring: save_cost_model also persists scheduler memos to
    the derived sidecar path."""
    cm_path = str(tmp_path / "cm.json")
    cat = _catalog()
    s1 = Session(catalog=cat, mode="sim", cost_model_path=cm_path)
    _program(s1)
    s1.engine.scheduler.pick(set(), now=0.0)
    s1.engine.save_cost_model()
    assert os.path.exists(cm_path + ".sched.json")
    s2 = Session(catalog=cat, mode="sim", cost_model_path=cm_path)
    _program(s2)
    # structure memos load even though calibration changed the cost state
    assert s2.engine.load_scheduler_memos() is True


# --------------------------------------------------------------------------- #
# serving layers: multi-tenant attribution + request(progressive=True)         #
# --------------------------------------------------------------------------- #


def test_multitenant_progressive_attribution_and_log():
    from repro.core import Engine
    from repro.serve.multitenant import (
        MultiTenantServer,
        register_synthetic_op,
        synthetic_trace_program,
    )

    eng = Engine(mode="sim", budget_bytes=1 << 20, speculation=False)
    register_synthetic_op(eng)
    srv = MultiTenantServer(eng, record_schedule=True)
    _, r1 = synthetic_trace_program(3, 0)
    prog = srv.submit("alice", [r1])
    root = prog.roots[0]

    pr = srv.interact("alice", root, progressive=True)
    assert srv.schedule_log[-1] == ["interact_progressive", "alice", root.nid, "miss"]
    # synthetic has no running combine: coverage-only channel
    est = pr.estimate()
    assert est.value is None and est.coverage < 1.0
    before = dict(eng.executor.stats.units_by_tenant)
    pr.refine(1)
    after = eng.executor.stats.units_by_tenant
    assert after.get("alice", 0) > before.get("alice", 0)
    exact = pr.upgrade()
    # non-progressive entry keeps its historical shape (now a cache hit)
    assert srv.interact("alice", root) == exact
    assert srv.schedule_log[-1] == ["interact", "alice", root.nid, "hit"]
