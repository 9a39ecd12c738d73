"""The program's spans (``repro.obs``): nesting, request ids, threads, the
ring's bound, self time; spans in a profiler trace; the device scope of a
kernel; and the spans of a real interaction under its request root."""
import collections
import glob
import importlib
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.frame import Catalog, ColSpec, Session, TableSpec
from repro.kernels import ops


def spans_since(sid0):
    return [r for r in obs.records() if r.sid > sid0]


def last_sid():
    return max((r.sid for r in obs.records()), default=0)


def test_nesting_parent_and_request_inheritance():
    sid0 = last_sid()
    with obs.span("engine.display", rid=7, node=3) as root:
        with obs.span("exec.node") as mid:
            with obs.span("dispatch.call", rows=10):
                pass
        with obs.span("worker.fetch", rid=("node", 5)):
            pass
    recs = {r.name: r for r in spans_since(sid0)}
    assert recs["engine.display"].parent == 0
    assert recs["exec.node"].parent == root.sid
    assert recs["dispatch.call"].parent == mid.sid
    assert recs["dispatch.call"].rid == 7 and recs["exec.node"].rid == 7
    assert recs["worker.fetch"].rid == ("node", 5)  # an explicit rid wins
    assert recs["engine.display"].attrs == {"node": 3}
    assert recs["dispatch.call"].attrs["rows"] == 10
    # children exit first: the ring holds them before their parents
    order = [r.name for r in spans_since(sid0)]
    assert order.index("dispatch.call") < order.index("exec.node") < order.index(
        "engine.display")
    assert obs._stack() == []


def test_span_records_on_exception_and_attrs_added_inside():
    sid0 = last_sid()
    with pytest.raises(ValueError):
        with obs.span("dispatch.readback") as s:
            s.attrs["bytes"] = 64
            raise ValueError("boom")
    (rec,) = spans_since(sid0)
    assert rec.attrs == {"bytes": 64} and rec.ns == s.ns >= 0


def test_each_thread_has_its_own_stack():
    sid0 = last_sid()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with obs.span("worker.execute", rid=("node", 1)):
            inside.set()
            release.wait(timeout=10)
            with obs.span("exec.node"):
                pass

    t = threading.Thread(target=worker)
    with obs.span("engine.display", rid=99):
        t.start()
        assert inside.wait(timeout=10)
        with obs.span("exec.node"):
            pass
        release.set()
        t.join(timeout=10)
    assert not t.is_alive()
    recs = spans_since(sid0)
    by_thread = collections.defaultdict(list)
    for r in recs:
        by_thread[r.thread].append(r)
    assert len(by_thread) == 2
    for rs in by_thread.values():
        sids = {r.sid for r in rs}
        (root,) = [r for r in rs if r.parent == 0]
        assert all(r.parent in sids for r in rs if r is not root)
        assert {r.rid for r in rs} == {root.rid}


def test_ring_is_bounded(monkeypatch):
    assert obs._RING.maxlen == obs.RING_SPANS
    monkeypatch.setattr(obs, "_RING", collections.deque(maxlen=4))
    for _ in range(10):
        with obs.span("dispatch.prep"):
            pass
    recs = obs.records()
    assert len(recs) == 4
    assert [r.sid for r in recs] == sorted(r.sid for r in recs)


def test_self_time_is_duration_less_children():
    sid0 = last_sid()
    with obs.span("exec.node"):
        with obs.span("exec.unit"):
            pass
        with obs.span("exec.unit"):
            with obs.span("dispatch.wait"):
                pass
    recs = spans_since(sid0)
    by_sid = {r.sid: r for r in recs}
    for r in recs:
        children = [c for c in recs if c.parent == r.sid]
        assert r.self_ns == r.ns - sum(c.ns for c in children)
        assert r.self_ns >= 0
    total_self = sum(r.self_ns for r in recs)
    (root,) = [r for r in recs if r.parent not in by_sid]
    assert total_self == root.ns


def small_session(backend="xla"):
    cat = Catalog()
    cat.register(TableSpec("events", nrows=20_000, seed=3, cols=(
        ColSpec("x", low=0.0, high=100.0),
        ColSpec("y", null_frac=0.2),
        ColSpec("k", kind="cat", n_categories=5),
    )))
    return Session(catalog=cat, mode="real", kernel_backend=backend, planner=False)


def test_profiler_trace_holds_program_spans_inside_the_caller(tmp_path):
    from jax.profiler import ProfileData

    s = small_session()
    df = s.read_table("events")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("caller"):
        s.show(df.describe())
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events]
    (caller,) = [e for e in events if e.name == "caller"]
    ours = [e for e in events if e.name in obs.NAMES]
    assert {"engine.display", "exec.node", "dispatch.call"} <= {e.name for e in ours}
    for e in ours:
        assert caller.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= caller.start_ns + caller.duration_ns


def test_filter_compact_scope_reaches_its_scatter():
    # the row stitch, once an XLA scatter (and a sort) in the wrapper, is
    # inside the kernel: none is left, and every op of the filter's jitted
    # body carries its device scope
    with ops.local_backend("interpret"):
        lowered = jax.jit(ops.filter_compact_padded).lower(
            jnp.ones(1000, jnp.float32), jnp.ones(1000, bool))
    lines = lowered.compile().as_text().splitlines()
    assert not [line for line in lines if re.search(r" (scatter|sort)\(", line)]
    ours = [line for line in lines if 'op_name="jit(filter_compact_padded)/jit(filter_compact)/' in line]
    assert ours
    assert all("/jit(filter_compact)/filter_compact/" in line for line in ours)


def test_interaction_spans_sit_under_their_request_root():
    s = small_session("xla")
    df = s.read_table("events")
    s.show(df.describe())  # compile outside the measured request
    filt = df[df["x"] > 50.0]
    sid0 = last_sid()
    s.show(filt.describe())
    recs = spans_since(sid0)
    roots = [r for r in recs if r.name == "engine.display"]
    assert len(roots) == 1
    root = roots[0]
    mine = [r for r in recs if r.rid == root.rid]
    names = {r.name for r in mine}
    assert {"engine.lock_wait", "exec.node", "exec.unit", "exec.combine",
            "dispatch.partial", "dispatch.prep", "dispatch.upload", "dispatch.call",
            "dispatch.wait", "dispatch.readback"} <= names
    by_sid = {r.sid: r for r in mine}
    for r in mine:
        if r is not root:
            assert r.parent in by_sid  # every span descends from the root
    assert sum(r.self_ns for r in mine) == root.ns
    uploads = [r for r in mine if r.name == "dispatch.upload"]
    assert all(r.attrs["bytes"] > 0 for r in uploads)
    calls = [r for r in mine if r.name == "dispatch.call"]
    assert {r.attrs["family"] for r in calls} >= {"filter", "stats"}
    assert all(r.attrs["bucket"] >= r.attrs["rows"] for r in calls)


def test_compile_clock_splits_phases():
    clock = obs.CompileClock()
    try:
        before = clock.split()
        jax.jit(lambda x: x * 3 + 1)(np.arange(17.0)).block_until_ready()
        got = obs.split_since(clock, before)
    finally:
        clock.close()
    assert got["trace_n"] >= 1 and got["lower_n"] >= 1 and got["compile_n"] >= 1
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["compile_s"] > 0
    assert clock.seconds >= got["compile_s"]


def test_join_spans_build_once_and_assemble_per_partition():
    """A join on the kernel path opens ``join.build`` once per right table
    and engine (the sorted keys stay on the device for every later probe),
    ``join.assemble`` once per joined partition, and its ``dispatch.call``
    carries the right side's size for the probe's roofline."""
    cat = Catalog()
    cat.register(TableSpec("facts", 6_000, (
        ColSpec("k", "int", low=0, high=500), ColSpec("x", "float")), seed=3))
    cat.register(TableSpec("dim", 400, (ColSpec("k", "key"), ColSpec("w", "float")), seed=4))
    s = Session(catalog=cat, mode="sim", kernel_backend="interpret")
    facts, dim = s.read_table("facts"), s.read_table("dim")
    nparts = len(s.engine.value_of(facts.node).partitions)
    assert nparts > 1
    sid0 = last_sid()
    s.show(facts.join(dim, on="k").describe())
    s.show(facts[facts["x"] > 0.5].join(dim, on="k").describe())
    recs = spans_since(sid0)
    builds = [r for r in recs if r.name == "join.build"]
    assert len(builds) == 1
    assert builds[0].attrs["right_rows"] == 400 and builds[0].attrs["bytes"] == 1600
    assemblies = [r for r in recs if r.name == "join.assemble"]
    assert len(assemblies) == 2 * nparts
    assert all(r.attrs["cols"] == 1 and r.attrs["chunks"] == 1 for r in assemblies)
    calls = [r for r in recs if r.name == "dispatch.call" and r.attrs["family"] == "join"]
    assert len(calls) == 2 * nparts
    assert all(r.attrs["right_rows"] == 400 for r in calls)
    # another engine over the same catalog builds its own
    t = Session(catalog=cat, mode="sim", kernel_backend="interpret")
    sid1 = last_sid()
    t.show(t.read_table("facts").join(t.read_table("dim"), on="k").describe())
    assert sum(r.name == "join.build" for r in spans_since(sid1)) == 1


def test_join_assemble_counts_its_chunks_and_opens_no_span_on_the_pool(monkeypatch):
    """From ``2 · ORDER_CHUNK`` rows a joined partition is assembled in
    ``band_order``'s chunks on its pool: ``join.assemble`` still opens once
    per joined partition, on the interacting thread, and counts the
    chunks; the pool's threads open no span."""
    JP = importlib.import_module("repro.kernels.join_probe")  # the package exports the function

    monkeypatch.setattr(JP, "ORDER_CHUNK", 1024)
    cat = Catalog()
    cat.register(TableSpec("facts", 12_000, (
        ColSpec("k", "int", low=0, high=500), ColSpec("x", "float")), seed=3))
    cat.register(TableSpec("dim", 400, (ColSpec("k", "key"), ColSpec("w", "float")), seed=4))
    s = Session(catalog=cat, mode="sim", kernel_backend="interpret")
    facts, dim = s.read_table("facts"), s.read_table("dim")
    lengths = [p.nrows for p in s.engine.value_of(facts.node).partitions]
    sid0 = last_sid()
    s.show(facts.join(dim, on="k").describe())
    recs = spans_since(sid0)
    assemblies = [r for r in recs if r.name == "join.assemble"]
    assert sorted(r.attrs["rows"] for r in assemblies) == sorted(lengths)
    assert all(r.attrs["chunks"] == len(JP.order_cuts(r.attrs["rows"])) - 1
               for r in assemblies)
    assert max(r.attrs["chunks"] for r in assemblies) > 1
    assert min(r.attrs["chunks"] for r in assemblies) == 1
    pool = {t.ident for t in JP._order_pool()._threads}
    assert pool and not any(r.thread in pool for r in recs)


def test_declared_dimension_builds_its_join_index_at_read():
    """An engine told the star's dimensions builds a dimension's join index
    (``join.build``) when it reads the table; its joins then build nothing
    and answer as an engine that builds in the first join."""
    cat = Catalog()
    cat.register(TableSpec("facts", 6_000, (
        ColSpec("k", "int", low=0, high=500), ColSpec("x", "float")), seed=3))
    cat.register(TableSpec("dim", 400, (ColSpec("k", "key"), ColSpec("w", "float")), seed=4))
    s = Session(catalog=cat, mode="sim", kernel_backend="interpret",
                join_dimensions=[("dim", "k")])
    sid0 = last_sid()
    s.engine.value_of(s.read_table("dim").node)
    builds = [r for r in spans_since(sid0) if r.name == "join.build"]
    assert len(builds) == 1 and builds[0].attrs["right_rows"] == 400
    sid1 = last_sid()
    got = s.show(s.read_table("facts").join(s.read_table("dim"), on="k")).to_pydict()
    recs = spans_since(sid1)
    assert not any(r.name == "join.build" for r in recs)
    assert any(r.name == "join.assemble" for r in recs)
    t = Session(catalog=cat, mode="sim", kernel_backend="interpret")
    want = t.show(t.read_table("facts").join(t.read_table("dim"), on="k")).to_pydict()
    assert list(got) == list(want)
    for col in want:
        np.testing.assert_array_equal(got[col], want[col])
