"""The program's spans (``repro.obs``): nesting, request ids, threads, the
ring's bound, self time; spans in a profiler trace; the device scope of a
kernel; and the spans of a real interaction under its request root."""
import collections
import glob
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
    with ops.local_backend("interpret"):
        lowered = jax.jit(ops.filter_compact_padded).lower(
            jnp.ones(1000, jnp.float32), jnp.ones(1000, bool))
    text = lowered.compile().as_text()
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters
    assert all(re.search(r'op_name="[^"]*/filter_compact/scatter"', line)
               for line in scatters)


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
