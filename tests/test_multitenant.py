"""Multi-tenant opportunistic serving: cross-tenant scheduling, cross-DAG
dedup, tenant-scoped quarantine, and trace-replay determinism.

Covers the multi-tenant contract end to end at the core + serve layers:

* cross-DAG CSE — two tenants' structurally identical programs intern to the
  same shared nodes, execute once, and return bit-identical results vs
  isolated per-tenant execution (property-tested under hypothesis);
* cross-tenant Eq-1 — a think window is allocated across all tenants' demand,
  weighted, and stays byte-identical to the brute-force oracle;
* (tenant, node)-scoped quarantine — one tenant's faulting window must not
  block a deduped node for everyone (regression for the shared-DAG fix);
* seeded Poisson traces replay to byte-identical schedules.
"""
import json

import pytest

from repro.core import DAG, Engine, intern_program
from repro.core.costmodel import CostModel
from repro.core.executor import OpRuntime, Unit
from repro.core.scheduler import Scheduler
from repro.data.synth import TraceSpec, poisson_trace
from repro.serve.multitenant import (
    MultiTenantServer,
    register_synthetic_op,
    synthetic_trace_program,
)


def _engine() -> Engine:
    eng = Engine(mode="sim", budget_bytes=1 << 20, speculation=False)
    register_synthetic_op(eng)
    return eng


# --------------------------------------------------------------- cross-DAG CSE --
def test_intern_program_dedups_and_maps():
    eng = _engine()
    d, root = synthetic_trace_program(3, 0)
    mapping, n_new = intern_program(eng.dag, [root])
    assert n_new == len(mapping) == len(d)
    # interning the same program again gains nothing
    d2, root2 = synthetic_trace_program(3, 0)
    mapping2, n_new2 = intern_program(eng.dag, [root2])
    assert n_new2 == 0
    assert mapping2[root2.nid].nid == mapping[root.nid].nid
    # a different param is a different program: only the shared source dedups
    d3, root3 = synthetic_trace_program(3, 1)
    mapping3, n_new3 = intern_program(eng.dag, [root3])
    assert 0 < n_new3 < len(mapping3)


def test_two_tenants_one_materialisation():
    eng = _engine()
    srv = MultiTenantServer(eng)
    _, r1 = synthetic_trace_program(2, 0)
    _, r2 = synthetic_trace_program(2, 0)
    p1 = srv.submit("alice", [r1])
    p2 = srv.submit("bob", [r2])
    assert p2.n_new == 0 and p2.n_deduped == p2.n_nodes
    assert p1.roots[0].nid == p2.roots[0].nid
    va = srv.interact("alice", p1.roots[0])
    completed = eng.executor.stats.nodes_completed
    vb = srv.interact("bob", p2.roots[0])
    # bob's identical query is served from the shared materialisation
    assert eng.executor.stats.nodes_completed == completed
    assert va == vb
    assert srv.dedup_rate() == pytest.approx(0.5)


def test_cross_tenant_cse_property():
    """Property: for any (template, param, depth), two tenants issuing the
    structurally identical program produce exactly one materialisation and
    bit-identical results vs isolated execution."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        tpl=st.integers(min_value=0, max_value=7),
        param=st.integers(min_value=0, max_value=3),
        stages=st.integers(min_value=1, max_value=4),
    )
    def prop(tpl, param, stages):
        eng = _engine()
        srv = MultiTenantServer(eng)
        _, r1 = synthetic_trace_program(tpl, param, n_stages=stages)
        _, r2 = synthetic_trace_program(tpl, param, n_stages=stages)
        p1 = srv.submit("alice", [r1])
        p2 = srv.submit("bob", [r2])
        assert p2.n_new == 0  # exactly one copy in the shared DAG
        va = srv.interact("alice", p1.roots[0])
        n = eng.executor.stats.nodes_completed
        vb = srv.interact("bob", p2.roots[0])
        assert eng.executor.stats.nodes_completed == n  # one materialisation
        # isolated oracle: the same program on a private engine
        iso = _engine()
        _, riso = synthetic_trace_program(tpl, param, n_stages=stages)
        miso, _ = intern_program(iso.dag, [riso])
        viso = iso.display(miso[riso.nid])
        assert va == vb == viso

    prop()


# -------------------------------------------------------- cross-tenant Eq-1 --
def _tenant_chain_dag():
    """Shared node S (cost 3) demanded by both tenants; X (cost 4) only by a."""
    d = DAG()
    s = d.add("synthetic", kwargs={"cost_s": 3.0, "tag": "S"})
    x = d.add("synthetic", kwargs={"cost_s": 4.0, "tag": "X"})
    return d, s, x


def test_cross_tenant_utility_weights_shared_demand():
    d, s, x = _tenant_chain_dag()
    sched = Scheduler(dag=d, cost_model=CostModel())
    # single-tenant view: X (cost 4) beats S (cost 3)
    assert sched.pick(set()).nid == x.nid
    # cross-tenant: S is demanded by two tenants → utility 3+3 > 4
    sched.set_tenant_demand("a", {s.nid, x.nid})
    sched.set_tenant_demand("b", {s.nid})
    assert sched.pick(set()).nid == s.nid
    # tenant weight tips it back: a's demand is 10x as urgent
    sched.tenant_weight["a"] = 10.0
    assert sched.pick(set()).nid == x.nid


def test_cross_tenant_pick_matches_reference_oracle():
    eng = _engine()
    srv = MultiTenantServer(eng)
    for t, (tpl, param) in (("a", (0, 0)), ("b", (0, 0)), ("c", (5, 2))):
        _, r = synthetic_trace_program(tpl, param)
        srv.submit(t, [r])
    sched = eng.scheduler
    done: set = set()
    while True:
        nxt = sched.pick(done, tenant="a")
        ref = sched.reference_pick(done, tenant="a")
        assert (nxt is None) == (ref is None)
        if nxt is None:
            break
        assert nxt.nid == ref.nid
        done.add(nxt.nid)


def test_think_window_serves_other_tenants_demand():
    """One tenant's think window executes another tenant's queue — the
    multi-tenant claim in one assertion."""
    eng = _engine()
    srv = MultiTenantServer(eng)
    _, ra = synthetic_trace_program(1, 0)
    pa = srv.submit("alice", [ra])
    _, rb = synthetic_trace_program(6, 3)
    pb = srv.submit("bob", [rb])
    srv.think("alice", 60.0)  # plenty: drains every tenant's queue
    assert pb.roots[0].nid in eng.cache  # bob's program ran in alice's window
    lat = srv.interact("bob", pb.roots[0])
    rec = eng.metrics.interactions[-1]
    assert rec.tenant == "bob" and rec.latency_s == 0.0
    # harvest attribution: alice's window paid for the units
    assert eng.executor.stats.units_by_tenant.get("alice", 0) > 0
    assert "bob" not in eng.executor.stats.units_by_tenant


# ----------------------------------------------- (tenant, node) quarantine --
def test_quarantine_scoped_to_tenant():
    d, s, x = _tenant_chain_dag()
    sched = Scheduler(dag=d, cost_model=CostModel())
    sched.quarantine(x.nid, now=0.0, error="boom", tenant="a")
    assert sched.is_quarantined(x.nid, now=0.1, tenant="a")
    assert not sched.is_quarantined(x.nid, now=0.1, tenant="b")
    assert not sched.is_quarantined(x.nid, now=0.1)  # untenanted view
    # a's pick skips X, b's pick still schedules it
    assert sched.pick(set(), now=0.1, tenant="a").nid == s.nid
    assert sched.pick(set(), now=0.1, tenant="b").nid == x.nid
    # an untenanted fault (e.g. real-mode worker) blocks every tenant
    sched.quarantine(s.nid, now=0.0, error="boom")
    assert sched.is_quarantined(s.nid, now=0.1, tenant="b")
    # success clears the node's history for all tenants
    sched.clear_quarantine(x.nid)
    assert not sched.is_quarantined(x.nid, now=0.1, tenant="a")
    assert "a:%d" % x.nid not in sched.quarantine_summary()


def test_one_tenants_fault_does_not_block_deduped_node(monkeypatch):
    """Regression (shared-DAG fix): tenant a's faulting background window
    must leave the deduped node schedulable — and attemptable — from tenant
    b's window."""
    eng = _engine()

    def units(node, inputs):
        def fail():
            raise RuntimeError("injected kernel fault")
        return [Unit(fn=fail, cost_s=0.1, tag="boom")]

    eng.register_op("boom", OpRuntime(units=units, combine=lambda n, i, r: 0))
    srv = MultiTenantServer(eng)
    private = DAG()
    boom = private.add("boom", kwargs={"cost_s": 0.1})
    pa = srv.submit("a", [boom])
    private2 = DAG()
    boom2 = private2.add("boom", kwargs={"cost_s": 0.1})
    pb = srv.submit("b", [boom2])
    nid = pa.roots[0].nid
    assert pb.roots[0].nid == nid  # deduped

    srv.think("a", 5.0)
    assert eng.metrics.quarantines == 1
    assert ("a", nid) in eng.scheduler.quarantined
    assert ("b", nid) not in eng.scheduler.quarantined
    # b's window still attempts the node (pre-fix: skipped, starving b)
    srv.think("b", 5.0)
    assert eng.metrics.quarantines == 2
    assert ("b", nid) in eng.scheduler.quarantined


# -------------------------------------------------- trace-replay determinism --
def test_poisson_trace_seeded_and_stable():
    spec = TraceSpec(n_sessions=20, n_events_per_session=4, seed=7)
    t1, t2 = poisson_trace(spec), poisson_trace(spec)
    assert t1 == t2
    assert len(t1) == 80
    assert all(a.at <= b.at for a, b in zip(t1, t1[1:]))
    t3 = poisson_trace(TraceSpec(n_sessions=20, n_events_per_session=4, seed=8))
    assert t3 != t1


def _replay(seed: int):
    """Minimal shared-mode trace replay (mirrors benchmarks/bench_serve.py);
    returns (schedule fingerprint, latency sequence)."""
    spec = TraceSpec(
        n_sessions=6, n_events_per_session=3, mean_think_s=2.0,
        n_templates=6, seed=seed,
    )
    events = poisson_trace(spec)
    eng = _engine()
    srv = MultiTenantServer(eng, record_schedule=True)
    per: dict = {}
    for e in events:
        per.setdefault(e.session, []).append(e)
    roots: dict = {}
    idx: dict = {}
    for s, evs in per.items():
        _, r = synthetic_trace_program(evs[0].template, evs[0].param)
        roots[(s, 0)] = srv.submit(f"s{s}", [r]).roots[0]
    prev_at, prev_s = 0.0, None
    for e in events:
        gap = e.at - prev_at
        if gap > 0 and prev_s is not None:
            srv.think(f"s{prev_s}", gap)
        k = idx.get(e.session, 0)
        srv.interact(f"s{e.session}", roots[(e.session, k)])
        idx[e.session] = k + 1
        evs = per[e.session]
        if k + 1 < len(evs):
            _, r = synthetic_trace_program(evs[k + 1].template, evs[k + 1].param)
            roots[(e.session, k + 1)] = srv.submit(f"s{e.session}", [r]).roots[0]
        prev_at, prev_s = e.at, e.session
    lats = [r.latency_s for r in eng.metrics.interactions]
    return srv.schedule_fingerprint(), lats


def test_trace_replay_deterministic():
    """Same seed → byte-identical schedule (background pick order + cache
    hit/miss sequence) and identical latencies across two replays."""
    fp1, lat1 = _replay(seed=3)
    fp2, lat2 = _replay(seed=3)
    assert fp1 == fp2  # byte-identical schedule log
    assert lat1 == lat2
    json.loads(fp1)  # fingerprint is well-formed canonical JSON
    fp3, _ = _replay(seed=4)
    assert fp3 != fp1  # the seed genuinely drives the schedule


# ------------------------------------------------------------------ stats --
def test_server_stats_surface():
    eng = _engine()
    srv = MultiTenantServer(eng)
    _, r = synthetic_trace_program(0, 0)
    p = srv.submit("t0", [r])
    srv.interact("t0", p.roots[0])
    st = srv.stats()
    assert st["tenants"] == ["t0"]
    assert st["n_programs"] == 1
    assert st["per_tenant_interactions"]["t0"]["n_interactions"] == 1
    assert st["cache"]["tenant_bytes"]["t0"] > 0


# ------------------------------------------------- intern-time observation --
def test_submit_feeds_predictor_and_speculation():
    """Multi-tenant submits bypass Engine.add, so without the intern-time
    observer the interaction predictor and speculation manager would never
    see them (the speculation blind spot).  submit() must mirror add()'s
    observation block for every genuinely new interned node — and stay
    silent for deduped resubmissions."""
    from repro.core.predictor import InteractionPredictor

    pred = InteractionPredictor()
    eng = Engine(mode="sim", budget_bytes=1 << 20, speculation=False, predictor=pred)
    register_synthetic_op(eng)
    srv = MultiTenantServer(eng)

    def transitions():
        return sum(sum(c.values()) for c in pred._next_counts.values())

    assert transitions() == 0
    _, root = synthetic_trace_program(1, 0)  # 4-node chain: 3 transitions
    srv.submit("alice", [root])
    assert transitions() == 3
    # structurally identical resubmission dedups fully: no new nodes, so no
    # phantom transition counts
    _, root2 = synthetic_trace_program(1, 0)
    srv.submit("bob", [root2])
    assert transitions() == 3
    # a fresh program's new nodes are observed again (including the add-path
    # interleaving: _last_op carries across intern and add)
    _, root3 = synthetic_trace_program(2, 1)
    srv.submit("alice", [root3])
    assert transitions() == 6  # source deduped, 3 new stage nodes observed


# ------------------------------------------------- submit against the worker --
def test_submit_races_no_background_pick():
    """The background worker picks under the engine's lock while analysts
    submit: ``submit`` interns into the shared DAG and edits the scheduler's
    per-tenant memo, so it takes the same lock (a pick filling the memo
    during ``set_tenant_demand``'s scan raised "dictionary changed size
    during iteration")."""
    import sys
    import threading
    import time

    eng = _engine()
    srv = MultiTenantServer(eng)
    errors: list = []
    done = threading.Event()
    counts = {"picks": 0, "submits": 0}

    def picker():
        try:
            while not done.is_set():
                with eng._lock:
                    eng.scheduler.pick(eng.cache.executed_ids(), now=0.0)
                counts["picks"] += 1
        except Exception as exc:  # recorded for the assertion below
            errors.append(exc)

    def submitter():
        try:
            i = 0
            while not done.is_set():
                # a source of its own per program: the memo grows with them
                d = DAG()
                src = d.add("synthetic", kwargs={"tag": f"s{i}", "cost_s": 0.1})
                root = d.add("synthetic", parents=[src], kwargs={"tag": f"r{i}"})
                srv.submit(f"t{i % 24}", [root])
                counts["submits"] += 1
                i += 1
        except Exception as exc:  # recorded for the assertion below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=picker), threading.Thread(target=submitter)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        done.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert counts["picks"] > 0 and counts["submits"] > 0
