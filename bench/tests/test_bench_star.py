"""The TPC-H star cell, ``star.runall``: its tables, its traffic, the
reference's answers, whole runs on the CPU (sound and with the join broken
underneath), and the readers of its per-layer metrics on a recorded span
list and trace."""
import time

import numpy as np
import pytest

from bench import check, control, harness, tables, traffic
from bench import trace as tracing
from bench.harness import read_metric
from bench.metrics import join_probe_roofline
from bench.reference import Reference
from repro.frame import blocking
from repro.obs import SpanRecord

from .conftest import tiny

CELL = "star.runall"
FOREIGN_KEYS = {("lineitem", "l_orderkey"): ("orders", "o_orderkey"),
                ("lineitem", "l_partkey"): ("part", "p_partkey"),
                ("orders", "o_custkey"): ("customer", "c_custkey")}


@pytest.fixture(scope="module")
def data():
    return tables.make_tables(tiny(CELL).config)


def test_tables_generate_and_every_foreign_key_hits(data):
    cell = tiny(CELL)
    for t in cell.config["tables"]:
        got = data[t["name"]]
        assert got.nrows == t["nrows"] >= 64
        assert all(len(v) == got.nrows for v in got.data.values())
    for (table, col), (dim, key) in FOREIGN_KEYS.items():
        keys = data[dim].data[key]
        assert len(np.unique(keys)) == len(keys)  # a dimension's keys are unique
        assert np.isin(data[table].data[col], keys).all()
    status = data["orders"].dictionary["o_orderstatus"]
    assert list(status) == ["F", "O", "P"]
    drawn = status[data["orders"].data["o_orderstatus"]]
    assert set(drawn) == {"F", "O"}
    assert ((drawn == "F") == (data["orders"].data["o_orderdate"] <= 1142)).all()


def test_star_shares_lineitem_and_part_with_the_lineitem_cell():
    """The same seed makes the same lineitem columns and part table as
    ``tpch_lineitem_sf1``: the two cells measure the same filters."""
    star = tiny(CELL).config
    flat = tiny("notebook.runall").config
    a, b = tables.make_tables(star), tables.make_tables(flat)
    for name in b["lineitem"].order:
        np.testing.assert_array_equal(a["lineitem"].data[name], b["lineitem"].data[name])
    for name in b["part"].order:
        np.testing.assert_array_equal(a["part"].data[name], b["part"].data[name])


def test_traffic_has_bounded_filters_and_distinct_frames():
    mix = harness.load_cell(CELL).mix
    items = [it for a in traffic.generate(mix, 5_000_000_011) for it in a.interactions]
    assert len(items) == 240
    filters = {it.recipe[:2] for it in items if it.recipe[1][0] == "where"}
    assert len(filters) <= 24
    keys = [(it.recipe, it.action) for it in items]
    assert len(set(keys)) == len(keys)
    counts = {t: sum(it.template == t for it in items)
              for t in ("q3", "q4", "q10", "q14", "q14_all")}
    assert counts == {"q3": 72, "q4": 48, "q10": 24, "q14": 48, "q14_all": 48}
    assert all(any(step[0] == "join" for step in it.recipe) for it in items)


def test_reference_answers_every_template(data):
    ref = Reference(data)
    seen = {}
    for a in traffic.generate(harness.load_cell(CELL).mix, 7):
        for it in a.interactions:
            if it.template not in seen:
                seen[it.template] = ref.evaluate(it.recipe, it.action)
    assert set(seen) == {"q3", "q4", "q10", "q14", "q14_all"}
    assert list(seen["q10"].table) == ["stat", "rev", "o_totalprice", "c_acctbal"]
    q14_all = seen["q14_all"].table
    assert q14_all["l_quantity"].min() >= 1  # every line joined, grouped by brand
    counts = seen["q4"].table["rev"]
    assert counts.sum() > 0 and (counts == np.round(counts)).all()


def test_bfloat16_control_fails():
    got = control.readings(tiny(CELL), 11, 3.0)
    assert got["compared"] > 0
    assert not check.within(got, harness.load_cell(CELL).limits), got


def run(traced=False):
    return harness.run_cell(tiny(CELL, "interpret"), 2**31 + 17, 3.0, traced,
                            time.perf_counter())


def test_sound_run_is_correct(no_chip_look):
    r = run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"interaction_p50_s", "interaction_p90_s", "setup_s"}


def test_join_that_drops_a_line_is_not_correct(monkeypatch, no_chip_look):
    def first_hit_lost(assemble):
        def broken(left, rmerged, gather, hit, *args, **kwargs):
            hit = np.asarray(hit).copy()
            if hit.any():
                hit[np.argmax(hit)] = False
            return assemble(left, rmerged, gather, hit, *args, **kwargs)
        return broken

    monkeypatch.setattr(blocking, "join_assemble", first_hit_lost(blocking.join_assemble))
    r = run()
    assert not r["correct"], r["checks"]


# -- the readers --------------------------------------------------------------

MS = 1_000_000  # ns
A = 11  # the interacting thread


def spans_of(rows):
    """SpanRecords whose self time is their duration less their children's."""
    child = {}
    for sid, parent, _, t0, t1, _ in rows:
        child[parent] = child.get(parent, 0) + (t1 - t0)
    return [SpanRecord(sid, parent, name, 1, A, t0 * MS, t1 * MS,
                       (t1 - t0 - child.get(sid, 0)) * MS, attrs)
            for sid, parent, name, t0, t1, attrs in rows]


# One interaction, due at 1000 ms and shown at 2000: the orders build (its
# upload inside it), one partition's probe call against 1,500,000 keys and
# its assembly, and a second partition's call against 200,000.
SPANS = spans_of([
    (1, 0, "engine.display", 1000, 1900, {}),
    (2, 1, "join.build", 1100, 1300, {"right_rows": 1_500_000, "bytes": 6_000_000}),
    (3, 2, "dispatch.upload", 1250, 1300, {"bytes": 6_000_000}),
    (4, 1, "dispatch.call", 1300, 1310, {"family": "join", "rows": 100_000,
                                         "bucket": 131072, "right_rows": 1_500_000}),
    (5, 1, "join.assemble", 1400, 1440, {"rows": 100_000, "cols": 5}),
    (6, 1, "dispatch.call", 1500, 1505, {"family": "join", "rows": 2_000_000,
                                         "bucket": 2097152, "right_rows": 200_000}),
    (7, 1, "dispatch.call", 1600, 1601, {"family": "filter", "rows": 9, "bucket": 512}),
])


def run_record(trace=None, n_shown=1):
    item = traffic.Interaction("q4", (), ("describe",))
    shown = [harness.Shown("a0", item, 1.0, 1.0, 2.0, 2.0, 0.0, False)] * n_shown
    return harness.RunRecord(cell=CELL, shown=shown, window_s=1.0, units_total=0,
                             units_foreground=0, served={}, window_compiles=0,
                             trace=trace)


def probe_trace():
    """The probe's op as a TPU trace names it: the Pallas call by its scope;
    another Pallas call and sorts (the probe runs none) do not count."""
    return tracing.TraceSummary(busy_s=1.0, window_s=2.0, op_s={
        "%join_probe.3 = (s32[131072,128]{1,0:T(8,128)}, s32[131072,128]{1,0:T(8,128)}) "
        "custom-call(s32[16,1,8]{2,1,0} %p0), custom_call_target=\"tpu_custom_call\"": 0.004,
        "%sort = (f32[131072]{0:T(1024)}, s32[131072]{0:T(1024)}) sort(f32[131072]"
        "{0:T(1024)} %p, s32[131072]{0:T(1024)} %iota), dimensions={0}": 0.001,
        "%masked_stats.1 = f32[8,128]{1,0:T(8,128)} custom-call(f32[32768,128] %x)": 0.5,
        "%sort = (f32[4194304]{0:T(1024)}, f32[4194304]{0:T(1024)}, f32[4194304]"
        "{0:T(1024)}, s32[4194304]{0:T(1024)}) sort(f32[4194304] %hi.1)": 0.25,
    })


def test_join_host_ms_reads_build_and_assemble(monkeypatch):
    from bench import spans

    monkeypatch.setattr(spans, "program_spans", lambda: SPANS)
    # build self 200 - 50 ms (its upload is a child), assemble 40 ms
    assert read_metric("join_host_ms", run_record()) == pytest.approx(190.0)
    monkeypatch.setattr(spans, "program_spans", lambda: [
        s for s in SPANS if not s.name.startswith("join.")])
    assert read_metric("join_host_ms", run_record()) is None


def test_join_probe_ms_reads_the_call_and_its_sort():
    """The call alone: a sort of keys and rows, which an earlier probe ran
    before its call, is another program's now."""
    assert read_metric("join_probe_ms", run_record(probe_trace(), 2)) == pytest.approx(2.0)
    assert read_metric("join_probe_ms", run_record()) is None


def test_join_probe_roofline_from_calls_and_trace(monkeypatch):
    from bench import spans

    monkeypatch.setattr(spans, "program_spans", lambda: SPANS)
    monkeypatch.setattr(harness.jax, "devices", lambda: [type("D", (), {
        "device_kind": "TPU v5 lite"})()])
    moved = (join_probe_roofline.probe_bytes(100_000, 1_500_000)
             + join_probe_roofline.probe_bytes(2_000_000, 200_000))
    assert moved == 9 * 2_100_000 + 4 * 1_700_000
    got = read_metric("join_probe_roofline", run_record(probe_trace()))
    assert got == pytest.approx(100 * moved / 819e9 / 0.004)
    assert 0 < got < 100
    # no probe calls with their right side's size (a program before the
    # attribute), or no trace: nothing to read
    monkeypatch.setattr(spans, "program_spans", lambda: [
        s._replace(attrs={k: v for k, v in s.attrs.items() if k != "right_rows"})
        for s in SPANS])
    assert read_metric("join_probe_roofline", run_record(probe_trace())) is None
    assert read_metric("join_probe_roofline", run_record()) is None
