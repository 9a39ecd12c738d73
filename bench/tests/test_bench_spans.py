"""The readers of the program's spans (``bench/spans.py``) on a recorded
span list: the window cut, each per-layer number, and None where the
program records no spans."""
import pytest

from bench import harness, spans, traffic
from bench import trace as tracing
from bench.harness import read_metric
from repro.obs import SpanRecord

MS = 1_000_000  # ns
A, B = 11, 22  # the interacting thread, the background worker's


def shown(due, at):
    item = traffic.Interaction("describe", (), ("describe",))
    return harness.Shown("a0", item, due, due, at, at, 0.0, False)


def record(sid, parent, name, rid, t0_ms, t1_ms, thread=A, **attrs):
    return [sid, parent, name, rid, thread, t0_ms * MS, t1_ms * MS, attrs]


def with_self_time(rows):
    """SpanRecords whose self time is their duration less their children's,
    as the program records it."""
    child = {}
    for sid, parent, _, _, _, t0, t1, _ in rows:
        child[parent] = child.get(parent, 0) + (t1 - t0)
    return [SpanRecord(sid, parent, name, rid, thread, t0, t1,
                       t1 - t0 - child.get(sid, 0), attrs)
            for sid, parent, name, rid, thread, t0, t1, attrs in rows]


# Two interactions: due at 1000 ms, shown at 2000 ms; due at 2000, shown at
# 3000.  Spans of the first (request 1) and second (request 2) on thread A;
# a warm-up span before the window, one after it, and the background
# worker's spans on thread B inside it, which the cut leaves out.
SPANS = with_self_time([
    record(1, 0, "engine.display", 1, 1000, 1900),
    record(2, 1, "engine.pause_ack", 1, 1000, 1010),
    record(3, 1, "engine.lock_wait", 1, 1010, 1020),
    record(4, 1, "exec.node", 1, 1100, 1800),
    record(5, 4, "exec.combine", 1, 1700, 1800),
    record(6, 5, "exec.combine", 1, 1750, 1760),  # nested: not counted twice
    record(7, 4, "dispatch.prep", 1, 1200, 1300),
    record(8, 7, "dispatch.upload", 1, 1200, 1250, bytes=2_000_000),
    record(9, 4, "dispatch.wait", 1, 1300, 1400),
    record(10, 4, "dispatch.readback", 1, 1400, 1450, bytes=1_000_000),
    record(11, 0, "serve.submit", None, 1950, 1990),
    record(12, 0, "engine.display", 2, 2000, 2500),
    record(13, 12, "engine.lock_wait", 2, 2000, 2300),
    record(20, 0, "engine.display", 0, 100, 200),  # warm-up: before the window
    record(21, 0, "dispatch.prep", None, 3500, 3600),  # after the window
    record(30, 0, "worker.execute", ("node", 4), 1500, 1600, thread=B),
    record(31, 30, "dispatch.upload", ("node", 4), 1500, 1550, thread=B,
           bytes=5_000_000),
])

EXPECTED = {
    # per request: 10 + 10 ms and 300 ms; the 90th percentile of the two
    "admission_wait_p90_s": 0.020 + 0.9 * (0.300 - 0.020),
    # display selves 180 + 200, pause 10, locks 10 + 300 ms, over two shown
    "engine_self_ms": (180 + 200 + 10 + 10 + 300) / 2,
    "combine_ms": 100 / 2,
    "host_prep_ms": (100 - 50) / 2,
    "transfer_ms": (50 + 50) / 2,
    "transfer_mb": 3.0 / 2,
    "device_wait_ms": 100 / 2,
}


def run_record(trace=None):
    return harness.RunRecord(
        cell="notebook.runall", shown=[shown(1.0, 2.0), shown(2.0, 3.0)],
        window_s=2.0, units_total=0, units_foreground=0, served={},
        window_compiles=0, trace=trace)


def test_window_keeps_the_interacting_threads_spans_inside_it(monkeypatch):
    monkeypatch.setattr(spans, "program_spans", lambda: SPANS)
    kept = spans.window(run_record())
    assert sorted(s.sid for s in kept) == list(range(1, 14))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_recorded_spans(name, monkeypatch):
    monkeypatch.setattr(spans, "program_spans", lambda: SPANS)
    assert read_metric(name, run_record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_program_spans_reads_nothing(name, monkeypatch):
    monkeypatch.setattr(spans, "program_spans", lambda: [])
    assert read_metric(name, run_record()) is None
    # spans, but none of an interacting thread inside the window
    monkeypatch.setattr(spans, "program_spans", lambda: SPANS[-4:])
    assert read_metric(name, run_record()) is None


def test_program_without_obs_gives_no_spans(monkeypatch):
    import importlib

    real = importlib.import_module

    def no_obs(name, *args):
        if name == "repro.obs":
            raise ModuleNotFoundError(name)
        return real(name, *args)

    monkeypatch.setattr(importlib, "import_module", no_obs)
    assert spans.program_spans() == []


def trace_summary():
    """Device ops as a TPU v5e trace names them (HLO text, no op metadata):
    the filter's Pallas call at two buckets, the fusion that stitches its
    rows (at 2^21 straight from the positions, at 2^22 after the sort), and
    ops of other kernels that must not count."""
    filt = {
        "%filter_compact.2 = f32[16384,128]{1,0:T(8,128)S(1)} custom-call(f32[16384,128]"
        "{1,0:T(8,128)S(1)} %bitcast.16, f32[16384,128]{1,0:T(8,128)S(1)} "
        "%convert_bitcast_fusion), custom_call_target=\"tpu_custom_call\"": 0.25,
        "%filter_compact.2 = f32[32768,128]{1,0:T(8,128)S(1)} custom-call(f32[32768,128]"
        "{1,0:T(8,128)S(1)} %bitcast.20, f32[32768,128]{1,0:T(8,128)S(1)} "
        "%convert_bitcast_fusion), custom_call_target=\"tpu_custom_call\"": 0.5,
        "%fusion = f32[2097153]{0:T(1024)S(1)} fusion(s32[2097152]{0:T(1024)S(1)} "
        "%bitcast.14, f32[2097152]{0:T(1024)S(1)} %bitcast.15, f32[]{:T(128)} "
        "%constant.10), kind=kCustom, calls=%fused_computation.6": 1.0,
        "%sort = (s32[4194304]{0:T(1024)S(1)}, f32[4194304]{0:T(1024)S(1)}) sort("
        "s32[4194304]{0:T(1024)S(1)} %bitcast.16, f32[4194304]{0:T(1024)S(1)} "
        "%bitcast.18), dimensions={0}, to_apply=%compare": 0.75,
        "%fusion = f32[4194305]{0:T(1024)S(1)} fusion(s32[4194304]{0:T(1024)S(1)} "
        "%get-tuple-element, f32[4194304]{0:T(1024)S(1)} %get-tuple-element.1, "
        "f32[]{:T(128)} %constant.10), kind=kCustom, calls=%fused_computation.6": 2.0,
    }
    other = {
        "%masked_stats.1 = f32[8,128]{1,0:T(8,128)S(1)} custom-call(f32[32768,128]"
        "{1,0:T(8,128)S(1)} %bitcast.3, f32[32768,128]{1,0:T(8,128)S(1)} "
        "%convert_bitcast_fusion), custom_call_target=\"tpu_custom_call\"": 0.125,
        "%sort = (f32[4194304]{0:T(1024)}, f32[4194304]{0:T(1024)}, f32[4194304]"
        "{0:T(1024)}, s32[4194304]{0:T(1024)}) sort(f32[4194304]{0:T(1024)} %hi.1, "
        "f32[4194304]{0:T(1024)} %mid.1, f32[4194304]{0:T(1024)} %lo.1, "
        "s32[4194304]{0:T(1024)} %iota), dimensions={0}, is_stable=true": 4.0,
        "%fusion.1 = f32[4194304]{0:T(1024)} fusion(s32[4194304]{0:T(1024)} %p), "
        "kind=kLoop, calls=%fused_computation.1": 8.0,
    }
    return tracing.TraceSummary(busy_s=16.625, window_s=20.0, op_s={**filt, **other})


def test_filter_device_ms_reads_the_whole_filter():
    run = run_record(trace_summary())
    assert read_metric("filter_device_ms", run) == pytest.approx(4.5e3 / 2)
    # the Pallas call alone is what filter_compact_ms reads
    assert read_metric("filter_compact_ms", run) == pytest.approx(0.75e3 / 2)
    assert read_metric("filter_device_ms", run_record()) is None
