"""trace.py on a small recorded trace (an XSpace as text proto): busy and
idle time inside the window, kernel seconds by name, gap labels."""
import pytest

from bench import trace as tracing

# Offsets are picoseconds from each line's timestamp (1 us).  Window:
# 1-11 us.  Device 0 runs the masked_stats kernel over 2-4 us, another op
# over 3-5 us (overlapping: 3 us busy) and fusion.1 over 9-10 us; an op at
# 21 us lies outside the window.  Device 1 runs one 5 us op.  The host is in
# interact:describe over 1-5 us and think_wait over 5-10 us.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000
             stats { metadata_id: 9 str_value: "tpu_custom_call _masked_stats_kernel" } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
  stat_metadata { key: 9 value { id: 9 name: "long_name" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "interact:describe" } }
  event_metadata { key: 3 value { id: 3 name: "think_wait" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return tracing.summarize(tracing.events_from_profile(ProfileData.from_text_proto(TRACE)))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(10e-6)
    # device 0: 3 us + 1 us inside the window; device 1: 5 us; averaged
    assert summary.busy_s == pytest.approx((4e-6 + 5e-6) / 2)
    assert summary.devices == 2


def test_op_seconds_and_kernel_match(summary):
    assert summary.op_s["fusion.1"] == pytest.approx(1e-6)  # the 20 us op is outside
    assert summary.seconds_matching("masked_stats") == pytest.approx(2e-6)
    assert summary.seconds_matching("join_probe") is None


def test_idle_gaps_are_labelled_by_host_span(summary):
    # device 0 idle: 1-2 us (interact), 5-9 us (think_wait), 10-11 us (other)
    gaps = dict((label, s) for label, s in summary.gaps)
    assert summary.gaps[0] == ("think_wait", pytest.approx(4e-6))
    assert gaps["interact:describe"] == pytest.approx(1e-6)
    assert gaps["other"] == pytest.approx(1e-6)
    bd = summary.breakdown()
    assert bd["device_ops"][0][0] == "fusion.7" and len(bd["idle_gaps"]) == 3


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.summarize([tracing.Event("/host:CPU", "python", "x", 0.0, 1.0)])
