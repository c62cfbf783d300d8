"""The trace reducer on small traces: one written out by hand, whose
numbers are known, and one recorded on a TPU v5e."""

import pathlib

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).parent / "data"

#: Times in ns from the start of the trace (the lines start at 1000 ns).
#: Window 0..100 us; bench.plan.run 10..30 us and 50..90 us; the device
#: runs ops at 15..20 us, 18..25 us (overlapping) and 60..70 us, inside
#: two runs of one program, and one op after the window closed.
HAND = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.plan.run" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 15000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 18000000 duration_ps: 7000000 }
    events { metadata_id: 1 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 120000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_solver(11)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_solver(12)" } }
}
"""


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(HAND)))


def test_busy_is_the_union_inside_the_window(hand):
    assert hand.n_devices == 1
    assert hand.window_s == pytest.approx(100e-6)
    assert hand.busy_s == pytest.approx(20e-6)     # 15..25 and 60..70 us


def test_ops_and_programs(hand):
    assert hand.ops == pytest.approx({"fusion.1": 15e-6, "copy.2": 7e-6})
    assert hand.top_ops(1) == [["fusion.1", pytest.approx(15e-6)]]
    secs, runs = hand.module_seconds(r"^jit_solver$")
    assert (secs, runs) == (pytest.approx(20e-6), 2)
    assert hand.module_seconds("nothing") == (0, 0)


def test_idle_gaps_are_named_by_the_open_span(hand):
    # Idle: 0..15 (plan.run from 10), 25..60 (plan.run to 30, from 50),
    # 70..100 (plan.run to 90).
    assert hand.gaps == pytest.approx({
        "bench.plan.run": 5e-6 + 5e-6 + 10e-6 + 20e-6,
        trace_reduce.BETWEEN: 10e-6 + 20e-6 + 10e-6})


def test_a_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    text = HAND.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(text)))


def test_recorded_tpu_trace():
    """Three runs of one small jitted program on a TPU v5e, each inside a
    ``bench.call`` span, all inside the window
    (``bench/tests/data/record_tiny.py``).  The device clock of this
    trace reads about 1.5 ms behind the host's, so the first run, which
    starts within 1.5 ms of the window's start, is stamped before it."""
    summary = trace_reduce.reduce(DATA / "tpu_v5e_tiny.xplane.pb")
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(9.935028e-3)
    assert summary.busy_s == pytest.approx(3.677e-6)
    secs, runs = summary.module_seconds(r"^jit_tiny$")
    assert runs == 2 and secs == pytest.approx(3.691e-6)
    assert summary.ops == pytest.approx(
        {"copy-start": 2.7e-8, "copy-done": 5e-9, "fusion": 3.645e-6})
    assert sum(summary.gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)
    assert set(summary.gaps) == {"bench.call", trace_reduce.BETWEEN}
