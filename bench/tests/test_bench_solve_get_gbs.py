"""The reader of the flat solve's copy-back rate, on windows written out
by hand: bytes the ``sharing.jax.get_bytes`` counter gained over the
``sharing.jax.get`` spans' seconds, and nothing where either is missing."""

import pytest

from bench import run as harness

MS = 1_000_000   # ns
BYTES = "sharing.jax.get_bytes"

#: Two window calls, each 20 ms in ``sharing.jax.get`` bringing back one
#: 33,554,432-byte slab; set-up had brought back one before the window.
SPANS = 2 * [("sharing.jax.put", 10 * MS), ("sharing.jax.get", 20 * MS)]
BEFORE = {(BYTES, ()): {"value": 33_554_432}}
AFTER = {(BYTES, ()): {"value": 3 * 33_554_432}}


def _reading(spans=(), before=None, after=None):
    trace = type("Trace", (), {"window_s": 10.0, "n_devices": 1,
                               "busy_s": 0.1})()
    events = [("span", name, 0, dur, 1, 0, None) for name, dur in spans]
    return harness.Reading(trace, events, before or {}, after or {},
                           {"calls": 2}, None)


def _read(reading):
    reader = harness.load_module(
        harness.BENCH / "metrics" / "solve_get_gbs.sweep.py",
        "test_reader_solve_get_gbs")
    return reader.read(reading)


def test_reads_bytes_over_get_seconds():
    assert _read(_reading(SPANS, BEFORE, AFTER)) == pytest.approx(
        2 * 33_554_432 / 0.040 / 1e9)


@pytest.mark.parametrize("spans,before,after", [
    ((), None, None),
    (SPANS, None, None),
    ((), BEFORE, AFTER),
    ([("sharing.jax.put", 10 * MS)], BEFORE, AFTER),
], ids=["neither", "no_counter", "no_span", "no_get_span"])
def test_reads_nothing_without_the_counter_or_the_span(spans, before, after):
    assert _read(_reading(spans, before, after)) is None
