"""The readers of the program's own spans and compile counter, on a window
whose spans and counters are written out by hand: each reads its value,
and reads nothing from a program that has neither."""

import pytest

from bench import run as harness

MS = 1_000_000   # ns
COMPILES = "backend.xla.compiles"

#: Two window calls: each sweep call spends 10 ms putting, 1 ms waiting
#: and 5 ms getting; each simulate call 200 ms expanding, 2 s building
#: records, 30 ms putting, 700 ms waiting and 20 ms getting.
SPANS = 2 * [("sharing.jax.put", 10 * MS), ("sharing.jax.wait", 1 * MS),
             ("sharing.jax.get", 5 * MS), ("api.compile.expand", 200 * MS),
             ("desync.jax.put", 30 * MS), ("desync.jax.wait", 700 * MS),
             ("desync.jax.get", 20 * MS),
             ("desync.records", 2000 * MS)]

#: Counters before and after the window: set-up compiled 3 executables
#: and loaded 2 from the cache; the window compiled 1 more.
BEFORE = {(COMPILES, (("how", "compile"),)): {"value": 3},
          (COMPILES, (("how", "cache"),)): {"value": 2}}
AFTER = {(COMPILES, (("how", "compile"),)): {"value": 4},
         (COMPILES, (("how", "cache"),)): {"value": 2}}


def _reading(spans=(), before=None, after=None, calls=2):
    trace = type("Trace", (), {"window_s": 10.0, "n_devices": 1,
                               "busy_s": 0.1})()
    events = [("span", name, 0, dur, 1, 0, None) for name, dur in spans]
    return harness.Reading(trace, events, before or {}, after or {},
                           {"calls": calls}, None)


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"test_reader_{name}")


READINGS = [
    ("solve_copy_ms.sweep", (10 + 5)),
    ("solve_wait_ms.sweep", 1),
    ("expand_ms.simulate", 200),
    ("records_ms.simulate", 2000),
    ("desync_copy_ms.simulate", 30 + 20),
    ("desync_wait_ms.simulate", 700),
    ("xla_compiles.sweep", 1),
    ("xla_compiles.simulate", 1),
]


@pytest.mark.parametrize("name,value", READINGS)
def test_reader_reads_its_spans_or_counter(name, value):
    got = _reader(name).read(_reading(SPANS, BEFORE, AFTER))
    assert got == pytest.approx(value)


@pytest.mark.parametrize("name", [n for n, _ in READINGS])
def test_reader_reads_nothing_without_them(name):
    assert _reader(name).read(_reading()) is None


@pytest.mark.parametrize("name", ["xla_compiles.sweep",
                                  "xla_compiles.simulate"])
def test_compile_counter_reads_zero_when_nothing_compiled(name):
    assert _reader(name).read(_reading(SPANS, BEFORE, BEFORE)) == 0


def test_span_reader_needs_window_calls():
    assert _reader("solve_wait_ms.sweep").read(
        _reading(SPANS, calls=0)) is None
