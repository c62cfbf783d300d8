"""The serve cell's own CPU tests: faults planted in its timed path turn
``correct`` false, and its per-layer readers read the service's spans
and counters, or nothing without them."""

import numpy as np
import pytest

from bench import conftest
from bench import run as harness

CELL = "serve.paper-table2.batch-posts"
SEED = 2**31 + 101
MS = 1_000_000   # ns


def _run():
    return harness.run(CELL, SEED, 0.6, False, require_tpu=False,
                       sizes=conftest.SIZES[CELL])


def _altered(fn):
    """Wrap the solver so that one answer of each call is off by 1e-6."""
    def wrapped(*args, **kwargs):
        out = list(fn(*args, **kwargs))
        bw = np.array(out[3], copy=True)
        flat = bw.reshape(-1)
        flat[np.flatnonzero(flat)[:1]] *= 1 + 1e-6
        out[3] = bw
        return tuple(out)
    return wrapped


def _swapped(solve):
    """Wrap the service's placed solve so that the first two lines of
    each tick are answered with each other's results."""
    def wrapped(self, sig, scens):
        out, engine = solve(self, sig, scens)
        out = list(out)
        if len(out) > 1:
            out[0], out[1] = out[1], out[0]
        return out, engine
    return wrapped


def _half_unanswered(stream):
    """Wrap the transport so that it answers the first half of each
    post's lines and ends the response there."""
    async def wrapped(self, writer, body, verb):
        lines = body.splitlines(keepends=True)
        await stream(self, writer, b"".join(lines[:len(lines) // 2]), verb)
    return wrapped


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert result["checks"]["bw_gap"]["value"] <= 1e-10


def _plant(fault, monkeypatch):
    from repro.core import sharing
    from repro.serve import coalesce, http
    if fault == "altered":
        monkeypatch.setattr(sharing, "solve_arrays",
                            _altered(sharing.solve_arrays))
    elif fault == "swapped":
        monkeypatch.setattr(coalesce.Coalescer, "_solve_placed",
                            _swapped(coalesce.Coalescer._solve_placed))
    else:
        monkeypatch.setattr(http.App, "_stream",
                            _half_unanswered(http.App._stream))


@pytest.mark.parametrize("fault", ["altered", "swapped", "half_unanswered"])
def test_serve_fault_is_caught(fault, monkeypatch):
    """The fault is planted when the window opens, after a sound set-up
    (whose warm-up posts the service must answer in full)."""
    from bench import drivers
    opened = drivers.annotation

    def opening(name, on):
        _plant(fault, monkeypatch)
        return opened(name, on)

    monkeypatch.setattr(drivers, "annotation", opening)
    result = _run()
    assert not result["correct"], result["checks"]


def _reading(spans=(), rows=None):
    """A window of the service: ``rows`` of ``serve.*`` instruments as
    ``{name: (before, after)}``, each a counter value or a histogram's
    (count, sum)."""
    before, after = {}, {}
    for name, (old, new) in (rows or {}).items():
        for side, v in ((before, old), (after, new)):
            side[(name, ())] = ({"value": v} if not isinstance(v, tuple)
                                else {"count": v[0], "sum": v[1]})
    events = [("span", name, 0, dur, 1, 0, {}) for name, dur in spans]
    trace = type("Trace", (), {"window_s": 1.0, "n_devices": 1,
                               "busy_s": 0.1})()
    return harness.Reading(trace, events, before, after, {}, None)


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"test_reader_{name}")


SERVE = {"serve.queue_s": ((2, 1.0), (6, 1.02)),
         "serve.tick.batch": ((1, 64), (3, 364)),
         "serve.plan.miss": (45, 45)}
READERS = ["serve_queue_ms.serve", "serve_tick_rows.serve",
           "serve_solve_ms.serve", "serve_plan_misses.serve"]


@pytest.mark.parametrize("name,value", zip(READERS, [
    1e3 * 0.02 / 4, 300 / 2, 20.0, 0]))
def test_serve_readers(name, value):
    r = _reading([("serve.solve", 10 * MS), ("serve.solve", 30 * MS),
                  ("serve.parse", 99 * MS)], SERVE)
    assert _reader(name).read(r) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
def test_serve_readers_without_ticks_read_nothing(name):
    assert _reader(name).read(_reading()) is None


def test_idle_share_reads_the_window():
    assert _reader("idle_share.serve").read(_reading()) == \
        pytest.approx(90.0)


#: Four coalesced solves in the window.
SOLVES = {"serve.tick.batch": ((1, 64), (5, 4160))}


@pytest.mark.parametrize("name,span", [("pack_ms.serve", "api.plan.pack"),
                                       ("solve_arrays_ms.serve",
                                        "sharing.solve_arrays")])
def test_serve_host_readers_read_ms_per_solve(name, span):
    reader = _reader(name)
    spans = [(span, 10 * MS), (span, 30 * MS), ("serve.solve", 99 * MS)]
    assert reader.read(_reading(spans, SOLVES)) == pytest.approx(10.0)
    # No solve in the window, or no such span: nothing to read.
    assert reader.read(_reading(spans)) is None
    assert reader.read(_reading([("serve.solve", 99 * MS)], SOLVES)) is None


@pytest.mark.parametrize("before,after,value", [(37, 37, 0), (37, 39, 2)])
def test_serve_window_compiles(before, after, value):
    r = _reading(rows={"backend.xla.compiles": (before, after)})
    assert _reader("xla_compiles.serve").read(r) == value


def test_serve_window_compiles_without_the_counter_read_nothing():
    assert _reader("xla_compiles.serve").read(_reading()) is None
