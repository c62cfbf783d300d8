"""Every cell's whole run at a tiny size on the CPU: set-up, window,
comparison.  Sound runs come out correct; the control (the reference in
float32 in the program's place) and faults planted in the timed path
come out not correct."""

import json

import numpy as np
import pytest

from bench import run as harness

SEED = 2**31 + 101

#: Tiny sizes laid over each cell's configuration and traffic.
SIZES = {
    "sweep.paper-table2.numbers": {
        "traffic": {"scenarios": 64, "pool": 3, "check_scenarios": 32}},
    "sweep.paper-table2.placements": {
        "traffic": {"scenarios": 256, "placement_pool": 512,
                    "max_groups": 2, "check_scenarios": 32}},
    "simulate.hpcg-rome2s-nps4.e256": {
        "config": {"iterations": 2},
        "traffic": {"ensemble": 4, "check_members": 3}},
}
CELLS = [w["name"] for w in
         harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


def _run(cell, trace=False, seconds=0.6):
    return harness.run(cell, SEED, seconds, trace, require_tpu=False,
                       sizes=SIZES[cell])


def test_every_cell_has_tiny_sizes():
    assert set(CELLS) <= set(SIZES)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    json.dumps(result, allow_nan=False)
    names = {m["name"] for m in harness.find_cell(cell)["end_to_end"]}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_window(cell):
    result = _run(cell, trace=True)
    assert result["correct"], result["checks"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _subject(cell):
    c = harness.find_cell(cell)
    for part, values in SIZES[cell].items():
        c[part] = {**c[part], **values}
    return _driver(c["traffic"]["driver"]).Cell(c["config"], c["traffic"],
                                                SEED)


def _driver(name):
    return harness.load_module(harness.BENCH / "drivers" / f"{name}.py",
                               f"test_driver_{name}")


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    subject = _subject(cell)
    window = subject.window(0.5, annotate=False)
    subject.release()
    checks, _, _ = subject.check(window, control=True)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _altered(fn, where):
    """Wrap a solver so that one answer it produces is off by 1e-6."""
    def wrapped(*args, **kwargs):
        out = list(fn(*args, **kwargs))
        bw = np.array(out[where], copy=True)
        flat = bw.reshape(-1)
        flat[np.flatnonzero(flat)[:1]] *= 1 + 1e-6
        out[where] = bw
        return tuple(out)
    return wrapped


def _half_left_out(fn, where):
    """Wrap a solver so that it solves the first half of the batch only."""
    def wrapped(*args, **kwargs):
        out = list(fn(*args, **kwargs))
        bw = np.array(out[where], copy=True)
        bw[bw.shape[0] // 2:] = 0.0
        out[where] = bw
        return tuple(out)
    return wrapped


def _stale(fn, where):
    """Wrap a solver so that every call returns the answers of its first
    call: the state is never brought up to date."""
    first = []

    def wrapped(*args, **kwargs):
        if not first:
            first.append(fn(*args, **kwargs))
        return first[0]
    return wrapped


FAULTS = {"altered": _altered, "half_left_out": _half_left_out,
          "stale": _stale}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", [c for c in CELLS if c.startswith("sweep.")])
def test_sweep_fault_is_caught(cell, fault, monkeypatch):
    from repro.core import sharing
    monkeypatch.setattr(sharing, "solve_arrays",
                        FAULTS[fault](sharing.solve_arrays, 3))
    result = _run(cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_simulate_fault_is_caught(fault, monkeypatch):
    from repro.core import desync_batch

    engine = desync_batch._run_jax
    first = []

    def faulty(enc, *args, **kwargs):
        res = engine(enc, *args, **kwargs)
        if fault == "stale":
            first.append(first[0] if first else res)
            return first[-1]
        start, end = res.start.copy(), res.end.copy()
        if fault == "altered":
            end[:, :, 1:] *= 1 + 1e-6
        else:
            start[start.shape[0] // 2:] = np.nan
            end[end.shape[0] // 2:] = np.nan
        res.start, res.end = start, end
        return res

    monkeypatch.setattr(desync_batch, "_run_jax", faulty)
    result = _run("simulate.hpcg-rome2s-nps4.e256")
    assert not result["correct"], result["checks"]
