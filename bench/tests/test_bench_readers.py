"""The per-layer readers of ``repro.obs`` spans on a window whose spans
are written out by hand, and the configurations' sizes against the
sources they are derived from."""

import json

import pytest

from bench import run as harness

MS = 1_000_000   # ns


def _reading(spans, calls=4):
    trace = type("Trace", (), {"window_s": 1.0, "n_devices": 1,
                               "busy_s": 0.1})()
    events = [("span", name, 0, dur, {}) for name, dur in spans]
    return harness.Reading(trace, events, {}, {}, {"calls": calls}, None)


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"test_reader_{name}")


#: Two window calls of 100 ms in ``api.plan.run``, each with 30 ms in
#: ``sharing.solve_arrays``, spread over four calls' worth of spans.
SPANS = [("api.plan.run", 100 * MS), ("sharing.solve_arrays", 30 * MS),
         ("api.plan.run", 100 * MS), ("sharing.solve_arrays", 30 * MS),
         ("api.plan.pack", 40 * MS), ("api.compile", 50 * MS)]


@pytest.mark.parametrize("name,value", [
    ("plan_host_ms.sweep", (200 - 60) / 2),
    ("solve_arrays_ms.sweep", 60 / 2),
    ("pack_ms.sweep", 40 / 2),
    ("compile_share.simulate", 100 * 0.05)])
def test_span_readers(name, value):
    assert _reader(name).read(_reading(SPANS, calls=2)) == \
        pytest.approx(value)


@pytest.mark.parametrize("name", ["plan_host_ms.sweep",
                                  "solve_arrays_ms.sweep", "pack_ms.sweep",
                                  "compile_share.simulate"])
def test_span_readers_without_spans_read_nothing(name):
    assert _reader(name).read(_reading([])) is None


def test_hpcg_bytes_follow_its_local_grid():
    """Each item's bytes are HPCG's traffic at the configuration's local
    grid: a symmetric Gauss-Seidel sweep forward and back over the matrix
    (value and column index) and three vectors, a dot product of two
    vectors, an in-place update reading two vectors and writing one."""
    config = json.loads((harness.BENCH / "configs"
                         / "hpcg-rome2s-nps4.json").read_text())
    g = config["local_grid"]
    rows = g["nx"] * g["ny"] * g["nz"]
    nonzeros = g["stencil_points"] * rows
    assert (g["rows"], g["nonzeros"]) == (rows, nonzeros)
    matrix = nonzeros * (g["value_bytes"] + g["index_bytes"])
    want = {"symgs": 2 * (matrix + 3 * 8 * rows), "ddot2": 2 * 8 * rows,
            "daxpy": 3 * 8 * rows}
    got = {s["tag"]: s["bytes"] for s in config["iteration"]
           if s["op"] == "work"}
    assert got == want
    assert config["source_values"]["iterations"] == 50
    assert config["iterations"] < 50 and "iterations" in config["reduced"]
