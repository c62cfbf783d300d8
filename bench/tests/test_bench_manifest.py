"""``BENCHMARK.json`` is well formed, and every name in it finds its file:
configurations, traffic mixes with their drivers, per-layer readers."""

import json
import re

import pytest

from bench import run as harness

ROOT = harness.ROOT
MANIFEST = harness.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_top_level_and_sizes():
    assert set(MANIFEST) == KEYS["top"]
    assert len(json.dumps(MANIFEST)) <= 64 * 1024
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")
    for word in MANIFEST["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word


def test_a_full_check_fits_its_time():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (MANIFEST["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("kind,entries", [
    ("config", MANIFEST["configs"]), ("workload", MANIFEST["workloads"]),
    ("end_to_end", MANIFEST["end_to_end"]),
    ("per_layer", MANIFEST["per_layer"])])
def test_entries_have_their_keys_and_names(kind, entries):
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
        assert KEYS[kind] <= set(e) <= KEYS[kind] | extra, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e and kind in ("config", "workload", "per_layer"):
                assert _line(e[key]), (key, e[key])


def test_names_are_unique_across_kinds():
    metrics = list(E2E) + [m["name"] for m in MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs_exist_and_are_used():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = set()
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        body = harness.load_json(ROOT / c["file"])
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in body
            assert not key.endswith(("_dim", "_rank"))
        assert c["source"].startswith(("https://", "http://", "arXiv:"))


def test_cells_find_their_traffic_and_driver():
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = harness.load_json(ROOT / "bench" / "traffic"
                                    / f"{w['traffic']}.json")
        assert (ROOT / "bench" / "drivers"
                / f"{traffic['driver']}.py").is_file()
        assert "limits" in traffic
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_end_to_end_metrics():
    assert "setup_s" in E2E
    assert E2E["setup_s"]["bound"] <= 0.25
    for m in E2E.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert _cells_of(m) <= set(CELLS)
    for cell in CELLS:
        reported = [n for n, m in E2E.items() if cell in _cells_of(m)]
        assert "setup_s" in reported and len(reported) >= 2, cell


def test_per_layer_metrics_find_readers_and_move_their_cells():
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in E2E
        assert _cells_of(m) <= _cells_of(E2E[m["moves"]]), m["name"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values()), layers
    for cell in CELLS:
        assert harness.reported_per_layer(harness.find_cell(cell)), cell


def test_files_under_paths_are_named_from_name_characters():
    for path in (ROOT / "bench").rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert PATH.match(rel), rel


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
