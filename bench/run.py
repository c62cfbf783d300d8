"""The benchmark's one command: run one cell of ``BENCHMARK.json`` on the
accelerator this process finds, and print one JSON result line.

    python bench/run.py --workload sweep.paper-table2.numbers \\
        --seed 1234 --seconds 30 --trace 0

Everything that belongs to one cell is data found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* ``bench/configs/<config>.json`` holds the deployment (machines, kernels,
  programs) as it is run, with ``reduced`` and ``assumed``;
* ``bench/traffic/<traffic>.json`` holds the mix's parameters and names
  the general driver in ``bench/drivers/`` that runs it;
* ``bench/metrics/<metric>.py`` reads one per-layer metric.

A run sets the cell up (loading, building the inputs from ``--seed``,
compiling, warming every shape the window uses), measures for
``--seconds`` seconds, reads the device's peak memory, frees the program's
state and compares what the window produced with the plain reference in
``bench/reference``.  ``--trace 1`` runs the window under the JAX
profiler and ``repro.obs`` tracing and reports the per-layer metrics
instead of the end-to-end ones.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: Profiler output of a traced run: a fixed path inside the checkout.
TRACE_DIR = ROOT / ".bench_run" / "trace"

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class Refused(SystemExit):
    """The run cannot be measured here; no result is printed."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic mix and
    metric entries, read from ``BENCHMARK.json``."""
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"bench: no workload {workload!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[cell["config"]]
    return {
        "workload": cell,
        "config": load_json(root / entry["file"]),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in manifest["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": manifest["per_layer"],
    }


def reported_per_layer(cell: dict) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a ``workloads`` key whose end-to-end metric it reports."""
    name = cell["workload"]["name"]
    e2e = {m["name"] for m in cell["end_to_end"]}
    return [m for m in cell["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def peaks_for(kind: str) -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def check_device(chips: int) -> dict:
    """The devices JAX computes on; refuses anything but ``chips`` or more
    TPUs whose kind has known peaks."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise Refused(f"bench: no TPU visible (JAX platform "
                      f"{device['platform']!r}); refusing to run")
    if device["count"] < chips:
        raise Refused(f"bench: the cell needs {chips} chips, JAX sees "
                      f"{device['count']}")
    peaks_for(device["kind"])
    return device


def _jit_misses() -> int:
    from repro.obs import metrics
    return sum(row["value"] for row in metrics.snapshot()
               if row["name"] == "backend.jit.miss")


def _peak_bytes(n_devices: int) -> int:
    import jax
    peaks = []
    for dev in jax.local_devices()[:n_devices]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class Reading:
    """What a per-layer metric reader sees: the reduced device trace, the
    ``repro.obs`` spans and counter deltas of the window, what the driver
    counted, and the device's peaks."""

    def __init__(self, summary, spans, before, after, info, peaks):
        self.trace = summary
        self.spans = spans
        self.info = info
        self.peaks = peaks
        self._before = before
        self._after = after

    def _rows(self, name):
        return [(k, v) for k, v in self._after.items() if k[0] == name]

    def counter(self, name: str) -> float:
        """Increase of every counter ``name`` (all labels) in the window."""
        return sum(v["value"] - self._before.get(k, {}).get("value", 0)
                   for k, v in self._rows(name))

    def histogram(self, name: str) -> tuple[int, float]:
        """(observations, their sum) of histogram ``name`` in the window."""
        n = s = 0
        for k, v in self._rows(name):
            old = self._before.get(k, {})
            n += v["count"] - old.get("count", 0)
            s += v["sum"] - old.get("sum", 0.0)
        return n, s

    def span_seconds(self, name: str) -> float:
        """Summed duration of the ``repro.obs`` spans called ``name``."""
        return sum(ev[3] for ev in self.spans
                   if ev[0] == "span" and ev[1] == name) * 1e-9


def _snapshot() -> dict:
    from repro.obs import metrics
    return {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in metrics.snapshot()}


def traced_window(driver_cell, seconds: float):
    """Run the window under the JAX profiler and ``repro.obs`` tracing;
    returns the window's result, the reduced trace, the spans and the
    counter snapshots before and after."""
    import jax
    from repro.obs import trace as obs_trace

    from bench import trace_reduce
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    obs_trace.enable(clear_events=True)
    before = _snapshot()
    # The device and the bench.* annotations only: no tracing of every
    # Python call, which would slow the host path it measures, and none of
    # the runtime's own host events, which nothing reads.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            window = driver_cell.window(seconds, annotate=True)
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        obs_trace.disable()
    after = _snapshot()
    t1 = time.perf_counter()
    summary = trace_reduce.reduce(trace_reduce.find_xplane(TRACE_DIR))
    print(f"bench: trace written in {t1 - t0:.1f} s, reduced in "
          f"{time.perf_counter() - t1:.1f} s", file=sys.stderr)
    return window, summary, obs_trace.events(), before, after


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, require_tpu: bool = True,
        sizes: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``require_tpu=False`` and ``sizes`` (``{"config": {...}, "traffic":
    {...}}`` entries laid over the cell's files) serve the CPU tests."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = find_cell(workload)
    for part, values in (sizes or {}).items():
        cell[part] = {**cell[part], **values}
    chips = int(cell["workload"]["chips"])
    if require_tpu:
        device = check_device(chips)
        peaks = peaks_for(device["kind"])
    else:
        import jax
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        peaks = None
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import backend
    backend.enable_compile_cache()

    traffic = cell["traffic"]
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    subject = driver.Cell(cell["config"], traffic, seed)
    setup_s = time.perf_counter() - t_start

    misses = _jit_misses()
    if trace:
        window, summary, spans, before, after = traced_window(subject,
                                                              seconds)
    else:
        window = subject.window(seconds, annotate=False)
    misses = _jit_misses() - misses
    memory_peak = _peak_bytes(device["count"])

    if trace:
        reading = Reading(summary, spans, before, after,
                          {**subject.info(window), "jit_misses": misses},
                          peaks)
        metrics = {}
        for m in reported_per_layer(cell):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = subject.end_to_end(window)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    subject.release()
    checks, attempted, failed = subject.check(window)

    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    dev = {**device, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps()}
    # A number that could not be read (no answer to compare) is not
    # finite; JSON has no such number, so it is printed as null.
    result["checks"] = {
        name: {k: (v if math.isfinite(v) else None) for k, v in c.items()}
        for name, c in checks.items()}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
