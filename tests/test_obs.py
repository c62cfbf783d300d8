"""The repro.obs instrumentation layer: spans, metrics, exporters —
and the two contracts the rest of the stack holds it to:

* **identity** — with tracing disabled (the default), every probed
  function returns bit-for-bit the same arrays/records as with tracing
  enabled: probes observe, they never steer;
* **overhead** — the disabled fast path is nanoseconds per probe site,
  and never enters the profiler's annotations.
"""

from __future__ import annotations

import importlib.util
import io
import json
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.calibrate import ScalingTrace, fit_scaling, forward_bandwidth
from repro.core import backend as backend_mod
from repro.core import sharing
from repro.core.hlo import RooflineTerms
from repro.obs import export, metrics, report, trace
from repro.obs import log as obs_log
from repro.runtime.overlap_schedule import (StopReason,
                                            gradient_pod_plan,
                                            pod_step_coefficients,
                                            relax_pod_plan)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off, stores empty, and
    the ring buffer back at its default capacity."""
    def pristine():
        trace.enable(capacity=trace.DEFAULT_CAPACITY, clear_events=True)
        trace.disable()
        trace.clear()
        metrics.reset()

    pristine()
    yield
    pristine()


# ---------------------------------------------------------------------------
# trace: spans, nesting, the disabled no-op path, the ring buffer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_attrs():
    trace.enable(clear_events=True)
    with trace.span("outer", who="t") as sp:
        with trace.span("inner"):
            pass
        sp.set(extra=3)
    evs = trace.events()
    assert [(e[0], e[1]) for e in evs] == [("span", "inner"),
                                           ("span", "outer")]
    inner, outer = evs
    assert inner[5] == 1 and outer[5] == 0          # depth
    assert inner[4] == outer[4]                     # same thread id
    assert outer[6] == {"who": "t", "extra": 3}
    assert outer[3] >= inner[3] >= 0                # durations nest
    assert outer[2] <= inner[2]                     # outer opened first


def test_disabled_span_is_shared_noop():
    assert not trace.enabled()
    a = trace.span("x", k=1)
    b = trace.span("y")
    assert a is b                      # one shared no-op object
    with a as sp:
        sp.set(anything=1)             # must be accepted and dropped
    trace.instant("z", k=2)
    trace.enable(clear_events=True)
    assert trace.events() == []        # nothing was recorded while off


def test_span_records_exception():
    trace.enable(clear_events=True)
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("no")
    (ev,) = trace.events()
    assert ev[1] == "boom" and ev[6]["error"] == "ValueError"


def test_ring_buffer_wraps_oldest_first():
    trace.enable(capacity=4, clear_events=True)
    for i in range(10):
        trace.instant("tick", i=i)
    evs = trace.events()
    assert [e[6]["i"] for e in evs] == [6, 7, 8, 9]  # newest 4, in order
    assert trace.dropped() == 6
    trace.clear()
    assert trace.events() == [] and trace.dropped() == 0


def test_traced_decorator_labels_by_qualname():
    @trace.traced()
    def helper():
        return 41 + 1

    trace.enable(clear_events=True)
    assert helper() == 42
    (ev,) = trace.events()
    assert ev[1].endswith("helper") and "." in ev[1]


# ---------------------------------------------------------------------------
# metrics: counters / gauges / histograms and the registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_roundtrip():
    metrics.counter("c", k="a").inc()
    metrics.counter("c", k="a").inc(2)
    metrics.counter("c", k="b").inc()
    metrics.gauge("g").set(1.5)
    h = metrics.histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in metrics.snapshot()}
    assert snap[("c", (("k", "a"),))]["value"] == 3
    assert snap[("c", (("k", "b"),))]["value"] == 1
    assert snap[("g", ())]["value"] == 1.5
    hrow = snap[("h", ())]
    assert hrow["count"] == 3 and hrow["sum"] == 6.0
    assert hrow["min"] == 1.0 and hrow["max"] == 3.0
    assert hrow["mean"] == pytest.approx(2.0)


def test_metrics_validation_and_reset():
    with pytest.raises(ValueError):
        metrics.counter("c2").inc(-1)
    metrics.counter("shared")
    with pytest.raises(TypeError):
        metrics.histogram("shared")    # same name, different type
    metrics.reset()
    assert metrics.snapshot() == []


# ---------------------------------------------------------------------------
# export: ndjson + Chrome trace_event
# ---------------------------------------------------------------------------


def _tiny_trace():
    trace.enable(clear_events=True)
    with trace.span("a.b.outer", k=1):
        with trace.span("a.b.inner"):
            pass
    trace.instant("a.mark", n=2)
    metrics.counter("a.count").inc(5)


def test_ndjson_export_rows():
    _tiny_trace()
    buf = io.StringIO()
    export.write_ndjson(buf)
    rows = [json.loads(line) for line in buf.getvalue().splitlines()]
    kinds = [r["kind"] for r in rows]
    assert kinds.count("span") == 2 and kinds.count("instant") == 1
    assert any(r["kind"] == "metric" and r["name"] == "a.count"
               and r["value"] == 5 for r in rows)
    spans = [r for r in rows if r["kind"] == "span"]
    assert all(r["dur_us"] >= 0 and r["ts_us"] >= 0 for r in spans)


def test_ndjson_reports_drops():
    trace.enable(capacity=2, clear_events=True)
    for i in range(5):
        trace.instant("t", i=i)
    buf = io.StringIO()
    export.write_ndjson(buf, include_metrics=False)
    first = json.loads(buf.getvalue().splitlines()[0])
    assert first["kind"] == "meta" and first["name"] == "trace.dropped"
    assert first["attrs"]["dropped"] == 3


def test_chrome_trace_structure(tmp_path):
    _tiny_trace()
    doc = export.chrome_trace()
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert "M" in phases                       # process/thread metadata
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a.b.outer", "a.b.inner"}
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert [e["name"] for e in doc["traceEvents"] if e["ph"] == "i"] \
        == ["a.mark"]
    out = tmp_path / "t.trace.json"
    export.write_chrome_trace(out)
    assert json.loads(out.read_text())["traceEvents"]


def test_report_cli_and_summary(tmp_path, capsys):
    _tiny_trace()
    path = tmp_path / "run.ndjson"
    with open(path, "w") as fh:
        export.write_ndjson(fh)
    summary = report.summarize([json.loads(s)
                                for s in path.read_text().splitlines()])
    names = {s["name"]: s for s in summary["spans"]}
    assert names["a.b.outer"]["count"] == 1
    assert names["a.b.outer"]["total_us"] >= names["a.b.inner"]["total_us"]
    assert report.main([str(path)]) == 0
    assert "a.b.outer" in capsys.readouterr().out
    assert report.main([str(tmp_path / "missing.ndjson")]) == 2


def test_log_emit_stdout_is_plain_print(capsys):
    obs_log.emit("hello world", event="x.y", n=1)
    assert capsys.readouterr().out == "hello world\n"
    trace.enable(clear_events=True)
    obs_log.emit("again", event="x.y", n=2)
    assert capsys.readouterr().out == "again\n"
    (ev,) = trace.events()
    assert ev[0] == "log" and ev[6] == {"text": "again", "n": 2}


# ---------------------------------------------------------------------------
# backend cache stats (satellite: per-bucket breakdown + reset)
# ---------------------------------------------------------------------------


def test_backend_cache_stats_buckets_and_reset():
    backend_mod.clear_jit_cache()
    key = ("test_obs.fn", 7)
    backend_mod.jitted(key, lambda: (lambda x: x + 1))
    backend_mod.jitted(key, lambda: (lambda x: x + 1))
    stats = backend_mod.cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert stats["entries"] == 1 and stats["hit_rate"] == 0.5
    bucket = stats["buckets"]["test_obs.fn/7"]
    assert bucket == {"hits": 1, "misses": 1}
    # Building the callable compiles nothing; its first call does.
    assert stats["xla"] == {"compiles": 0, "compile_s": 0.0}
    f = backend_mod.jitted(("test_obs.jit", 7), lambda: jax.jit(
        lambda x: x * 7 + 1))
    f(np.arange(7.0))
    xla = backend_mod.cache_stats()["xla"]
    assert xla["compiles"] >= 1 and xla["compile_s"] > 0.0
    backend_mod.clear_jit_cache()       # also resets the registry
    stats = backend_mod.cache_stats()
    assert stats == {"hits": 0, "misses": 0, "entries": 0,
                     "hit_rate": 0.0, "buckets": {},
                     "xla": {"compiles": 0, "compile_s": 0.0}}


def _xla_compiles() -> int:
    return sum(row["value"] for row in metrics.snapshot()
               if row["name"] == "backend.xla.compiles")


def _sweep_arrays(B, G):
    rng = np.random.default_rng(B * 100 + G)
    return (rng.integers(1, 5, (B, G)).astype(np.float64),
            rng.uniform(0.1, 1.0, (B, G)), rng.uniform(40.0, 120.0, (B, G)))


def test_xla_compile_counter_counts_new_buckets_only():
    backend_mod.clear_jit_cache()
    # First use of the solver path in this process: conversions compile.
    sharing.solve_arrays(*_sweep_arrays(3, 5), backend="jax",
                         utilization="queue")
    before = _xla_compiles()
    sharing.solve_arrays(*_sweep_arrays(700, 5), backend="jax",
                         utilization="queue")      # new 1024-row bucket
    assert _xla_compiles() == before + 1
    sharing.solve_arrays(*_sweep_arrays(900, 5), backend="jax",
                         utilization="queue")      # same bucket again
    assert _xla_compiles() == before + 1


def _program_name(jitted_fn, *shapes) -> str:
    """The name XLA gives the program, as the profiler's module line
    shows it."""
    with backend_mod.x64():
        text = jitted_fn.lower(*shapes).compile().as_text()
    return re.match(r"HloModule (\S+?),", text).group(1)


def _bench_pattern(reader: str, monkeypatch) -> str:
    root = pathlib.Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    path = root / "bench" / "metrics" / f"{reader}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{reader}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.PROGRAM


def test_device_programs_keep_the_names_benchmark_readers_match(
        monkeypatch):
    from repro.core import desync_batch

    f64, i32 = jnp.float64, jnp.int32
    shape = jax.ShapeDtypeStruct
    solver = sharing._build_jax_solver("recursion", 4, (8, 3))
    name = _program_name(solver, *([shape((8 * 3,), f64)] * 3),
                         shape((), f64))
    assert re.search(_bench_pattern("solve_device_ms.sweep", monkeypatch),
                     name), name
    B, R, L, K, D = 2, 4, 8, 2, 1
    runner = desync_batch._build_jax_runner(B, R, L, K, D)
    name = _program_name(
        runner, shape((B, R, L), i32), shape((B, R, L), f64),
        shape((B, R, L), i32), shape((B, R), i32), shape((R,), i32),
        shape((K,), f64), shape((K,), f64), shape((), f64),
        shape((), jnp.int64))
    assert re.search(
        _bench_pattern("desync_us_per_step.simulate", monkeypatch),
        name), name


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------


def _sim_scenario():
    MB = 1e6
    return (api.Scenario.on("CLX").ranks(4)
            .with_noise(6e-5, seed=0, ensemble=2)
            .step("DCOPY", 2 * MB).barrier().step("DAXPY", MB)
            .options(backend="jax"))


def _host_spans(directory) -> dict:
    """``name -> [(start_ns, end_ns), ...]`` of the events on the host
    planes of the profile written under ``directory``."""
    (path,) = pathlib.Path(directory).rglob("*.xplane.pb")
    spans: dict = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
    return spans


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] <= inner[1] <= outer[1]


def test_spans_land_on_the_profiler_host_plane_nested(tmp_path):
    sharing.solve_arrays(*_sweep_arrays(128, 2), backend="jax")  # compile
    api.simulate(_sim_scenario(), t_max=60.0)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace.enable(clear_events=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        sharing.solve_arrays(*_sweep_arrays(128, 2), backend="jax")
        api.simulate(_sim_scenario(), t_max=60.0)
    finally:
        jax.profiler.stop_trace()
        trace.disable()
    spans = _host_spans(tmp_path)
    (solve,) = spans["sharing.solve_arrays"]
    parts = [spans[f"sharing.jax.{p}"][0] for p in ("put", "wait", "get")]
    assert all(_inside(p, solve) for p in parts)
    assert parts[0][1] <= parts[1][0] and parts[1][1] <= parts[2][0]
    (run,) = spans["desync.run"]
    (records,) = spans["desync.records"]
    assert _inside(records, run)
    assert all(_inside(spans[f"desync.jax.{p}"][0], run)
               for p in ("put", "wait", "get"))
    (compile_,) = spans["api.compile"]
    assert _inside(spans["api.compile.expand"][0], compile_)


def test_disabled_spans_never_enter_the_profiler(monkeypatch):
    args = _sweep_arrays(128, 2)
    want = sharing.solve_arrays(*args, backend="jax")

    def refuse(name, **kwargs):
        raise AssertionError(f"TraceAnnotation({name!r}) entered")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not trace.enabled()
    got = sharing.solve_arrays(*args, backend="jax")
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    trace.enable(clear_events=True)
    with pytest.raises(AssertionError, match="TraceAnnotation"):
        sharing.solve_arrays(*args, backend="jax")


# ---------------------------------------------------------------------------
# identity: tracing must never change a probed function's output
# ---------------------------------------------------------------------------


def _on_off(fn):
    """Run ``fn`` with tracing off then on; return both results."""
    trace.disable()
    off = fn()
    trace.enable(clear_events=True)
    try:
        on = fn()
    finally:
        trace.disable()
        trace.clear()
    return off, on


def test_identity_solve_arrays():
    n = np.array([[2.0, 4.0], [1.0, 3.0]])
    f = np.array([[0.4, 0.7], [0.9, 0.2]])
    bs = np.array([[82.0, 95.0], [120.0, 105.0]])
    for mode in sharing.UTILIZATION_MODES:
        off, on = _on_off(lambda: sharing.solve_arrays(
            n, f, bs, utilization=mode, backend="numpy"))
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)


def test_identity_placed_batch_predict():
    base = api.Scenario.on("CLX").using("CLX-2S")
    scens = [base.placed("DCOPY", 1 + i % 4, "CLX/s0/d0")
                 .placed("DDOT2", 1 + (i * 3) % 4, "CLX/s1/d0")
             for i in range(8)]
    batch = api.ScenarioBatch.of(scens)
    off, on = _on_off(lambda: api.predict(batch).bw_group)
    np.testing.assert_array_equal(off, on)


def test_identity_simulate():
    MB = 1e6
    sc = (api.Scenario.on("CLX").ranks(4)
          .with_noise(6e-5, seed=0, ensemble=2)
          .step("DCOPY", 2 * MB).barrier().step("DAXPY", MB))

    def run():
        res = api.simulate(sc, t_max=60.0)
        return res.t_end.copy(), [res.records(b)
                                  for b in range(res.n_scenarios)]

    (t_off, rec_off), (t_on, rec_on) = _on_off(run)
    np.testing.assert_array_equal(t_off, t_on)
    assert rec_off == rec_on


def test_identity_fit_scaling():
    cores = tuple(range(1, 13))
    bw = forward_bandwidth(np.array(cores), 0.3, 80.0,
                           utilization="queue")
    tr = ScalingTrace(kernel="syn", arch="X", cores=cores,
                      bandwidth=tuple(float(b) for b in bw))
    off, on = _on_off(lambda: fit_scaling([tr], backend="numpy"))
    np.testing.assert_array_equal(off.f, on.f)
    np.testing.assert_array_equal(off.bs, on.bs)


def _coeffs():
    terms = RooflineTerms(name="step", t_compute=0.0, t_memory=0.0,
                          t_collective=0.0, flops=2.0e12,
                          hbm_bytes=8.0e9, wire_bytes=1.0e9,
                          model_flops=2.0e12)
    return pod_step_coefficients(terms), terms


def test_identity_relax_pod_plan():
    coeffs, _ = _coeffs()
    lb, ub = [0.7] * 4, [1.3] * 4
    off, on = _on_off(lambda: relax_pod_plan(coeffs, total=4.0,
                                             lb=lb, ub=ub))
    np.testing.assert_array_equal(off.x, on.x)
    assert off.t == on.t and off.n_iters == on.n_iters
    assert off.trajectory == on.trajectory
    assert off.stop_reason == on.stop_reason


# ---------------------------------------------------------------------------
# relax_pod_plan trajectory + stop reason (satellite regression test)
# ---------------------------------------------------------------------------


def test_relax_trajectory_and_stop_reason():
    coeffs, _ = _coeffs()
    res = relax_pod_plan(coeffs, total=4.0, lb=[0.7] * 4, ub=[1.3] * 4,
                         iters=300)
    # Historical 3-tuple unpacking still works.
    x, t, n = res
    assert (x == res.x).all() and t == res.t and n == res.n_iters
    # Trajectory: initial projection first, one entry per iterate after.
    assert len(res.trajectory) == res.n_iters + 1
    # Best-by-exact-makespan (improvements below the 1e-12 relative
    # stall threshold intentionally don't move the incumbent).
    assert res.t == pytest.approx(min(res.trajectory), rel=1e-11)
    assert res.stop_reason == StopReason.CONVERGED
    assert res.stop_reason == "converged"   # str-enum compares plainly
    assert str(res.stop_reason) == "converged"


def test_relax_stop_reason_iters_exhausted():
    coeffs, _ = _coeffs()
    res = relax_pod_plan(coeffs, total=4.0, lb=[0.7] * 4, ub=[1.3] * 4,
                         iters=2)
    assert res.n_iters == 2
    assert res.stop_reason == StopReason.ITERS_EXHAUSTED
    assert len(res.trajectory) == 3


def test_relax_stop_reason_point_polytope():
    coeffs, _ = _coeffs()
    res = relax_pod_plan(coeffs, total=4.0, lb=[1.0] * 4, ub=[1.0] * 4)
    assert res.stop_reason == StopReason.POINT_POLYTOPE
    assert res.n_iters == 0 and len(res.trajectory) == 1
    np.testing.assert_allclose(res.x, [1.0] * 4)


def test_gradient_plan_result_carries_relaxation():
    _, terms = _coeffs()
    cands = [(1.0, 1.0, 1.0, 1.0), (1.3, 0.9, 0.9, 0.9),
             (0.7, 1.1, 1.1, 1.1)]
    res = gradient_pod_plan(terms, cands)
    assert isinstance(res.stop_reason, StopReason)
    assert len(res.trajectory) == res.n_iters + 1
    assert res.t_relaxed == pytest.approx(min(res.trajectory), rel=1e-11)


# ---------------------------------------------------------------------------
# overhead: the disabled fast path must stay in nanosecond territory
# ---------------------------------------------------------------------------


def test_disabled_probe_calls_are_cheap():
    assert not trace.enabled()
    reps = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            trace.span("bench.noop")
        best = min(best, (time.perf_counter() - t0) / reps)
    # ~0.1 µs in practice; 5 µs is the generous CI-noise ceiling, still
    # far below the milliseconds of any probed call.
    assert best < 5e-6, f"disabled span() costs {best * 1e9:.0f} ns"
