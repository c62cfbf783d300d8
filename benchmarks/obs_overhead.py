"""Instrumentation overhead gate for the obs layer (spans + metrics).

The instrumentation PR wires trace spans and metric probes through the
hot layers (api plan dispatch, backend jit cache, Eq. 4-5 solver,
desync event loop).  This benchmark proves the two bounds the layer is
held to, on the B = 256 placed-batch solve from
``benchmarks/placement_scaling.py``:

* ``disabled`` — with tracing off (the default), the probes must cost
  < 2 % of the solve.  Measured as a per-call microbenchmark of the
  disabled fast paths (``trace.span``/``trace.enabled``/counter inc),
  multiplied by the number of probe sites one ``plan.run()`` actually
  crosses (counted by running once with tracing on), relative to the
  disabled end-to-end run time.  This estimate is an upper bound: most
  disabled sites are a bare ``enabled()`` check, cheaper than a full
  disabled ``span()`` call.
* ``enabled`` — with tracing on, the end-to-end run must stay within
  10 % of the disabled run ((t_on - t_off) / t_off < 0.10).

``python benchmarks/obs_overhead.py --out BENCH_obs.json`` writes the
committed artifact and exits nonzero if a bound is broken.
``--trace-out FILE`` additionally records one fully-traced demo run
(jit compile + placed-batch predict + desync simulate) and writes the
Chrome ``trace_event`` artifact for chrome://tracing / Perfetto.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from repro import api
from repro.core import backend as backend_mod
from repro.obs import export, metrics, trace

B_SWEEP = 256
DISABLED_BOUND = 0.02   # probe cost with tracing off, fraction of run
ENABLED_BOUND = 0.10    # end-to-end slowdown with tracing on
REPS = 30
SAMPLES = 7

KERNELS = ("DCOPY", "DDOT2", "DAXPY", "Schoenauer")
DOMAINS = ("CLX/s0/d0", "CLX/s1/d0")


def _time_us(fn, reps: int = REPS, samples: int = SAMPLES) -> float:
    """Best-of-``samples`` mean over ``reps`` calls, in µs, GC paused
    (same protocol as benchmarks/placement_scaling.py)."""
    best = float("inf")
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(samples):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            best = min(best, (time.perf_counter() - t0) / reps)
            gc.collect()
    finally:
        if gc_was_enabled:
            gc.enable()
    return best * 1e6


def _placed_scenarios(b: int, shift: int = 0) -> list:
    """B placement candidates for a two-kernel co-run on CLX-2S (the
    benchmarks/placement_scaling.py sweep)."""
    base = api.Scenario.on("CLX").using("CLX-2S")
    out = []
    for i in range(b):
        j = i + shift
        sc = (base
              .placed(KERNELS[j % 3], 1 + j % 8, DOMAINS[j % 2])
              .placed(KERNELS[(j + 1) % 4], 1 + (j * 3) % 8,
                      DOMAINS[(j + 1) % 2]))
        if j % 2:
            sc = sc.placed("DAXPY", 1 + j % 4, DOMAINS[0])
        out.append(sc)
    return out


def _metric_totals() -> dict:
    """Snapshot reduced to one update-count per instrument (counter
    value, histogram count; gauges report 1 write)."""
    out = {}
    for r in metrics.snapshot():
        key = (r["name"], tuple(sorted(r["labels"].items())))
        v = r["count"] if r["type"] == "histogram" else r.get("value")
        out[key] = float(v if v is not None else 1)
    return out


def _sim_scenario():
    MB = 1e6
    return (api.Scenario.on("CLX").ranks(6)
            .with_noise(6e-5, seed=0, ensemble=4)
            .step("Schoenauer", 8 * MB, tag="symgs")
            .step("DDOT2", 2 * MB, tag="ddot2")
            .barrier()
            .step("DAXPY", 6 * MB, tag="daxpy"))


def measure() -> dict:
    plan = api.compile(api.ScenarioBatch.of(_placed_scenarios(B_SWEEP)))
    plan.run()                      # warm caches + jit before timing

    # Per-call cost of the disabled fast paths.
    t_span_off_us = _time_us(lambda: trace.span("bench.noop"),
                             reps=20_000, samples=SAMPLES)
    t_check_us = _time_us(trace.enabled, reps=20_000, samples=SAMPLES)
    t_counter_us = _time_us(metrics.counter("bench.count").inc,
                            reps=20_000, samples=SAMPLES)

    # Probe sites one plan.run() crosses: run once traced and count.
    trace.enable(clear_events=True)
    before = _metric_totals()
    plan.run()
    n_spans = len(trace.events())
    after = _metric_totals()
    n_metric_updates = int(sum(
        after[k] - before.get(k, 0.0) for k in after
        if not k[0].startswith("bench.")))
    trace.disable()
    trace.clear()

    # End-to-end: tracing off vs on (large buffer so nothing reallocs).
    t_off_us = _time_us(plan.run)
    trace.enable(capacity=1 << 18, clear_events=True)
    t_on_us = _time_us(plan.run)
    trace.disable()
    trace.clear()
    metrics.reset()

    probe_cost_us = (n_spans * max(t_span_off_us, t_check_us)
                     + n_metric_updates * t_counter_us)
    disabled_frac = probe_cost_us / t_off_us
    enabled_frac = max(0.0, (t_on_us - t_off_us) / t_off_us)

    return {
        "B": B_SWEEP,
        "backend": plan.engine,
        "span_call_disabled_ns": round(t_span_off_us * 1e3, 2),
        "enabled_check_ns": round(t_check_us * 1e3, 2),
        "counter_inc_ns": round(t_counter_us * 1e3, 2),
        "spans_per_run": n_spans,
        "metric_updates_per_run": n_metric_updates,
        "run_disabled_us": round(t_off_us, 1),
        "run_enabled_us": round(t_on_us, 1),
        "disabled_overhead_frac": round(disabled_frac, 5),
        "enabled_overhead_frac": round(enabled_frac, 4),
    }


def write_demo_trace(path: str) -> dict:
    """One fully-traced run touching every layer: jit compile (backend),
    placed-batch predict (api -> sharing), desync simulate (desync).
    Writes the Chrome trace_event artifact and returns span-name counts."""
    backend_mod.clear_jit_cache()    # force first-call XLA compiles
    trace.enable(capacity=1 << 18, clear_events=True)
    try:
        plan = api.compile(api.ScenarioBatch.of(_placed_scenarios(64)))
        plan.run()
        sim = api.compile(_sim_scenario(), verb="simulate")
        sim.run(t_max=60.0)
        export.write_chrome_trace(path)
        names: dict[str, int] = {}
        for ev in trace.events():
            names[ev[1]] = names.get(ev[1], 0) + 1
    finally:
        trace.disable()
        trace.clear()
        metrics.reset()
    return names


def check(r: dict) -> bool:
    return (r["disabled_overhead_frac"] < DISABLED_BOUND
            and r["enabled_overhead_frac"] < ENABLED_BOUND)


def rows():
    r = measure()
    return [
        (f"obs/B={r['B']}/run_disabled", r["run_disabled_us"],
         f"probe_sites={r['spans_per_run']}"),
        (f"obs/B={r['B']}/run_enabled", r["run_enabled_us"],
         f"enabled_frac={r['enabled_overhead_frac']}"),
        ("obs/span_call_disabled", r["span_call_disabled_ns"] / 1e3,
         f"counter_inc={r['counter_inc_ns']}ns"),
        ("obs/check/bounds", 0.0,
         f"ok={check(r)};disabled<{DISABLED_BOUND};"
         f"enabled<{ENABLED_BOUND}"),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="JSON artifact path")
    ap.add_argument("--trace-out", default=None,
                    help="also write a demo Chrome trace to this path")
    args = ap.parse_args(argv)
    r = measure()
    ok = check(r)
    if args.trace_out:
        names = write_demo_trace(args.trace_out)
        layers = {n.split(".", 1)[0] for n in names}
        r["demo_trace"] = {"path": args.trace_out,
                           "span_names": dict(sorted(names.items())),
                           "layers": sorted(layers)}
        print(f"wrote {args.trace_out}  "
              f"(layers: {', '.join(sorted(layers))})")
    report = {
        "benchmark": "obs_overhead",
        "jax": backend_mod.HAVE_JAX,
        "bound_disabled_frac": DISABLED_BOUND,
        "bound_enabled_frac": ENABLED_BOUND,
        "ok": ok,
        "results": r,
    }
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.out}  (ok={ok})")
    print(f"B={r['B']}: disabled run {r['run_disabled_us']:.0f}us "
          f"({r['spans_per_run']} probe sites, est overhead "
          f"{r['disabled_overhead_frac']:.3%})  enabled run "
          f"{r['run_enabled_us']:.0f}us "
          f"(+{r['enabled_overhead_frac']:.1%})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
