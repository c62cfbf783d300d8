"""Unified instrumentation: spans, metrics, and trace export.

The repo's runtime signals used to be scattered — backend cache
counters, solver iteration counts, raw ``print()`` reporting — with no
way to see where a ``plan.run()`` or ``calibrate.fit`` spends its time.
This package is the one substrate they all share:

* :mod:`~repro.obs.trace` — nestable spans (context manager /
  decorator) over a thread-safe ring buffer; near-zero cost while
  disabled, enabled via ``REPRO_TRACE=1`` or :func:`trace.enable`.
  Enabled spans are also ``jax.profiler.TraceAnnotation``s, so a
  profiler session shows them on the device trace's clock.
* :mod:`~repro.obs.metrics` — named counters / gauges / histograms on
  a process-wide registry (supersedes ``core.backend``'s private
  ``_STATS`` dict).
* :mod:`~repro.obs.export` — ndjson event stream + Chrome
  ``trace_event`` JSON for ``chrome://tracing`` / Perfetto; written
  automatically at exit under ``REPRO_TRACE=1``.
* :mod:`~repro.obs.log` — structured stdout reporter (text unchanged,
  events under tracing).
* :mod:`~repro.obs.report` — ``python -m repro.obs.report`` summary
  CLI over an exported ndjson file.

Probes are wired through every hot layer (backend jit cache and XLA
compiles, the Eq. 4-5 solvers and their host-device copies, the desync
engine, Gauss-Newton calibration, pod-plan relaxation, plan compile/run),
so one traced run of any benchmark or example emits a complete
correlated timeline.  Span and metric names follow ``layer.noun.verb``
— the full catalog lives in docs/observability.md.

Instrumentation never changes results: with tracing disabled every
probed function is bit-for-bit its un-instrumented self
(tests/test_obs.py).  A span costs about 0.1 µs disabled and a few µs
enabled on a CPU host, about 1 µs of it the profiler annotation; a
placed-sweep call opens 6 or 7 spans and a desync-simulate call 10,
against calls of 160 ms and 6.7 s on one TPU v5e (PERF.md gives what
tracing costs the benchmark's traced runs there).
"""

from . import export, log, metrics, trace
from .metrics import REGISTRY, counter, gauge, histogram
from .trace import disable, enable, enabled, instant, span, traced

__all__ = [
    "trace", "metrics", "export", "log",
    "span", "traced", "instant", "enabled", "enable", "disable",
    "counter", "gauge", "histogram", "REGISTRY",
]
