"""Named counters, gauges, and histograms on one process-wide registry.

Spans (:mod:`repro.obs.trace`) answer *where time went*; metrics answer
*how much of what happened* — jit-cache hits per shape bucket, solver
iterations, deadlocked scenarios — as cheap always-on aggregates that
survive even when tracing is off.

This registry absorbs and supersedes the private ``_STATS`` dict that
``core/backend.py`` used to keep: the backend's hit/miss counters are
now ordinary instruments here, and ``backend.clear_jit_cache()`` resets
the whole registry so tests cannot leak counts across cases.

Naming scheme (see docs/observability.md): dotted lowercase
``layer.noun.verb`` names, with variable dimensions (shape buckets,
backends) as *labels*, never baked into the name::

    from repro.obs import metrics

    metrics.counter("backend.jit.miss", key="sharing.solve_batch").inc()
    metrics.gauge("serve.queue.depth").set(12)
    metrics.histogram("backend.xla.compile_s", how="compile").observe(0.41)

Instruments are get-or-create on every call — handles looked up in hot
paths stay valid, but after :func:`reset` a cached handle is orphaned
(its updates vanish from snapshots), so hot paths should re-look-up
rather than cache across cache-clear boundaries.  Lookups are one dict
access under one lock; measured cost is tens of nanoseconds.
"""

from __future__ import annotations

import math
import threading

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "counter", "gauge", "histogram", "snapshot", "reset",
]


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


class Counter:
    """Monotonically increasing count."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int | float = 1) -> None:
        if n < 0:
            raise ValueError(f"counters only go up; got inc({n})")
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def to_dict(self) -> dict:
        return {"value": self._value}


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = None
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = value

    def add(self, delta: float) -> None:
        with self._lock:
            self._value = (self._value or 0) + delta

    @property
    def value(self):
        return self._value

    def to_dict(self) -> dict:
        return {"value": self._value}


class Histogram:
    """Streaming summary: count / sum / min / max / mean / stddev.

    Keeps moments rather than samples so memory stays O(1) no matter
    how hot the probe is; exporters that need percentiles should use
    span durations from the trace buffer instead.
    """

    __slots__ = ("_count", "_sum", "_sumsq", "_min", "_max", "_lock")

    def __init__(self):
        self._count = 0
        self._sum = 0.0
        self._sumsq = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._count += 1
            self._sum += v
            self._sumsq += v * v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def to_dict(self) -> dict:
        if not self._count:
            return {"count": 0, "sum": 0.0, "min": None, "max": None,
                    "mean": None, "stddev": None}
        mean = self._sum / self._count
        var = max(0.0, self._sumsq / self._count - mean * mean)
        return {"count": self._count, "sum": self._sum, "min": self._min,
                "max": self._max, "mean": mean, "stddev": math.sqrt(var)}


class Registry:
    """Process-wide instrument store keyed by (name, sorted labels)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict = {}

    def _get(self, cls, name: str, labels: dict):
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                inst = cls()
                self._instruments[key] = inst
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r}{dict(key[1]) or ''} already registered "
                    f"as {type(inst).__name__}, requested {cls.__name__}")
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get(Histogram, name, labels)

    def snapshot(self) -> list[dict]:
        """One dict per instrument: name, labels, type, and its stats —
        ndjson-ready rows (sorted for deterministic export)."""
        with self._lock:
            items = sorted(self._instruments.items())
        rows = []
        for (name, labels), inst in items:
            rows.append({"name": name, "labels": dict(labels),
                         "type": type(inst).__name__.lower(),
                         **inst.to_dict()})
        return rows

    def reset(self) -> None:
        """Forget every instrument.  Cached handles become orphans whose
        updates no longer appear in snapshots."""
        with self._lock:
            self._instruments.clear()


REGISTRY = Registry()

# Module-level sugar over the process-wide registry.
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
snapshot = REGISTRY.snapshot
reset = REGISTRY.reset
