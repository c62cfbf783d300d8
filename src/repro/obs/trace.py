"""Nestable spans over a thread-safe ring buffer — the tracing core.

The paper's method is measurement-first: Eq. 4-5 predictions are only as
good as the signals behind them, and the same holds for this repo's own
runtime.  This module records *where time goes* inside a solve /
simulate / calibrate / plan call as a tree of spans:

    from repro.obs import trace

    with trace.span("sharing.solve_arrays", backend="numpy", B=256):
        ...                      # nested spans become children

Design constraints (in priority order):

1. **Near-zero cost when disabled.**  ``span(...)`` checks one module
   global and returns a shared no-op context manager; no timestamps, no
   allocation beyond the kwargs dict at the call site.  Probes in
   per-event hot loops must additionally guard with ``if enabled():``.
2. **Bounded memory.**  Events land in a fixed-capacity ring buffer
   (default ``REPRO_TRACE_CAPACITY`` = 65536); old events are
   overwritten, never grown.  ``dropped()`` reports the overflow count
   so exporters can flag truncation instead of lying by omission.
3. **Correlation without coordination.**  Each event carries a
   monotonic ``perf_counter_ns`` start, duration, thread id, and nest
   depth; exporters rebuild the parent/child tree from (tid, depth,
   time) alone — probes never pass span handles around.
4. **One clock with the device.**  Once JAX is loaded, an enabled span
   also enters a ``jax.profiler.TraceAnnotation`` of its name (not its
   attributes), so a profiler session stamps it on the host plane of
   the same timeline as the device's operations.  This module never
   imports JAX itself.

Enable via ``REPRO_TRACE=1`` in the environment (which also registers
an at-exit export, see :mod:`repro.obs.export`) or programmatically
with :func:`enable` / :func:`disable`.

Events are plain tuples ``(kind, name, t0_ns, dur_ns, tid, depth,
attrs)`` — ``kind`` is ``"span"``, ``"instant"``, or ``"log"``; attrs
is a dict or None.  Use :mod:`repro.obs.export` to turn them into
ndjson or Chrome ``trace_event`` JSON.
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time

__all__ = [
    "enabled", "enable", "disable", "span", "traced", "instant",
    "events", "clear", "dropped", "DEFAULT_CAPACITY",
]

_TRUTHY = frozenset({"1", "true", "yes", "on"})

DEFAULT_CAPACITY = 65536


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() in _TRUTHY


def _env_capacity() -> int:
    raw = os.environ.get("REPRO_TRACE_CAPACITY", "")
    try:
        cap = int(raw)
    except ValueError:
        return DEFAULT_CAPACITY
    return cap if cap > 0 else DEFAULT_CAPACITY


class _RingBuffer:
    """Fixed-capacity event store; appends are O(1) under one lock."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._cap = int(capacity)
        self._buf: list = [None] * self._cap
        self._n = 0  # total events ever appended
        self._lock = threading.Lock()

    def append(self, event) -> None:
        with self._lock:
            self._buf[self._n % self._cap] = event
            self._n += 1

    def snapshot(self) -> list:
        """Events in append order, oldest surviving first."""
        with self._lock:
            n, cap = self._n, self._cap
            if n <= cap:
                return list(self._buf[:n])
            i = n % cap
            return list(self._buf[i:]) + list(self._buf[:i])

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self._cap
            self._n = 0

    @property
    def dropped(self) -> int:
        with self._lock:
            return max(0, self._n - self._cap)

    @property
    def capacity(self) -> int:
        return self._cap


BUFFER = _RingBuffer(_env_capacity())

_TLS = threading.local()

# The one global the disabled fast path reads.  Module-level lookup of a
# bool is the cheapest gate python offers short of deleting the probe.
_ENABLED = _env_flag("REPRO_TRACE")


def enabled() -> bool:
    """True when spans are being recorded.  Hot loops guard expensive
    attribute computation with this before building kwargs."""
    return _ENABLED


def enable(*, capacity: int | None = None, clear_events: bool = False) -> None:
    """Turn tracing on (idempotent).  ``capacity`` resizes (and clears)
    the ring buffer; ``clear_events`` drops already-recorded events."""
    global _ENABLED, BUFFER
    if capacity is not None and capacity != BUFFER.capacity:
        BUFFER = _RingBuffer(capacity)
    elif clear_events:
        BUFFER.clear()
    _ENABLED = True


def disable() -> None:
    """Turn tracing off.  Recorded events stay in the buffer."""
    global _ENABLED
    _ENABLED = False


def events() -> list:
    """Snapshot of recorded event tuples, oldest first."""
    return BUFFER.snapshot()


def clear() -> None:
    """Drop all recorded events (the enabled/disabled state is kept)."""
    BUFFER.clear()


def dropped() -> int:
    """Events lost to ring-buffer overwrite since the last clear."""
    return BUFFER.dropped


class _NoopSpan:
    """Shared do-nothing span: the entire disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "attrs", "_t0", "_depth", "_annotation")

    def __init__(self, name: str, attrs: dict | None):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        # Looked up per span, so JAX loaded after this module still
        # gets the annotations; without JAX there is no profiler.
        profiler = sys.modules.get("jax.profiler")
        if profiler is None:
            self._annotation = None
        else:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        depth = getattr(_TLS, "depth", 0)
        _TLS.depth = depth + 1
        self._depth = depth
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (iteration counts,
        residuals, chosen backend...)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        _TLS.depth = self._depth
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        BUFFER.append(("span", self.name, self._t0, t1 - self._t0,
                       threading.get_ident(), self._depth, self.attrs))
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


def span(name: str, **attrs):
    """Context manager timing a named region.  Attributes are any
    json-serializable kwargs; add more later with ``.set(...)``."""
    if not _ENABLED:
        return _NOOP
    return _Span(name, attrs or None)


def instant(name: str, *, kind: str = "instant", **attrs) -> None:
    """Record a zero-duration event (a log line, a decision point)."""
    if not _ENABLED:
        return
    t = time.perf_counter_ns()
    BUFFER.append((kind, name, t, 0, threading.get_ident(),
                   getattr(_TLS, "depth", 0), attrs or None))


def traced(name: str | None = None):
    """Decorator form of :func:`span`; the span is named after the
    function (``module.qualname``) unless ``name`` is given."""

    def wrap(fn):
        label = name or f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__qualname__}"

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            with _Span(label, None):
                return fn(*args, **kwargs)

        return inner

    return wrap


# When tracing was requested via the environment, arrange for the
# timeline to be written out at interpreter exit so that *any* script —
# benchmark, example, test run — emits its trace with no code changes.
if _ENABLED:  # pragma: no cover - exercised via subprocess in tests
    import atexit

    def _export_at_exit() -> None:
        if BUFFER.snapshot():
            from . import export as _export  # lazy: avoids import cycles

            _export.write_default_artifacts()

    atexit.register(_export_at_exit)
