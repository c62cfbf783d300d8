"""THE execution substrate: one backend policy for every batched engine.

Before this module existed, the ``numpy`` / ``jax`` / ``auto`` decision —
"is jax importable, is the batch big enough to amortize jit dispatch?" —
was re-implemented independently in ``core/sharing.py``,
``core/desync_batch.py``, ``calibrate/fit.py``, and ``api/engine.py``.
Four forks of the same policy meant four places to thread a new backend
through, four private cutoff constants, and four separate jit caches.
This module is the single implementation:

* **capability probe** — :data:`HAVE_JAX` is defined here (and only
  here); the other modules import it.
* **resolution policy** — :func:`resolve` maps a requested backend
  (``"numpy"`` / ``"jax"`` / ``"auto"``) plus a batch size to the
  backend that will actually run.  ``auto`` picks jax for batches of
  at least :data:`DEFAULT_JAX_CUTOFF` scenarios.
* **jitted-solver cache** — :func:`jitted` is a process-wide registry
  of compiled solver callables keyed by *padded shape bucket*
  (:func:`bucket` rounds sizes up to powers of two), so sweeping over
  nearby batch sizes reuses one XLA executable instead of recompiling
  per shape.  :func:`cache_stats` exposes hit/miss counters — the
  plan-overhead benchmark records the hit rate — and the XLA compiles
  the process made, counted from ``jax.monitoring`` events.
* **numerics and compile cache** — :func:`x64` is the one float64 scope
  every jitted solver runs in, and :func:`enable_compile_cache` the one
  persistent-compilation-cache set-up every entry point calls.

A future backend (pallas kernels, multi-device sharding) registers
here once — a new ``resolve`` target plus its ``jitted`` builders —
instead of being threaded through four modules.
"""

from __future__ import annotations

import os
import pathlib
import threading
from typing import Callable

import numpy as np

from ..obs import metrics

try:  # The jax paths are optional: numpy covers hermetic containers.
    import jax  # noqa: F401  (re-exported capability, used by clients)

    HAVE_JAX = True
except ModuleNotFoundError:  # pragma: no cover - exercised only without jax
    HAVE_JAX = False

#: Backends the substrate can resolve to.  ``"auto"`` is a request, not
#: a backend: :func:`resolve` always returns one of these.
BACKENDS = ("numpy", "jax")

#: Batches at least this large dispatch to the jitted jax solver under
#: ``backend="auto"``: below it, jit dispatch overhead outweighs the
#: vmap win (see BENCH_api.json).
DEFAULT_JAX_CUTOFF = 64


def resolve(backend: str, batch_size: int | None = None, *,
            prefer: str = "jax") -> str:
    """Map a requested backend to the one that will run.

    ``backend``: ``"numpy"``, ``"jax"``, or ``"auto"``.  Explicit
    requests are honored (``"jax"`` raises :class:`RuntimeError` when
    jax is not importable — the caller asked for something the process
    cannot do).  ``"auto"`` resolves by policy:

    * ``prefer="jax"`` (the batched solvers): jax when importable and
      the batch is at least :data:`DEFAULT_JAX_CUTOFF` scenarios (an unknown
      ``batch_size=None`` counts as large);
    * ``prefer="numpy"`` (the desync event engine, whose numpy path is
      the reference implementation): numpy, always — jax runs only on
      explicit request.

    This is the **only** place in the tree that makes this decision.
    """
    if backend == "auto":
        if prefer == "numpy":
            return "numpy"
        if prefer != "jax":
            raise ValueError(f"unknown auto preference {prefer!r}")
        if not HAVE_JAX:
            return "numpy"
        if batch_size is not None and batch_size < DEFAULT_JAX_CUTOFF:
            return "numpy"
        return "jax"
    if backend == "jax":
        if not HAVE_JAX:
            raise RuntimeError("backend='jax' requested but jax is not "
                               "importable")
        return "jax"
    if backend == "numpy":
        return "numpy"
    raise ValueError(f"unknown backend {backend!r}")


def x64():
    """Context in which jax traces and runs in float64: the numerics of
    every jitted solver (sharing, desync, calibration fits).

    It stays a scope around the solver calls and never becomes a
    process-wide flag: Mosaic refuses to compile the Pallas kernels
    when they are traced with x64 on."""
    return jax.enable_x64(True)


def device_info() -> dict:
    """The device jax computes on, as every result names it: its
    ``platform``, ``device_kind`` and the process's device count."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


#: The compile cache's directory when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: fixed inside the checkout, because the path is part of what a
#: later process must find again.
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / \
    ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE`.  Every executable is cached, however
    quickly it compiled, so the small solver buckets are kept too.
    Called by the entry points (``chip_smoke.py``, ``python -m
    repro.serve``, ``benchmarks/run.py``), never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ---------------------------------------------------------------------------
# Process-wide jitted-solver cache, keyed by padded shape buckets
# ---------------------------------------------------------------------------

_JIT_CACHE: dict[tuple, Callable] = {}
_JIT_LOCK = threading.Lock()

# Hit/miss accounting lives on the process-wide metrics registry
# (repro.obs.metrics) — this module's former private ``_STATS`` dict,
# now visible to every exporter.  One labeled instrument per cache key
# gives :func:`cache_stats` its per-bucket breakdown.
_HIT_METRIC = "backend.jit.hit"
_MISS_METRIC = "backend.jit.miss"

# XLA compiles happen at a jitted callable's first call with a new
# shape, not when :func:`jitted` builds it, so they are counted from
# JAX's own monitoring events: each ``backend_compile_duration`` is one
# executable, either compiled (``how="compile"``) or loaded from the
# persistent compilation cache (``how="cache"``, announced by a
# ``cache_hits`` event just before it on the same thread).
_XLA_COMPILES = "backend.xla.compiles"
_XLA_COMPILE_S = "backend.xla.compile_s"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_TLS = threading.local()


def _on_event(event: str, **_kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _COMPILE_TLS.cache_hit = True


def _on_duration(event: str, duration_s: float, **_kwargs) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    how = "cache" if getattr(_COMPILE_TLS, "cache_hit", False) \
        else "compile"
    _COMPILE_TLS.cache_hit = False
    metrics.counter(_XLA_COMPILES, how=how).inc()
    metrics.histogram(_XLA_COMPILE_S, how=how).observe(duration_s)


if HAVE_JAX:  # one pair of listeners per process, at first import
    from jax import monitoring as _monitoring

    _monitoring.register_event_listener(_on_event)
    _monitoring.register_event_duration_secs_listener(_on_duration)


def _key_label(key: tuple) -> str:
    """Cache key -> flat metric label (``sharing.solve_batch/jax/256``)."""
    return "/".join(str(part) for part in key)


def bucket(n: int, *, minimum: int = 1) -> int:
    """Round ``n`` up to the next power of two (at least ``minimum``).

    Shape buckets bound the number of distinct compiled executables to
    O(log B) across a sweep of batch sizes: inputs are padded with
    neutral rows up to the bucket, solved, and sliced back."""
    n = max(int(n), minimum, 1)
    return 1 << (n - 1).bit_length()


def jitted(key: tuple, build: Callable[[], Callable]) -> Callable:
    """The process-wide compiled-solver registry.

    ``key`` identifies one compiled callable — by convention
    ``(module.fn, static-config..., bucketed-shapes...)`` — and
    ``build`` constructs it (typically ``jax.jit`` of a vmapped
    kernel) on the first request.  Subsequent requests with the same
    key return the cached callable, preserving jax's own
    per-callable compilation cache across calls, call sites, and
    plans."""
    label = _key_label(key)
    with _JIT_LOCK:
        fn = _JIT_CACHE.get(key)
    if fn is not None:
        metrics.counter(_HIT_METRIC, key=label).inc()
        return fn
    # Build outside the lock; a racing duplicate build is harmless —
    # setdefault keeps the first insertion and discards the loser, and
    # both callables compute the same thing.
    fn = build()
    metrics.counter(_MISS_METRIC, key=label).inc()
    with _JIT_LOCK:
        _JIT_CACHE.setdefault(key, fn)
        return _JIT_CACHE[key]


#: Additional cache-stats scopes registered by higher layers (e.g. the
#: serving subsystem's plan cache); name → zero-arg provider returning a
#: stats dict.  The substrate cannot import those layers, so they
#: register themselves here at import time.
_SCOPE_PROVIDERS: dict[str, Callable[[], dict]] = {}


def register_cache_scope(name: str,
                         provider: Callable[[], dict]) -> None:
    """Register (or replace) a named cache-stats scope for
    :func:`cache_stats`.  ``provider`` is called lazily per query;
    ``name`` must not shadow the built-in ``"jit"``/``"all"`` scopes."""
    if name in ("jit", "all"):
        raise ValueError(f"scope name {name!r} is reserved")
    _SCOPE_PROVIDERS[name] = provider


def cache_stats(scope: str = "jit") -> dict:
    """Hit/miss counters and entry count of the substrate's caches.

    ``scope="jit"`` (the default) reports the jitted-solver cache, plus
    a per-bucket breakdown: ``"buckets"`` maps each cache-key label to
    its ``{"hits", "misses"}``.  ``"xla"`` gives the XLA executables
    the process compiled and the seconds they took, ``{"compiles",
    "compile_s"}`` (loads from the persistent cache not included).
    ``scope="all"`` reports every known cache once, keyed by scope name
    (``{"jit": ..., "plan": ...}`` with :mod:`repro.serve` imported) —
    the shape ``/statsz`` and ``repro.obs.report`` consume, with no
    double-counting because each scope owns disjoint counters.  Any
    other ``scope`` selects one registered scope by name."""
    if scope == "all":
        out = {"jit": cache_stats("jit")}
        for name, provider in sorted(_SCOPE_PROVIDERS.items()):
            out[name] = provider()
        return out
    if scope != "jit":
        provider = _SCOPE_PROVIDERS.get(scope)
        if provider is None:
            from ..api.registry import unknown_key_error
            raise unknown_key_error(
                "cache scope", scope,
                ["jit", "all", *sorted(_SCOPE_PROVIDERS)])
        return provider()
    buckets: dict[str, dict] = {}
    hits = misses = compiles = 0
    compile_s = 0.0
    for row in metrics.snapshot():
        if row["name"] == _XLA_COMPILES and \
                row["labels"] == {"how": "compile"}:
            compiles = row["value"]
        elif row["name"] == _XLA_COMPILE_S and \
                row["labels"] == {"how": "compile"}:
            compile_s = row["sum"]
        elif row["name"] in (_HIT_METRIC, _MISS_METRIC):
            counts = buckets.setdefault(row["labels"]["key"],
                                        {"hits": 0, "misses": 0})
            if row["name"] == _HIT_METRIC:
                counts["hits"] = row["value"]
                hits += row["value"]
            else:
                counts["misses"] = row["value"]
                misses += row["value"]
    with _JIT_LOCK:
        entries = len(_JIT_CACHE)
    total = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "entries": entries,
        "hit_rate": (hits / total) if total else 0.0,
        "buckets": buckets,
        "xla": {"compiles": compiles, "compile_s": compile_s},
    }


def clear_jit_cache() -> None:
    """Drop every cached callable and reset the **whole** metrics
    registry (not just the jit counters), so tests cannot leak counts
    across cases."""
    with _JIT_LOCK:
        _JIT_CACHE.clear()
    metrics.reset()


def pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    """Pad ``arr`` along axis 0 with zeros up to ``rows`` (no copy when
    already that size).  Zero rows are exactly neutral for every solver
    on the substrate (``n = 0`` groups, ``mask = False`` cells, empty
    programs), so padding never perturbs the real rows."""
    if arr.shape[0] == rows:
        return arr
    if arr.shape[0] > rows:
        raise ValueError(
            f"cannot pad {arr.shape[0]} rows down to {rows}")
    pad = np.zeros((rows - arr.shape[0],) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)
