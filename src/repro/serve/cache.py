"""The serving plan cache: compiled plans kept hot across requests.

One level above the substrate's jit cache: where
:func:`repro.core.backend.jitted` caches compiled *solver callables*
per shape bucket, this caches compiled *plans* (trace + pack + backend
resolution + the jitted callable underneath) per scenario structure and
bucket, so a long-running server pays ``api.compile`` once per distinct
request shape.  Hit/miss/eviction counters land in the ``repro.obs``
metrics registry under ``serve.plan.*`` with the same ``key=`` label
convention as the jit cache, and the whole scope is queryable as
``backend.cache_stats(scope="plan")`` (registered at import).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from typing import Callable

from ..core import backend as backend_mod
from ..obs import metrics, trace
from . import keys as keys_mod

_HIT_METRIC = "serve.plan.hit"
_MISS_METRIC = "serve.plan.miss"
_EVICT_METRIC = "serve.plan.evict"
_COMPILE_METRIC = "serve.plan.compile_s"

#: Live caches, for the aggregate ``plan_cache_stats`` scope.
_CACHES: "weakref.WeakSet[PlanCache]" = weakref.WeakSet()


class PlanCache:
    """LRU cache of compiled plans, keyed by structure + bucket.

    Thread-safe get-or-build (builds happen outside the lock; a racing
    duplicate keeps the first insertion, mirroring the jit cache's
    policy — both plans compute the same thing).  ``max_entries``
    bounds memory: least-recently-used entries evict first.
    """

    def __init__(self, max_entries: int = 128):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._entries: OrderedDict[tuple, object] = OrderedDict()
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        _CACHES.add(self)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get_or_build(self, key: tuple, build: Callable[[], object], *,
                     label: str = "?") -> object:
        """Return the cached plan for ``key``, building (and caching)
        it on first request.  ``label`` is the metrics key label."""
        with self._lock:
            plan = self._entries.get(key)
            if plan is not None:
                self._entries.move_to_end(key)
                self._hits += 1
        if plan is not None:
            metrics.counter(_HIT_METRIC, key=label).inc()
            return plan
        with trace.span("serve.plan.build", key=label):
            t0 = time.perf_counter()
            plan = build()
            dt = time.perf_counter() - t0
        metrics.counter(_MISS_METRIC, key=label).inc()
        metrics.histogram(_COMPILE_METRIC, key=label).observe(dt)
        with self._lock:
            self._misses += 1
            self._entries.setdefault(key, plan)
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self._evictions += 1
                metrics.counter(_EVICT_METRIC).inc()
            return self._entries[key]

    def warmup(self, scenario, *, verb: str | None = None,
               buckets=(1,), lanes=None) -> int:
        """Precompile the plans that will serve ``scenario``'s structure
        at each batch bucket (each rounded up to a power of two), so the
        first live tick hits.  Returns the number of entries compiled
        (cached buckets count zero).

        A placed prediction's plans serve its whole node: one per row
        bucket and lane bucket (``lanes``, default the scenario's own).
        Each of them that solves on the jax backend is also run once
        per recursion bound the node can reach, so that no live tick
        compiles a solver program either."""
        from .. import api
        if verb is None:
            verb = api.infer_verb(scenario)
        sig = keys_mod.group_key(scenario, verb)
        placed = keys_mod.is_placed(scenario, verb)
        if placed and lanes is None:
            lanes = (keys_mod.placement(scenario)[1],)
        built = 0
        for b in sorted({backend_mod.bucket(b) for b in buckets}):
            for k in (sorted({keys_mod.lane_bucket(k, scenario.topo)
                              for k in lanes})
                      if placed else (None,)):
                key, rows = keys_mod.plan_entry(verb, sig, b, k)
                before = self._misses
                plan = self.get_or_build(
                    key, lambda: keys_mod.compile_group([scenario], verb,
                                                        rows, k),
                    label=keys_mod.key_label(verb, scenario, rows, k))
                built += self._misses - before
                if placed and plan.engine == "jax":
                    for batch in keys_mod.warm_placements(scenario, rows,
                                                          k):
                        plan.run(placement=batch)
            if verb == "simulate":
                break  # bucket-free: one entry serves every batch size
        return built

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        """This cache's counters (process-lifetime hit/miss/eviction
        totals plus current entry count) — the ``/statsz`` payload."""
        with self._lock:
            hits, misses = self._hits, self._misses
            entries, evictions = len(self._entries), self._evictions
        total = hits + misses
        return {
            "hits": hits,
            "misses": misses,
            "entries": entries,
            "max_entries": self.max_entries,
            "evictions": evictions,
            "hit_rate": (hits / total) if total else 0.0,
        }


def plan_cache_stats() -> dict:
    """The ``cache_stats(scope="plan")`` provider: process-wide plan
    cache counters in the jit scope's shape.  Hit/miss totals and the
    per-key ``"buckets"`` breakdown come from the ``serve.plan.*``
    metrics (disjoint from the jit scope's ``backend.jit.*`` counters,
    so ``scope="all"`` never double-counts); ``"entries"`` sums the
    live caches."""
    buckets: dict[str, dict] = {}

    def _bucket(label: str) -> dict:
        return buckets.setdefault(
            label, {"hits": 0, "misses": 0, "compile_s": 0.0})

    hits = misses = evictions = 0
    for row in metrics.snapshot():
        label = row["labels"].get("key")
        if row["name"] == _HIT_METRIC and label is not None:
            _bucket(label)["hits"] = row["value"]
            hits += row["value"]
        elif row["name"] == _MISS_METRIC and label is not None:
            _bucket(label)["misses"] = row["value"]
            misses += row["value"]
        elif row["name"] == _COMPILE_METRIC and label is not None:
            _bucket(label)["compile_s"] = row["sum"]
        elif row["name"] == _EVICT_METRIC:
            evictions += row["value"]
    total = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "entries": sum(len(c) for c in _CACHES),
        "evictions": evictions,
        "hit_rate": (hits / total) if total else 0.0,
        "buckets": buckets,
    }


backend_mod.register_cache_scope("plan", plan_cache_stats)
