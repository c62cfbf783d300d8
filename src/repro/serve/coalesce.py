"""The request coalescer: many concurrent requests, one batched solve.

PR 5's measurement (``BENCH_plan.json``) is the motivation: a compiled
plan re-run is ~40× a per-call ``predict`` at B = 256, so the winning
move under concurrency is to *not* solve requests one by one.  The
coalescer holds requests for one tick (default 1 ms), groups the tick's
arrivals by structure key, and runs each group as a single batched
``plan.run()`` on a cached plan, fanning results back to the awaiting
futures.  Under light load a request pays one tick of latency; under
heavy load the batch packs to ``max_batch`` and throughput scales with
the batched-solver win instead of per-request overhead.

Admission control and backpressure: the queue is bounded
(``max_queue`` → :class:`QueueFull`, HTTP 429), each request carries a
deadline (expired requests fail with :class:`DeadlineExceeded`, HTTP
504, *before* wasting a solve), and ``close(drain=True)`` stops intake
but runs every queued request to completion — no future is ever left
unresolved.

Everything here is socket-free: tests drive ``submit``/``close``
directly under ``asyncio.run``.  Solves run inline on the event loop
(a deliberate single-process design — the host work around a solve is
GIL-bound, and a worker thread for device ticks measured slower on the
chip; see docs/serving.md for the scaling discussion).
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time
import weakref
from collections import deque
from typing import Iterable

from .. import api
from ..obs import metrics, trace
from .cache import PlanCache
from . import keys as keys_mod


class ServeError(Exception):
    """Base class for request-level serving failures; ``status`` is the
    HTTP status the transport maps the error to."""
    status = 500


class BadRequest(ServeError):
    status = 400


class QueueFull(ServeError):
    status = 429


class Draining(ServeError):
    status = 503


class DeadlineExceeded(ServeError):
    status = 504


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs for one coalescer (and the server wrapping it)."""

    #: Coalescing window: how long the loop sleeps after waking so
    #: concurrent arrivals land in one batch.  0 disables the wait.
    tick_s: float = 1e-3
    #: Most requests drained per tick; the rest wait for the next one.
    max_batch: int = 256
    #: Admission bound: submits beyond this many queued requests are
    #: rejected with :class:`QueueFull` (the backpressure signal).
    max_queue: int = 1024
    #: Deadline applied to requests that do not carry their own
    #: (seconds; ``None`` = no deadline).
    default_deadline_s: float | None = 30.0
    #: LRU capacity of the plan cache the server builds when the caller
    #: does not pass one.
    cache_entries: int = 128


def _engine_error(e: Exception) -> ServeError:
    """An engine failure, reported to its requests; serving goes on."""
    return ServeError(f"{type(e).__name__}: {e}")


#: Requests a tick hands their results at a time (:meth:`Coalescer._release`).
#: A placed result costs about 0.1 ms to build, so a piece is tens of ms.
_PIECE = 1024


@dataclasses.dataclass
class _Pending:
    scenario: "api.Scenario"
    verb: str
    future: asyncio.Future
    deadline: float | None   # absolute time.monotonic(), or None
    t_submit: float
    seq: int


class Coalescer:
    """Tick-based batching front for the prediction/simulation engines.

    Usage (socket-free)::

        c = Coalescer(ServeConfig(tick_s=1e-3))
        pred = await c.submit(scenario)            # one Prediction back
        await c.close(drain=True)

    ``submit`` enqueues and awaits; the background tick task drains the
    queue, groups by :func:`repro.api.structure_key`, and solves each
    group through the plan cache.  The task starts lazily on first
    submit (or explicitly via :meth:`start`).
    """

    def __init__(self, config: ServeConfig | None = None, *,
                 cache: PlanCache | None = None):
        self.config = config or ServeConfig()
        # "is None", not "or": an empty PlanCache is len() == 0 == falsy.
        self.cache = (cache if cache is not None
                      else PlanCache(self.config.cache_entries))
        self._pending: deque[_Pending] = deque()
        self._wake = asyncio.Event()
        self._closing = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False
        self._seq = 0
        self._ticks = 0
        self.counts = {"accepted": 0, "completed": 0, "rejected": 0,
                       "expired": 0, "errors": 0, "drained": 0}
        # Hot-path instrument handles, resolved once: the registry
        # lookup (name + label canonicalization under a lock) costs
        # more than the update itself at coalescing rates.
        self._m_accepted = {
            v: metrics.counter("serve.accepted", verb=v)
            for v in ("predict", "simulate")}
        self._m_latency = {
            v: metrics.histogram("serve.latency_s", verb=v)
            for v in ("predict", "simulate")}
        self._m_batch = metrics.histogram("serve.tick.batch")
        self._m_queue = metrics.histogram("serve.queue_s")
        self._m_depth = metrics.gauge("serve.queue.depth")
        # Structure-key memo for resubmitted scenario *objects* (the
        # embedded-client pattern: a calibration loop holds scenarios
        # and submits them every round).  Keyed by id() with a weakref
        # identity check, so a recycled id never returns a stale key.
        self._key_memo: dict = {}

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Start the tick task (idempotent; ``submit`` also starts it)."""
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="repro.serve.coalescer")

    async def close(self, *, drain: bool = True) -> None:
        """Stop intake and shut the tick task down.

        ``drain=True`` (graceful): every queued request still runs and
        resolves its future.  ``drain=False``: queued requests fail
        immediately with :class:`Draining`.  Either way no future is
        left unresolved."""
        self._closed = True
        self._closing.set()
        if not drain:
            while self._pending:
                p = self._pending.popleft()
                if not p.future.done():
                    p.future.set_exception(
                        Draining("server shut down before this request "
                                 "was solved"))
                self.counts["drained"] += 1
        self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        self._gauge()

    async def __aenter__(self) -> "Coalescer":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close(drain=True)

    # -- intake -------------------------------------------------------------

    async def submit(self, scenario, *, verb: str | None = None,
                     deadline_s: float | None = None):
        """Enqueue one request and await its result.

        Raises :class:`Draining` after :meth:`close`, :class:`QueueFull`
        at the admission bound, :class:`DeadlineExceeded` when the
        request's deadline passes before it is solved, and re-raises
        whatever the solve itself raised (as :class:`BadRequest` for
        scenario validation errors)."""
        if self._closed:
            metrics.counter("serve.rejected", reason="draining").inc()
            self.counts["rejected"] += 1
            raise Draining("server is draining; not accepting requests")
        if len(self._pending) >= self.config.max_queue:
            metrics.counter("serve.rejected", reason="queue_full").inc()
            self.counts["rejected"] += 1
            raise QueueFull(
                f"queue full ({self.config.max_queue} requests pending)")
        if verb is None:
            verb = api.infer_verb(scenario)
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        now = time.monotonic()
        self._seq += 1
        with trace.span("serve.accept", verb=verb, seq=self._seq):
            p = _Pending(
                scenario=scenario, verb=verb,
                future=asyncio.get_running_loop().create_future(),
                deadline=(now + deadline_s
                          if deadline_s is not None else None),
                t_submit=now, seq=self._seq)
            self._pending.append(p)
            self._m_accepted[verb].inc()
            self.counts["accepted"] += 1
            self.start()
            self._wake.set()
        return await p.future

    # -- the tick loop ------------------------------------------------------

    async def _run(self) -> None:
        while True:
            if not self._pending:
                if self._closed:
                    return
                self._wake.clear()
                await self._wake.wait()
                continue
            if self.config.tick_s > 0 and not self._closed:
                # The coalescing window: let concurrent arrivals land.
                # Waiting on the closing event (instead of a bare sleep)
                # lets close() cut the window short, so drains never
                # stall a full tick.
                try:
                    await asyncio.wait_for(self._closing.wait(),
                                           timeout=self.config.tick_s)
                except asyncio.TimeoutError:
                    pass
            batch = []
            while self._pending and len(batch) < self.config.max_batch:
                batch.append(self._pending.popleft())
            self._gauge()
            self._ticks += 1
            await self._process(batch)
            await asyncio.sleep(0)  # yield between solves under load

    async def _process(self, batch: "list[_Pending]") -> None:
        now = time.monotonic()
        groups: dict[tuple, list[_Pending]] = {}
        for p in batch:
            if p.future.done():     # caller gave up (cancel/timeout)
                continue
            if p.deadline is not None and now > p.deadline:
                metrics.counter("serve.expired", verb=p.verb).inc()
                self.counts["expired"] += 1
                p.future.set_exception(DeadlineExceeded(
                    f"deadline passed before solve (queued "
                    f"{now - p.t_submit:.3f}s)"))
                continue
            groups.setdefault((p.verb,) + (self._group_key(
                p.scenario, p.verb),), []).append(p)
        if not groups:
            return
        solved = []
        with trace.span("serve.coalesce", tick=self._ticks,
                        n=sum(len(g) for g in groups.values()),
                        groups=len(groups)):
            for (verb, sig), plist in groups.items():
                self._m_batch.observe(len(plist))
                try:
                    results = self._timed_solve(verb, sig, plist)
                except ServeError as e:
                    self._fail(plist, e)
                except (ValueError, TypeError, KeyError) as e:
                    self._fail(plist, BadRequest(str(e)))
                except Exception as e:  # engine failure: report, keep serving
                    self._fail(plist, _engine_error(e))
                else:
                    solved.append((verb, plist, iter(results)))
        for verb, plist, results in solved:
            await self._release(verb, plist, results)

    async def _release(self, verb: str, plist: "list[_Pending]",
                       results) -> None:
        """Hand a solved group's requests their results, ``_PIECE`` at a
        time, yielding to the loop between pieces: the streams of a
        large tick write its first answers while its later results are
        still being built."""
        latency = self._m_latency[verb]
        for at in range(0, len(plist), _PIECE):
            piece = plist[at:at + _PIECE]
            try:
                got = list(itertools.islice(results, len(piece)))
            except Exception as e:  # a result failed to build
                self._fail(plist[at:], _engine_error(e))
                return
            done = time.monotonic()
            for p, result in zip(piece, got):
                if isinstance(result, ServeError):
                    self._fail([p], result)   # it failed alone
                    continue
                if not p.future.done():
                    p.future.set_result(result)
                latency.observe(done - p.t_submit)
                self.counts["completed"] += 1
            await asyncio.sleep(0)

    def _timed_solve(self, verb: str, sig: tuple,
                     plist: "list[_Pending]") -> Iterable:
        """One group's solve under its ``serve.solve`` span (labelled
        with the backend its plan ran on), after recording how long
        each request queued for it."""
        start = time.monotonic()
        queued = self._m_queue
        for p in plist:
            queued.observe(start - p.t_submit)
        with trace.span("serve.solve", n=len(plist)) as sp:
            results, engine = self._solve(verb, sig, plist)
            sp.set(backend=engine)
            return results

    def _group_key(self, sc, verb: str) -> tuple:
        memo = self._key_memo
        mk = (id(sc), verb)
        hit = memo.get(mk)
        if hit is not None and hit[0]() is sc:
            return hit[1]
        key = keys_mod.group_key(sc, verb)
        if len(memo) >= 4096:        # bound the memo; rebuilt on demand
            memo.clear()
        try:
            memo[mk] = (weakref.ref(sc), key)
        except TypeError:            # pragma: no cover - non-weakrefable
            pass
        return key

    def _fail(self, plist: "list[_Pending]", exc: ServeError) -> None:
        metrics.counter("serve.errors").inc(len(plist))
        self.counts["errors"] += len(plist)
        for p in plist:
            if not p.future.done():
                p.future.set_exception(exc)

    # -- the batched solve --------------------------------------------------

    def _solve(self, verb: str, sig: tuple,
               plist: "list[_Pending]") -> tuple[Iterable, str]:
        scens = [p.scenario for p in plist]
        first = scens[0]
        if keys_mod.is_placed(first, verb):
            return self._solve_placed(sig, scens)
        key, rows = keys_mod.plan_entry(verb, sig, len(scens))
        label = keys_mod.key_label(verb, first, rows)
        plan = self.cache.get_or_build(
            key, lambda: keys_mod.compile_group(scens, verb, rows),
            label=label)
        if verb == "simulate":
            # Identical structure (numbers included) → one shared run.
            return [plan.run()] * len(scens), plan.engine
        n, f, bs = keys_mod.swap_arrays(scens, rows, plan.n.shape[1])
        pred = plan.run(cores=n, f=f, b_s=bs)
        # bulk fan-out (one tolist pass)
        return pred.rows(len(scens)), plan.engine

    def _solve_placed(self, sig: tuple, scens: "list[api.Scenario]"
                      ) -> tuple[Iterable, str]:
        """Every placed request of one node in one solve: the node's
        plan for the tick's row and lane buckets, with the tick's
        placements swapped in.  A request whose placement is invalid
        fails alone (its entry is the error).  The results come lazily,
        in request order."""
        results: list = [None] * len(scens)
        live, placed, most = [], [], 1
        for i, sc in enumerate(scens):
            try:
                pl, k = keys_mod.placement(sc)
            except (KeyError, ValueError) as e:
                results[i] = BadRequest(str(e.args[0] if e.args else e))
            else:
                live.append(i)
                placed.append(pl)
                most = max(most, k)
        if not live:
            return results, "none"
        first = scens[live[0]]
        lanes = keys_mod.lane_bucket(most, first.topo)
        key, rows = keys_mod.plan_entry("predict", sig, len(live), lanes)
        plan = self.cache.get_or_build(
            key, lambda: keys_mod.compile_group([first], "predict", rows,
                                                lanes),
            label=keys_mod.key_label("predict", first, rows, lanes))
        pred = plan.run(placement=keys_mod.padded_placements(placed, rows))
        pred = dataclasses.replace(pred, provenance=tuple(
            scens[i].provenance for i in live))
        # Each live request's result is built as it is handed out.
        row = iter(range(len(live)))
        return (r if r is not None else pred[next(row)]
                for r in results), plan.engine

    # -- introspection ------------------------------------------------------

    def _gauge(self) -> None:
        self._m_depth.set(len(self._pending))

    def stats(self) -> dict:
        """Coalescer gauges for ``/statsz``: intake counters, queue
        depth, tick count, and the live config."""
        return {
            "queue_depth": len(self._pending),
            "closed": self._closed,
            "ticks": self._ticks,
            **self.counts,
            "config": dataclasses.asdict(self.config),
        }
