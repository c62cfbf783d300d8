"""The serving subsystem: plan cache, request coalescer, transport.

Acceptance gates of the serving PR: coalesced responses must be
bit-for-bit equal to per-request ``api.predict``/``plan.run`` on both
backends; admission control must reject over-queue submits (429) and
expire past-deadline requests (504); graceful drain must leave no
dropped futures; the plan cache must hit (rate 1.0) on
repeated-structure workloads and evict LRU-first; and
``cache_stats(scope=...)`` must report the jit and plan caches without
double-counting.  The coalescer tests are socket-free (``asyncio.run``
directly); one HTTP test drives the full wire path.
"""

import asyncio
import json
import threading

import numpy as np
import pytest

from repro import api
from repro.core import backend
from repro.obs import trace
from repro.serve import keys
from repro.serve import (App, BadRequest, Coalescer, DeadlineExceeded,
                         Draining, PlanCache, QueueFull, ServeConfig,
                         build_response, error_response, parse_request,
                         plan_cache_stats)

BACKENDS = ["numpy"] + (["jax"] if backend.HAVE_JAX else [])

D0, D1 = "CLX/s0/d0", "CLX/s1/d0"


def _scenarios(b, bk="numpy"):
    """b same-structure scenarios with distinct numeric payloads."""
    return [api.Scenario.on("CLX", backend=bk)
            .run("DCOPY", 1 + i % 19).run("DDOT2", 20 - i % 19)
            for i in range(b)]


def _assert_same_prediction(got, ref):
    np.testing.assert_array_equal(got.bw_group, ref.bw_group)
    np.testing.assert_array_equal(got.alphas, ref.alphas)
    np.testing.assert_array_equal(got.b_overlap, ref.b_overlap)
    assert got.total_bw == ref.total_bw
    assert [g.provenance for g in got.groups] == \
        [g.provenance for g in ref.groups]


# ---------------------------------------------------------------------------
# coalesced == per-request, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bk", BACKENDS)
def test_coalesced_bit_for_bit(bk):
    scs = _scenarios(16, bk)

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
            got = await asyncio.gather(*[c.submit(sc) for sc in scs])
            return got, c.cache.stats(), c.stats()

    got, cache, stats = asyncio.run(main())
    # The per-request reference: api.predict of each scenario, solved
    # as the same batch the coalescer packed (compile(...).run() of the
    # concurrent request set) — same backend, same power-of-two bucket,
    # so equality is exact, not approximate.
    refs = api.predict(api.ScenarioBatch.of(scs))
    for i, g in enumerate(got):
        _assert_same_prediction(g, refs[i])
    # ...and against the scalar reference solver, which the numpy batch
    # path reproduces bit-for-bit (the jitted jax path is allowed the
    # usual 1-ULP compiler latitude).
    for sc, g in zip(scs, got):
        ref = api.predict(sc)
        if bk == "numpy":
            _assert_same_prediction(g, ref)
        else:
            np.testing.assert_allclose(g.bw_group, ref.bw_group,
                                       rtol=1e-13)
    # One structure -> one plan compile, one batched solve.
    assert cache["misses"] == 1
    assert stats["accepted"] == stats["completed"] == 16


@pytest.mark.parametrize("bk", BACKENDS)
def test_repeated_structure_hits_cache(bk):
    scs = _scenarios(8, bk)

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
            first = await asyncio.gather(*[c.submit(sc) for sc in scs])
            second = await asyncio.gather(*[c.submit(sc) for sc in scs])
            return first, second, c.cache.stats()

    first, second, cache = asyncio.run(main())
    for a, b in zip(first, second):
        _assert_same_prediction(a, b)
    assert cache["misses"] == 1 and cache["hits"] >= 1


def test_mixed_structures_split_groups():
    a = api.Scenario.on("CLX").run("DCOPY", 12).run("DDOT2", 8)
    b = api.Scenario.on("CLX").run("JacobiL2-v1", 7)
    c_ = api.Scenario.on("CLX").run("DCOPY", 3).run("DDOT2", 17)

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
            return await asyncio.gather(
                c.submit(a), c.submit(b), c.submit(c_)), c.cache.stats()

    (ra, rb, rc), cache = asyncio.run(main())
    _assert_same_prediction(ra, api.predict(a))
    _assert_same_prediction(rb, api.predict(b))
    _assert_same_prediction(rc, api.predict(c_))
    # a and c_ share a structure (and a plan); b has its own.
    assert cache["entries"] == 2


def test_placed_bit_for_bit():
    scs = [api.Scenario.on("CLX").using("CLX-2S")
           .placed("DCOPY", 2 + i, D0).placed("DDOT2", 18 - i, D1)
           for i in range(6)]

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
            return await asyncio.gather(*[c.submit(sc) for sc in scs])

    got = asyncio.run(main())
    for sc, g in zip(scs, got):
        ref = api.predict(sc)
        _assert_same_prediction(g, ref)
        assert [d.domain for d in g.domains] == \
            [d.domain for d in ref.domains]


def test_simulate_shared_and_bit_for_bit():
    sim = (api.Scenario.on("CLX").ranks(4).with_noise(6e-5, ensemble=2)
           .step("DDOT2", 2e6, tag="ddot2").barrier())
    other = sim.with_noise(6e-5, seed=7, ensemble=2)

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
            return await asyncio.gather(
                c.submit(sim), c.submit(sim), c.submit(other))

    s1, s2, s3 = asyncio.run(main())
    assert s1 is s2          # identical structure -> one shared run
    ref = api.simulate(sim)
    np.testing.assert_array_equal(s1.t_end, ref.t_end)
    np.testing.assert_array_equal(s1.skew("ddot2"), ref.skew("ddot2"))
    # A different seed is a different structure key -> its own run.
    np.testing.assert_array_equal(s3.t_end, api.simulate(other).t_end)
    assert not np.array_equal(s3.t_end, s1.t_end)


# ---------------------------------------------------------------------------
# admission control, deadlines, drain
# ---------------------------------------------------------------------------


def test_deadline_expired_requests_fail_504():
    sc = _scenarios(1)[0]

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-2)) as c:
            ok_task = asyncio.ensure_future(c.submit(sc))
            with pytest.raises(DeadlineExceeded):
                await c.submit(sc, deadline_s=0.0)
            ok = await ok_task          # live request still solved
            return ok, c.stats()

    ok, stats = asyncio.run(main())
    _assert_same_prediction(ok, api.predict(sc))
    assert stats["expired"] == 1 and stats["completed"] == 1
    assert DeadlineExceeded.status == 504


def test_queue_full_rejects_429_and_drain_completes():
    scs = _scenarios(3)

    async def main():
        c = Coalescer(ServeConfig(tick_s=5.0, max_queue=2))
        t1 = asyncio.ensure_future(c.submit(scs[0]))
        t2 = asyncio.ensure_future(c.submit(scs[1]))
        await asyncio.sleep(0.05)       # both queued, tick window open
        with pytest.raises(QueueFull):
            await c.submit(scs[2])
        # Graceful drain: close() cuts the 5 s window short and the
        # queued requests still complete.
        await c.close(drain=True)
        return await t1, await t2, c.stats()

    r1, r2, stats = asyncio.run(main())
    _assert_same_prediction(r1, api.predict(scs[0]))
    _assert_same_prediction(r2, api.predict(scs[1]))
    assert stats["rejected"] == 1 and stats["completed"] == 2
    assert QueueFull.status == 429


def test_drain_leaves_no_dropped_futures():
    scs = _scenarios(32)

    async def main():
        c = Coalescer(ServeConfig(tick_s=0.2))
        tasks = [asyncio.ensure_future(c.submit(sc)) for sc in scs]
        await asyncio.sleep(0)          # enqueue, don't let the tick end
        await c.close(drain=True)
        return await asyncio.gather(*tasks), c.stats()

    got, stats = asyncio.run(main())
    assert stats["completed"] == 32
    for sc, g in zip(scs, got):
        _assert_same_prediction(g, api.predict(sc))


def test_close_without_drain_fails_pending_and_rejects_new():
    scs = _scenarios(4)

    async def main():
        c = Coalescer(ServeConfig(tick_s=0.5))
        tasks = [asyncio.ensure_future(c.submit(sc)) for sc in scs]
        await asyncio.sleep(0)
        await c.close(drain=False)
        results = await asyncio.gather(*tasks, return_exceptions=True)
        with pytest.raises(Draining):
            await c.submit(scs[0])
        return results

    results = asyncio.run(main())
    assert len(results) == 4
    assert all(isinstance(r, Draining) for r in results)


# ---------------------------------------------------------------------------
# plan cache: LRU, warmup, stats scopes
# ---------------------------------------------------------------------------


def test_plan_cache_lru_eviction():
    cache = PlanCache(max_entries=2)
    built = []

    def make(i):
        return lambda: built.append(i) or i

    assert cache.get_or_build(("a",), make("a")) == "a"
    assert cache.get_or_build(("b",), make("b")) == "b"
    assert cache.get_or_build(("a",), make("a2")) == "a"   # refresh a
    assert cache.get_or_build(("c",), make("c")) == "c"    # evicts b
    assert cache.get_or_build(("a",), make("a3")) == "a"   # a survived
    assert cache.get_or_build(("b",), make("b2")) == "b2"  # b was evicted
    st = cache.stats()
    assert st["entries"] == 2 and st["evictions"] == 2
    assert built == ["a", "b", "c", "b2"]


def test_warmup_gives_hit_rate_one():
    template = _scenarios(1)[0]
    cache = PlanCache()
    built = cache.warmup(template, buckets=(1, 5))   # buckets 1 and 8
    assert built == 2 and len(cache) == 2
    scs = _scenarios(6)     # bucket(6) == 8: warmed

    async def main():
        async with Coalescer(ServeConfig(tick_s=1e-3),
                             cache=cache) as c:
            return await asyncio.gather(*[c.submit(sc) for sc in scs])

    got = asyncio.run(main())
    for sc, g in zip(scs, got):
        _assert_same_prediction(g, api.predict(sc))
    st = cache.stats()
    assert st["misses"] == 2          # only the warmup compiles
    assert st["hits"] >= 1            # the live tick was a pure hit
    # Warming again is free: every bucket already cached.
    assert cache.warmup(template, buckets=(1, 5)) == 0


def test_cache_stats_scope_selector():
    backend.clear_jit_cache()         # reset metrics for exact counts
    cache = PlanCache()
    cache.get_or_build(("x",), lambda: "x", label="L")
    cache.get_or_build(("x",), lambda: "x", label="L")
    jit = backend.cache_stats()       # default: the jit cache
    assert set(jit) == {"hits", "misses", "entries", "hit_rate",
                        "buckets", "xla"}
    plan = backend.cache_stats(scope="plan")
    assert plan["hits"] == 1 and plan["misses"] == 1
    assert plan["buckets"]["L"]["hits"] == 1
    assert plan == plan_cache_stats()
    both = backend.cache_stats(scope="all")
    assert set(both) >= {"jit", "plan"}
    # No double-counting: each scope owns disjoint counters.
    assert both["jit"] == jit and both["plan"]["hits"] == 1
    assert "serve.plan.hit" not in json.dumps(jit)
    with pytest.raises(KeyError, match="cache scope"):
        backend.cache_stats(scope="nope")


# ---------------------------------------------------------------------------
# structure keys
# ---------------------------------------------------------------------------


def test_structure_key_contract():
    a, b = _scenarios(2)
    assert a.runs[0].n != b.runs[0].n
    # predict: numbers are swappable, not structural.
    assert api.structure_key(a) == api.structure_key(b)
    other = api.Scenario.on("CLX").run("JacobiL2-v1", 7)
    assert api.structure_key(a) != api.structure_key(other)
    assert api.structure_key(a) != \
        api.structure_key(a.options(utilization=0.7))
    # simulate: numbers (and seeds) are structural.
    sim = (api.Scenario.on("CLX").ranks(2)
           .step("DDOT2", 2e6).barrier())
    assert api.structure_key(sim) != \
        api.structure_key(sim.with_noise(5e-5, seed=3))
    assert api.infer_verb(sim) == "simulate"
    assert api.infer_verb(a) == "predict"
    batch = api.ScenarioBatch.of([a, b])
    assert api.structure_key(batch) == \
        (api.structure_key(a), api.structure_key(b))
    with pytest.raises(ValueError, match="verb"):
        api.structure_key(a, verb="banana")


@pytest.mark.parametrize("bk", BACKENDS)
def test_batch_rows_match_getitem(bk):
    # The serving fan-out uses BatchPrediction.rows() (one bulk tolist
    # pass); it must be indistinguishable from per-row __getitem__.
    scs = _scenarios(5, bk)
    pred = api.predict(api.ScenarioBatch.of(scs))
    rows = pred.rows()
    assert len(rows) == len(pred) == 5
    for i in range(len(pred)):
        assert rows[i] == pred[i]
        assert repr(rows[i]) == repr(pred[i])
    assert pred.rows(2) == [pred[0], pred[1]]


# ---------------------------------------------------------------------------
# obs: correlated spans across the stack
# ---------------------------------------------------------------------------


def test_request_spans_correlate():
    trace.enable(clear_events=True)
    try:
        scs = _scenarios(4)

        async def main():
            async with Coalescer(ServeConfig(tick_s=1e-3)) as c:
                await asyncio.gather(*[c.submit(sc) for sc in scs])

        asyncio.run(main())
    finally:
        trace.disable()
    names = [e[1] for e in trace.events()]
    assert names.count("serve.accept") == 4
    assert "serve.coalesce" in names and "api.plan.run" in names
    by_name = {e[1]: e for e in trace.events()}
    # plan.run nests inside the coalescing span (same thread, deeper).
    assert by_name["api.plan.run"][5] > by_name["serve.coalesce"][5]
    trace.clear()


# ---------------------------------------------------------------------------
# protocol: parse/build
# ---------------------------------------------------------------------------


def test_protocol_parse_and_response():
    req = parse_request({
        "id": 7, "arch": "CLX", "deadline_ms": 250,
        "groups": [{"kernel": "DCOPY", "n": 12},
                   {"kernel": [0.5, 110.0], "n": 8, "tag": "custom"}]})
    assert req.verb == "predict" and req.deadline_s == 0.25
    pred = api.predict(req.scenario)
    out = build_response(req, pred, 0.002)
    assert out["id"] == 7 and out["ok"] and out["kind"] == "prediction"
    assert out["total_bw"] == pred.total_bw and out["serve_ms"] == 2.0

    sim = parse_request({
        "arch": "CLX", "ranks": 4, "t_max": 5, "tags": ["ddot2"],
        "noise": {"exp_mean_s": 6e-5, "ensemble": 2},
        "steps": [{"op": "work", "kernel": "DDOT2", "bytes": 2e6,
                   "tag": "ddot2"}, {"op": "barrier"}]})
    assert sim.verb == "simulate" and sim.scenario.t_max == 5.0
    body = build_response(sim, api.simulate(sim.scenario), 0.01)
    assert body["kind"] == "simulation" and "ddot2" in body["skew"]


@pytest.mark.parametrize("bad,match", [
    ({}, "missing required field 'arch'"),
    ({"arch": "CLX", "bogus": 1}, "unknown request fields"),
    ({"arch": "CLX", "groups": [{"kernel": {"x": 1}, "n": 2}]},
     "kernel must be"),
    ({"arch": "CLX", "kind": "guess"}, "kind must be"),
    ({"arch": "CLX", "ranks": 2, "steps": [{"op": "warp"}]},
     "unknown op"),
    ({"arch": "CLX", "options": {"nope": 1}}, "unknown scenario options"),
])
def test_protocol_rejects_bad_requests(bad, match):
    with pytest.raises(BadRequest, match=match):
        parse_request(bad)
    assert BadRequest.status == 400


def test_error_response_envelope():
    out = error_response(3, DeadlineExceeded("too slow"))
    assert out == {"id": 3, "ok": False, "kind": "error", "status": 504,
                   "error": "too slow"}


# ---------------------------------------------------------------------------
# the wire: one full HTTP round trip
# ---------------------------------------------------------------------------


class _Server:
    """App on a background thread with its own loop (the client side of
    the test is blocking http.client)."""

    def __init__(self, config=None):
        self.config = config or ServeConfig(tick_s=1e-3)

    def __enter__(self):
        self.loop = asyncio.new_event_loop()
        ready = threading.Event()
        self.box = {}

        def run():
            asyncio.set_event_loop(self.loop)

            async def go():
                self.app = App(self.config)
                self.box["stop"] = asyncio.Event()
                self.port = await self.app.start(port=0)
                ready.set()
                await self.box["stop"].wait()
                await self.app.shutdown(drain=True)

            self.loop.run_until_complete(go())
            self.loop.close()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert ready.wait(10), "server failed to start"
        return self

    def __exit__(self, *exc):
        self.loop.call_soon_threadsafe(self.box["stop"].set)
        self.thread.join(10)
        assert not self.thread.is_alive(), "server failed to shut down"


def test_http_round_trip_streams_in_order():
    from repro.serve import client

    rows = [{"id": i, "arch": "CLX",
             "groups": [{"kernel": "DCOPY", "n": 1 + i},
                        {"kernel": "DDOT2", "n": 19 - i}]}
            for i in range(10)]
    rows.insert(5, {"id": "bad", "arch": "CLX",
                    "groups": [{"kernel": "NOPE", "n": 2}]})
    with _Server() as srv:
        status, health = client.get_json("127.0.0.1", srv.port,
                                         "/healthz")
        assert status == 200 and health["ok"]
        out = client.solve("127.0.0.1", srv.port, rows)
        # Streamed in request order, bad line isolated.
        assert [r["id"] for r in out] == [r["id"] for r in rows]
        bad = out[5]
        assert not bad["ok"] and bad["status"] == 400
        assert "NOPE" in bad["error"]
        for r in (x for x in out if x["ok"]):
            sc = api.Scenario.on("CLX").run("DCOPY", 1 + r["id"]) \
                .run("DDOT2", 19 - r["id"])
            assert r["total_bw"] == api.predict(sc).total_bw
        status, stats = client.get_json("127.0.0.1", srv.port,
                                        "/statsz")
        assert status == 200
        assert stats["coalescer"]["accepted"] == 10
        assert stats["plan_cache"]["entries"] >= 1
        assert set(stats["caches"]) >= {"jit", "plan"}
        status, err = client.get_json("127.0.0.1", srv.port, "/wat")
        assert status == 404 and not err["ok"]
    # Exiting the context asserts a clean drain/shutdown.


# ---------------------------------------------------------------------------
# placed batch posts: one plan per node, placements swapped per tick
# ---------------------------------------------------------------------------

NPS4 = [f"ROME/s{s}/d{d}" for s in range(2) for d in range(4)]
ROME_KERNELS = ["ADD", "DAXPY", "DCOPY", "DDOT2", "DSCAL", "STREAM",
                "vectorSUM"]


def _placed_rows(count, seed):
    """Request lines drawn as the placements cell draws scenarios: 1-3
    Table II groups of 1-4 threads, each on a random NPS4 domain within
    its 8 cores."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(count):
        used = [0] * len(NPS4)
        groups = []
        for _ in range(rng.integers(1, 4)):
            d = int(rng.integers(len(NPS4)))
            free = 8 - used[d]
            if not free:
                continue
            n = int(rng.integers(1, min(4, free) + 1))
            used[d] += n
            groups.append({"kernel": ROME_KERNELS[int(rng.integers(7))],
                           "n": n, "domain": NPS4[d]})
        rows.append({"id": i, "arch": "ROME", "topology": "ROME-2S-NPS4",
                     "groups": groups})
    return rows


def _coalesce(scs, config):
    async def main():
        async with Coalescer(config) as c:
            got = await asyncio.gather(*[c.submit(sc) for sc in scs])
            return got, c.cache.stats(), c.stats()
    return asyncio.run(main())


def test_placed_batch_posts_over_http():
    from repro.serve import client

    rows = _placed_rows(2048, 1)
    posts = [rows[:300], rows[300:1100], rows[1100:]]
    config = ServeConfig(tick_s=1e-3, max_batch=2048, max_queue=4096)
    with _Server(config) as srv:
        out = [r for post in posts
               for r in client.solve("127.0.0.1", srv.port, post,
                                     path="/v1/predict")]
        status, stats = client.get_json("127.0.0.1", srv.port, "/statsz")
    assert [r["id"] for r in out] == [r["id"] for r in rows]
    assert all(r["ok"] for r in out)
    scs = [parse_request(r).scenario for r in rows]
    batch = api.ScenarioBatch.of(scs)
    plan = api.compile(batch)
    assert plan.engine == "jax"
    ref = plan.run(placement=batch.placements)
    for i, (sc, r) in enumerate(zip(scs, out)):
        got = [g["bw"] for g in r["groups"]]
        assert got == list(ref[i].bw_group)
        assert [g["provenance"] for g in r["groups"]] == \
            list(sc.provenance)
        np.testing.assert_allclose(
            got, api.predict(sc, backend="numpy").bw_group, rtol=1e-12)
    # Every placed line of the node went through one plan per tick.
    assert stats["plan_cache"]["misses"] <= stats["coalescer"]["ticks"]


def test_removed_dispatch_option_fails_its_line_alone():
    from repro.serve import client

    rows = _placed_rows(6, 2)
    rows[2] = dict(rows[2], options={"jax_cutoff": 1})
    with _Server() as srv:
        out = client.solve("127.0.0.1", srv.port, rows, path="/v1/predict")
    assert [r["id"] for r in out] == [r["id"] for r in rows]
    bad = out[2]
    assert not bad["ok"] and bad["status"] == 400
    assert bad["error"].startswith("options: ") and \
        "jax_cutoff" in bad["error"]
    for row, r in zip(rows, out):
        if row is rows[2]:
            continue
        assert r["ok"]
        sc = parse_request(row).scenario
        assert [g["bw"] for g in r["groups"]] == pytest.approx(
            list(api.predict(sc, backend="numpy").bw_group), rel=1e-12)


def test_parse_ahead_parses_a_post_in_pieces(monkeypatch):
    from repro.serve import client, http

    rows = _placed_rows(100, 8)
    got = {}
    trace.enable(clear_events=True)
    try:
        for ahead in (None, 16):
            monkeypatch.setattr(http, "_PARSE_AHEAD", ahead or 2048)
            with _Server(ServeConfig(tick_s=1e-3)) as srv:
                got[ahead] = client.solve("127.0.0.1", srv.port, rows,
                                          path="/v1/predict")
            got[ahead, "parsed"] = [e[6]["lines"] for e in trace.events()
                                    if e[1] == "serve.parse"]
            trace.clear()
    finally:
        trace.disable()
    assert [r["id"] for r in got[16]] == list(range(100))
    assert all(r["ok"] for r in got[16])
    for whole, piece in zip(got[None], got[16]):
        np.testing.assert_allclose([g["bw"] for g in piece["groups"]],
                                   [g["bw"] for g in whole["groups"]],
                                   rtol=1e-12)
    # The whole post on arrival, or 16 lines and then pieces of 8.
    assert got[None, "parsed"] == [100]
    assert got[16, "parsed"] == [16] + [8] * 10 + [4]


def test_placed_lines_share_one_plan_per_bucket():
    scs = [parse_request(r).scenario for r in _placed_rows(1024, 2)]
    # By structure these would be hundreds of plans...
    assert len({api.structure_key(sc) for sc in scs}) > 500
    got, cache, stats = _coalesce(
        scs, ServeConfig(tick_s=0.05, max_batch=1024, max_queue=2048))
    # ...but one tick compiles one plan: its (row, lane) bucket's.
    assert stats["ticks"] == 1 and stats["completed"] == 1024
    assert cache["misses"] == cache["entries"] == 1
    for sc, g in list(zip(scs, got))[::37]:
        np.testing.assert_allclose(
            g.bw_group, api.predict(sc, backend="numpy").bw_group,
            rtol=1e-12)


def test_device_tick_span_and_queue_time():
    from repro.obs import metrics
    scs = [parse_request(r).scenario for r in _placed_rows(256, 3)]

    def queued():
        return sum(r["count"] for r in metrics.snapshot()
                   if r["name"] == "serve.queue_s")

    before = queued()
    trace.enable(clear_events=True)
    try:
        got, _, stats = _coalesce(scs, ServeConfig(tick_s=0.05,
                                                   max_batch=256))
    finally:
        trace.disable()
    # One tick, one solve on the device path, labelled with its backend;
    # every request's queue time recorded.
    solves = [e for e in trace.events() if e[1] == "serve.solve"]
    assert [(e[6]["backend"], e[6]["n"]) for e in solves] == [("jax", 256)]
    assert queued() - before == 256 == stats["completed"]
    trace.clear()


def test_placed_requests_key_by_their_node():
    a, b = (parse_request(r).scenario for r in _placed_rows(2, 6))
    assert api.structure_key(a) != api.structure_key(b)
    for sc in (a, b, _scenarios(1)[0]):
        assert api.structure_key(sc)[:4] == api.node_key(sc)
    assert keys.group_key(a, "predict") == keys.group_key(b, "predict") \
        == api.node_key(a, verb="predict")


def test_large_tick_answers_in_pieces(monkeypatch):
    from repro.serve import coalesce
    monkeypatch.setattr(coalesce, "_PIECE", 4)
    scs = [parse_request(r).scenario for r in _placed_rows(18, 7)]

    async def main():
        seen = []
        async with Coalescer(ServeConfig(tick_s=0.05)) as c:
            futs = [asyncio.ensure_future(c.submit(sc)) for sc in scs]

            async def watch():
                while not all(f.done() for f in futs):
                    seen.append(sum(f.done() for f in futs))
                    await asyncio.sleep(0)

            await asyncio.gather(watch(), *futs)
            return [f.result() for f in futs], seen, c.stats()

    got, seen, stats = asyncio.run(main())
    assert stats["ticks"] == 1
    # One tick, answered four requests at a time, in request order.
    assert {4, 8, 12, 16} <= set(seen) and max(seen) < 18
    for sc, g in zip(scs, got):
        np.testing.assert_allclose(
            g.bw_group, api.predict(sc, backend="numpy").bw_group,
            rtol=1e-12)


def test_server_times_its_full_collections(monkeypatch):
    import gc
    from repro.serve import http
    monkeypatch.setattr(http, "_GC_PERIOD_S", 0.01)
    saved = gc.get_threshold()
    trace.enable(clear_events=True)
    try:
        with _Server(ServeConfig(tick_s=1e-3)):
            during = gc.get_threshold()
            threading.Event().wait(0.1)
    finally:
        trace.disable()
    assert during == (saved[0], saved[1], 2**31 - 1)
    assert gc.get_threshold() == saved
    assert any(e[1] == "serve.gc" for e in trace.events())
    trace.clear()


def test_bad_placed_request_fails_alone():
    good = [parse_request(r).scenario for r in _placed_rows(20, 4)]
    bad = api.Scenario.on("ROME").using("ROME-2S-NPS4") \
        .placed("DCOPY", 9, NPS4[0])          # 9 threads on 8 cores
    lost = api.Scenario.on("ROME").using("ROME-2S-NPS4") \
        .placed("DCOPY", 1, "ROME/s7/d0")

    async def main():
        async with Coalescer(ServeConfig(tick_s=0.05)) as c:
            return await asyncio.gather(
                *[c.submit(sc) for sc in good + [bad, lost]],
                return_exceptions=True)

    got = asyncio.run(main())
    for sc, g in zip(good, got):
        assert g.bw_group == api.predict(sc, backend="numpy").bw_group
    assert isinstance(got[-2], BadRequest) and "overcommitted" in \
        str(got[-2])
    assert isinstance(got[-1], BadRequest) and "unknown domain" in \
        str(got[-1])


def test_placed_warmup_covers_row_and_lane_buckets():
    probe = api.Scenario.on("ROME").using("ROME-2S-NPS4") \
        .placed("ADD", 1, NPS4[0])
    cache = PlanCache()
    # Rows 8 and 64, lanes 1, 2 and 3 -> 4: six plans.
    assert cache.warmup(probe, buckets=(5, 64), lanes=(1, 2, 3)) == 6
    compiles = backend.cache_stats()["xla"]["compiles"]
    scs = [parse_request(r).scenario for r in _placed_rows(60, 5)]

    async def main():
        async with Coalescer(ServeConfig(tick_s=0.05), cache=cache) as c:
            return await asyncio.gather(*[c.submit(sc) for sc in scs])

    got = asyncio.run(main())
    assert len(got) == 60
    st = cache.stats()
    assert st["misses"] == 6 and st["hits"] >= 1
    # The live tick compiled no solver program either.
    assert backend.cache_stats()["xla"]["compiles"] == compiles
    assert cache.warmup(probe, buckets=(5, 64), lanes=(1, 2, 3)) == 0
