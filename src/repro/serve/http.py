"""ndjson-over-HTTP transport: the asyncio front end.

A deliberately small HTTP/1.1 server on ``asyncio`` streams (stdlib
only — no framework): POST an ndjson body of request lines to
``/v1/solve`` (or the verb-pinning aliases ``/v1/predict`` /
``/v1/simulate``) and the responses stream back as chunked ndjson, one
line per request **in request order**, as each one's coalesced solve
lands.  ``GET /healthz`` answers liveness (503 while draining);
``GET /statsz`` returns the device, plan-cache, coalescer, and substrate
cache stats (``backend.cache_stats(scope="all")``) as one JSON document.

Connections are one-shot (``Connection: close``): the client idiom is
one POST per workload, many lines per POST — coalescing happens across
lines *and* across concurrent connections, so parallel clients batch
into the same ticks.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import gc
import itertools
import json
import time

from ..core import backend as backend_mod
from ..obs import metrics, trace
from .cache import PlanCache
from .coalesce import Coalescer, ServeConfig, ServeError
from . import protocol

#: Largest accepted request body (bytes); admission control for the
#: transport layer, matching the coalescer's queue bound in spirit.
MAX_BODY = 32 * 1024 * 1024
_MAX_HEADER = 64 * 1024

_STATUS_TEXT = {200: "OK", 400: "Bad Request", 404: "Not Found",
                405: "Method Not Allowed", 411: "Length Required",
                413: "Payload Too Large", 429: "Too Many Requests",
                500: "Internal Server Error", 503: "Service Unavailable",
                504: "Gateway Timeout"}


#: Most request lines of one post parsed and queued past its first
#: unanswered line; the rest follow, in pieces of half as many, as the
#: answers go out.  A post of 16,384 lines so keeps its parsing next to
#: its answers and holds few parsed requests at a time; eight such
#: posts still fill ticks of up to 16,384 lines.
_PARSE_AHEAD = 2048

#: Seconds between the server's own full garbage collections, which
#: replace Python's automatic ones while it serves.  Under a deep queue
#: every queued request's objects reach the oldest generation, and the
#: automatic schedule walks them all every second or two.
_GC_PERIOD_S = 10.0


async def _collect_garbage(period_s: float) -> None:
    """Collect the oldest generation every ``period_s`` seconds, and
    only then, until cancelled; Python's thresholds come back after."""
    saved = gc.get_threshold()
    gc.set_threshold(saved[0], saved[1], 2**31 - 1)
    try:
        while True:
            await asyncio.sleep(period_s)
            with trace.span("serve.gc"):
                gc.collect()
    finally:
        gc.set_threshold(*saved)


class App:
    """The server: one coalescer + plan cache behind an asyncio
    listener.  Socket-free layers stay reachable (``app.coalescer``,
    ``app.cache``) so tests and embedders can bypass HTTP."""

    def __init__(self, config: ServeConfig | None = None, *,
                 cache: PlanCache | None = None):
        self.config = config or ServeConfig()
        # "is None", not "or": an empty PlanCache is len() == 0 == falsy.
        self.cache = (cache if cache is not None
                      else PlanCache(self.config.cache_entries))
        self.coalescer = Coalescer(self.config, cache=self.cache)
        self._server: asyncio.base_events.Server | None = None
        self._gc: asyncio.Task | None = None
        self._t0 = time.monotonic()

    # -- lifecycle ----------------------------------------------------------

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Bind and listen; returns the bound port (useful with
        ``port=0``)."""
        self.coalescer.start()
        if self._gc is None:
            self._gc = asyncio.get_running_loop().create_task(
                _collect_garbage(_GC_PERIOD_S), name="repro.serve.gc")
        self._server = await asyncio.start_server(
            self._client, host=host, port=port)
        return self._server.sockets[0].getsockname()[1]

    async def shutdown(self, *, drain: bool = True) -> None:
        """Stop listening, then drain (or fail) queued requests."""
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        await self.coalescer.close(drain=drain)
        if self._gc is not None:
            self._gc.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._gc
            self._gc = None

    async def serve_forever(self) -> None:
        assert self._server is not None, "call start() first"
        await self._server.serve_forever()

    # -- introspection ------------------------------------------------------

    def statsz(self) -> dict:
        return {
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "device": backend_mod.device_info(),
            "coalescer": self.coalescer.stats(),
            "plan_cache": self.cache.stats(),
            "caches": backend_mod.cache_stats(scope="all"),
        }

    # -- the connection handler ---------------------------------------------

    async def _client(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            method, path, headers = await self._head(reader)
            if method is None:
                return
            if path in ("/healthz", "/statsz"):
                if method != "GET":
                    await self._json(writer, 405, {
                        "ok": False, "error": f"{path} is GET-only"})
                elif path == "/healthz":
                    draining = self.coalescer._closed
                    await self._json(
                        writer, 503 if draining else 200,
                        {"ok": not draining,
                         "status": "draining" if draining else "serving"})
                else:
                    await self._json(writer, 200, self.statsz())
                return
            verb = {"/v1/solve": None, "/v1/predict": "predict",
                    "/v1/simulate": "simulate"}.get(path, "?")
            if verb == "?":
                await self._json(writer, 404, {
                    "ok": False, "error": f"no route {path!r}; try "
                    f"/v1/solve, /v1/predict, /v1/simulate, /healthz, "
                    f"/statsz"})
                return
            if method != "POST":
                await self._json(writer, 405, {
                    "ok": False, "error": f"{path} is POST-only "
                    f"(ndjson body, one request per line)"})
                return
            body, err = await self._body(reader, headers)
            if err is not None:
                await self._json(writer, err[0], {"ok": False,
                                                  "error": err[1]})
                return
            await self._stream(writer, body, verb)
        except (ConnectionError, asyncio.IncompleteReadError):
            pass     # client went away; nothing to answer
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except ConnectionError:  # pragma: no cover - racy teardown
                pass

    async def _head(self, reader):
        try:
            raw = await reader.readuntil(b"\r\n\r\n")
        except (asyncio.IncompleteReadError, asyncio.LimitOverrunError):
            return None, None, None
        if len(raw) > _MAX_HEADER:
            return None, None, None
        lines = raw.decode("latin-1").split("\r\n")
        parts = lines[0].split()
        if len(parts) != 3:
            return None, None, None
        headers = {}
        for line in lines[1:]:
            if ":" in line:
                k, v = line.split(":", 1)
                headers[k.strip().lower()] = v.strip()
        return parts[0].upper(), parts[1], headers

    async def _body(self, reader, headers):
        if "content-length" not in headers:
            return None, (411, "POST needs a Content-Length")
        try:
            length = int(headers["content-length"])
        except ValueError:
            return None, (411, "malformed Content-Length")
        if length > MAX_BODY:
            return None, (413, f"body over {MAX_BODY} bytes")
        return await reader.readexactly(length), None

    async def _stream(self, writer, body: bytes, verb: str | None) -> None:
        """Parse and submit the request lines, at most ``_PARSE_AHEAD``
        past the first unanswered one, and stream the response lines in
        request order as their (coalesced, out-of-order) solves land."""
        lines = [ln for ln in body.decode("utf-8", "replace").splitlines()
                 if ln.strip()]
        metrics.counter("serve.http.posts").inc()
        t0 = time.monotonic()
        ahead = _PARSE_AHEAD
        refill = max(ahead // 2, 1)
        todo = iter(lines)
        queued: collections.deque = collections.deque()

        def feed(n: int) -> None:
            chunk = list(itertools.islice(todo, n))
            if not chunk:
                return
            with trace.span("serve.parse", lines=len(chunk)):
                parsed = [self._parse(ln, verb) for ln in chunk]
            queued.extend(asyncio.ensure_future(self._one(req, t0))
                          if isinstance(req, protocol.Request) else req
                          for req in parsed)

        feed(ahead)
        writer.write(
            b"HTTP/1.1 200 OK\r\n"
            b"Content-Type: application/x-ndjson\r\n"
            b"Transfer-Encoding: chunked\r\n"
            b"Connection: close\r\n\r\n")
        while queued:
            t = queued.popleft()
            row = t if isinstance(t, dict) else await t
            if len(queued) <= ahead - refill:
                feed(refill)
            data = (json.dumps(row) + "\n").encode()
            writer.write(b"%x\r\n%s\r\n" % (len(data), data))
            await writer.drain()   # transport backpressure, per line
        writer.write(b"0\r\n\r\n")
        await writer.drain()

    @staticmethod
    def _parse(line: str, verb: str | None) -> "protocol.Request | dict":
        """One request line parsed, or its error response."""
        req_id = None
        try:
            try:
                d = json.loads(line)
            except json.JSONDecodeError as e:
                raise protocol.BadRequest(f"bad JSON: {e}") from None
            if isinstance(d, dict):
                req_id = d.get("id")
                if verb is not None:
                    d = {**d, "kind": verb}
            return protocol.parse_request(d)
        except Exception as e:   # per-line isolation: stream continues
            if not isinstance(e, ServeError):
                metrics.counter("serve.http.errors").inc()
            return protocol.error_response(req_id, e)

    async def _one(self, req: "protocol.Request", t0: float) -> dict:
        try:
            result = await self.coalescer.submit(
                req.scenario, verb=req.verb, deadline_s=req.deadline_s)
            return protocol.build_response(
                req, result, time.monotonic() - t0)
        except Exception as e:   # per-line isolation: stream continues
            if not isinstance(e, ServeError):
                metrics.counter("serve.http.errors").inc()
            return protocol.error_response(req.id, e)

    async def _json(self, writer, status: int, payload: dict) -> None:
        data = (json.dumps(payload, sort_keys=True) + "\n").encode()
        writer.write(
            b"HTTP/1.1 %d %s\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n"
            b"Connection: close\r\n\r\n%s"
            % (status, _STATUS_TEXT.get(status, "?").encode(),
               len(data), data))
        await writer.drain()
