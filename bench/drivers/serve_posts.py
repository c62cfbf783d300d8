"""Batch posts of placed scenarios to the prediction service
(``repro.serve``), open loop, from a client process of their own.

Set-up starts the service (``repro.serve.App``, with the traffic's
``serve`` settings) on an event loop of its own in this process, so its
spans and the device's trace are this process's, and serves it on a
localhost port.  It warms the service through its coalescer: one tick
for every power-of-two row count a tick can reach up to ``max_batch``
and every number of groups, up to ``max_groups``, that a line can put
on one domain.  It also starts the client, this file run as a
script, once: it draws ``posts`` posts from the seed, each of a number
of lines drawn log-uniform from ``min_lines`` to ``max_lines``, each
line a placed scenario drawn as the placements cell draws one (1 to
``max_groups`` Table II kernel groups of 1 to ``max_threads`` threads,
each on a random domain within its cores).

Set-up ends ``lead_s`` seconds after the client starts sending the
posts (taken round) open loop: Poisson arrivals at
``rate_lines_per_s`` lines a second, at most ``in_flight`` posts at
once (later arrivals wait on the client, and their latency counts from
the scheduled send).  The client reads each post's response lines as
they stream in, checks that each comes back in request order with its
id and with ``ok``, and counts those received in the window.  Of each
post it may keep ``check_per_post`` lines drawn from the seed, those
answered in the window; of these it keeps ``check_lines``, drawn from
the seed, so that the comparison covers the whole window at its load.
When the window's time is up it stops sending, drops its connections,
and reports; the service then closes without draining.  The kept lines
are solved again by the plain reference once the window has closed.
"""

from __future__ import annotations

import asyncio
import gc
import json
import math
import subprocess
import sys
import threading
import time

import numpy as np


def _posts(config: dict, traffic: dict, seed: int) -> list[list[str]]:
    """The request lines of every post, drawn from the seed."""
    node = config["node"]
    domains = list(node["domains"])
    cap = int(node["cores_per_domain"])
    kernels = sorted(config["kernels"])
    lo, hi = int(traffic["min_lines"]), int(traffic["max_lines"])
    rng = np.random.default_rng(seed)
    head = (f'"arch": "{node["arch"]}", "topology": "{node["name"]}", '
            f'"groups": [')
    posts = []
    for _ in range(int(traffic["posts"])):
        size = int(math.exp(rng.uniform(math.log(lo), math.log(hi + 1))))
        lines = []
        for i in range(min(max(size, lo), hi)):
            used = [0] * len(domains)
            groups = []
            for _ in range(rng.integers(1, traffic["max_groups"] + 1)):
                d = int(rng.integers(len(domains)))
                free = cap - used[d]
                if not free:
                    continue
                n = int(rng.integers(1, min(traffic["max_threads"], free)
                                     + 1))
                used[d] += n
                k = kernels[int(rng.integers(len(kernels)))]
                groups.append(f'{{"kernel": "{k}", "n": {n}, '
                              f'"domain": "{domains[d]}"}}')
            lines.append(f'{{"id": {i}, {head}{", ".join(groups)}]}}')
        posts.append(lines)
    return posts


def _kept(traffic: dict, seed: int, post: int, size: int) -> set[int]:
    """The lines of the ``post``-th post sent whose answers may be kept."""
    rng = np.random.default_rng([seed, 2, post])
    per = min(int(traffic["check_per_post"]), size)
    return set(rng.choice(size, per, replace=False).tolist())


# -- the client (this file run as a script) -----------------------------------


async def _client(port: int, posts: list[list[str]], traffic: dict,
                  seed: int) -> dict:
    """Send the posts from now on; read the window's seconds from
    standard input when set-up ends, and report once it has closed."""
    bodies = [("\n".join(p) + "\n").encode() for p in posts]
    mean = sum(map(len, posts)) / len(posts)
    rng = np.random.default_rng([seed, 1])
    rate = float(traffic["rate_lines_per_s"])
    slots = asyncio.Semaphore(int(traffic["in_flight"]))
    check_lines = int(traffic["check_lines"])
    out = {"posts_sent": 0, "posts_done": 0, "offered_lines": 0,
           "sent_lines": 0, "ok_lines": 0, "error_lines": 0,
           "unanswered_lines": 0, "order_errors": 0,
           "outstanding_lines": 0, "latencies_s": [], "kept": [],
           "first_error": None, "in_flight_samples": []}
    live: dict[int, list[int]] = {}    # post -> [lines sent, answered]
    window = [math.inf, math.inf]      # its start and end, once known

    def within() -> bool:
        return window[0] <= time.perf_counter() < window[1]

    async def one(k: int, at: float) -> None:
        p = k % len(posts)
        lines = posts[p]
        async with slots:
            keep = _kept(traffic, seed, k, len(lines))
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            try:
                writer.write(b"POST /v1/predict HTTP/1.1\r\n"
                             b"Host: 127.0.0.1\r\n"
                             b"Content-Type: application/x-ndjson\r\n"
                             b"Content-Length: %d\r\n\r\n%s"
                             % (len(bodies[p]), bodies[p]))
                out["posts_sent"] += 1
                out["sent_lines"] += len(lines)
                state = live[k] = [len(lines), 0]
                await writer.drain()
                await reader.readuntil(b"\r\n\r\n")
                while True:
                    size = int(await reader.readline(), 16)
                    if size == 0:
                        break
                    row = json.loads(await reader.readexactly(size + 2))
                    i = state[1]
                    state[1] += 1
                    if row.get("id") != i:
                        out["order_errors"] += 1
                    if not row.get("ok"):
                        out["error_lines"] += 1
                        out["first_error"] = out["first_error"] or row
                        continue
                    if within():
                        out["ok_lines"] += 1
                        if i in keep:
                            out["kept"].append(
                                [lines[i], [g["bw"] for g in row["groups"]]])
                out["unanswered_lines"] += state[0] - state[1]
                out["posts_done"] += 1
                if within():
                    out["latencies_s"].append(time.perf_counter() - at)
                del live[k]
            finally:
                writer.close()

    async def arrivals() -> None:
        """Poisson posts from now on, at the offered rate in lines."""
        at, k = time.perf_counter(), 0
        while True:
            at += rng.exponential(mean / rate)
            await asyncio.sleep(max(0.0, at - time.perf_counter()))
            if within():
                out["offered_lines"] += len(posts[k % len(posts)])
            tasks.append(asyncio.ensure_future(one(k, at)))
            k += 1

    async def sample() -> None:
        while True:
            await asyncio.sleep(0.1)
            if within():
                out["in_flight_samples"].append(
                    sum(s - a for s, a in live.values()))

    tasks = [asyncio.ensure_future(arrivals()),
             asyncio.ensure_future(sample())]
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    window[0] = time.perf_counter()
    window[1] = window[0] + json.loads(line)["seconds"]
    await asyncio.sleep(window[1] - time.perf_counter())
    for t in list(tasks):
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    out["seconds"] = time.perf_counter() - window[0]
    out["outstanding_lines"] = sum(s - a for s, a in live.values())
    kept = out["kept"]
    if len(kept) > check_lines:
        pick = np.random.default_rng([seed, 3]).choice(
            len(kept), check_lines, replace=False)
        out["kept"] = [kept[i] for i in sorted(pick)]
    return out


def client_main() -> int:
    """Draw the posts and say ``ready``; start sending at the ``port``
    line, and report when the window of the ``seconds`` line closes."""
    spec = json.loads(sys.stdin.readline())
    posts = _posts(spec["config"], spec["traffic"], spec["seed"])
    print(json.dumps({"ready": sum(map(len, posts))}), flush=True)
    go = json.loads(sys.stdin.readline())
    report = asyncio.run(_client(go["port"], posts, spec["traffic"],
                                 spec["seed"]))
    print(json.dumps(report), flush=True)
    return 0


# -- the benchmark's side -----------------------------------------------------


class _Pauses:
    """A ``gc.callbacks`` entry that adds up this process's garbage
    collection pauses and counts its full collections."""

    def __init__(self):
        self.seconds, self.full, self._t = 0.0, 0, None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:   # not one that began before it was added
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2
            self._t = None


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        node = config["node"]
        self.domains = list(node["domains"])
        self.p0 = float(config["utilization"]["p0_factor"])
        self.f = {k: v["f"][node["arch"]]
                  for k, v in config["kernels"].items()}
        self.bs = {k: v["b_s"][node["arch"]]
                   for k, v in config["kernels"].items()}
        self.limits = traffic["limits"]
        self.client = subprocess.Popen(
            [sys.executable, __file__], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self._say({"config": config, "traffic": traffic, "seed": seed})

        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="bench-serve", daemon=True)
        self.thread.start()
        self.app = self._await(self._start(traffic["serve"]))
        self._warm(node, sorted(config["kernels"])[0], traffic)
        ready = self._hear()
        if "ready" not in ready:
            raise RuntimeError(f"the client did not start: {ready}")
        # The posts start arriving in set-up, so that the window measures
        # a service already under its load.
        self._say({"port": self.port})
        time.sleep(float(traffic["lead_s"]))

    # -- plumbing ---------------------------------------------------------

    def _say(self, obj: dict) -> None:
        self.client.stdin.write(json.dumps(obj) + "\n")
        self.client.stdin.flush()

    def _hear(self) -> dict:
        line = self.client.stdout.readline()
        if not line:
            raise RuntimeError(f"the client exited with "
                               f"{self.client.wait()}")
        return json.loads(line)

    def _await(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result()

    async def _start(self, settings: dict):
        import dataclasses

        from repro.serve import App, ServeConfig
        known = {f.name for f in dataclasses.fields(ServeConfig)}
        app = App(ServeConfig(**{k: v for k, v in settings.items()
                                 if k in known}))
        self.port = await app.start("127.0.0.1", 0)
        return app

    def _warm(self, node: dict, kernel: str, traffic: dict) -> None:
        """Every shape a tick of the traffic can take, through the
        service's coalescer and plan cache, in set-up: for each number
        of groups the traffic can put on one domain, one tick per
        power-of-two row bucket up to ``max_batch``, of as many
        requests as reach that bucket, submitted at once.  Each request
        fills a domain (one group on the rest of its cores, the others
        of one thread each), so it also reaches the node's deepest
        recursion bound.  The service caches what its own ticks of
        these shapes use."""
        from repro.core import backend
        from repro.serve import parse_request

        cap = int(node["cores_per_domain"])
        top = backend.bucket(int(traffic["serve"]["max_batch"]))
        sizes = [1] + [(1 << i) + 1 for i in range(top.bit_length() - 1)]
        for k in range(1, int(traffic["max_groups"]) + 1):
            groups = ([{"kernel": kernel, "n": cap - k + 1,
                        "domain": self.domains[-1]}]
                      + [{"kernel": kernel, "n": 1,
                          "domain": self.domains[-1]}] * (k - 1))
            scenario = parse_request(
                {"kind": "predict", "arch": node["arch"],
                 "topology": node["name"], "groups": groups}).scenario
            for size in sizes:
                self._await(self._tick([scenario] * size))

    async def _tick(self, scenarios: list) -> None:
        submit = self.app.coalescer.submit
        await asyncio.gather(*(submit(sc) for sc in scenarios))

    # -- the cell ---------------------------------------------------------

    def window(self, seconds: float, *, annotate: bool) -> dict:
        from bench.drivers import annotation

        pauses = _Pauses()
        gc.callbacks.append(pauses)
        try:
            with annotation("bench.serve.posts", annotate):
                self._say({"seconds": seconds})
                report = self._hear()
        finally:
            gc.callbacks.remove(pauses)
        self._close()
        lat = sorted(report["latencies_s"])
        q = (np.quantile(lat, [0.5, 0.99]).tolist() if lat
             else [math.nan, math.nan])
        flight = report["in_flight_samples"]
        print("bench: serve posts " + json.dumps({
            "posts_done": report["posts_done"],
            "posts_sent": report["posts_sent"],
            "latency_p50_s": q[0], "latency_p99_s": q[1],
            "error_share": report["error_lines"]
            / max(report["sent_lines"], 1),
            "offered_lines_per_s": report["offered_lines"]
            / report["seconds"],
            "lines_in_flight_mean": float(np.mean(flight)) if flight
            else 0.0,
            "gc_full": pauses.full, "gc_pause_s": pauses.seconds,
            "first_error": report["first_error"]}), file=sys.stderr)
        return report

    def end_to_end(self, window: dict) -> dict:
        return {"scenarios_per_s": window["ok_lines"] / window["seconds"]}

    def info(self, window: dict) -> dict:
        return {"posts": window["posts_done"], "lines": window["ok_lines"]}

    def _close(self) -> None:
        """Stop the service without draining what it still holds."""
        if self.app is not None:
            self._await(self.app.shutdown(drain=False))
            self.app = None

    def release(self) -> None:
        self._close()
        if self.client.poll() is None:
            self.client.stdin.close()
            self.client.wait(30)
        if self.thread.is_alive():
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(30)
            self.loop.close()

    def _grid(self, line: str):
        """(n, f, b_s) lanes of one request line, as the configuration
        gives them, and the (domain, lane) of each of its groups."""
        groups = json.loads(line)["groups"]
        D, K = len(self.domains), max(len(groups), 1)
        n, f, bs = (np.zeros((D, K)) for _ in range(3))
        lane = [0] * D
        slots = []
        for g in groups:
            d = self.domains.index(g["domain"])
            at = (d, lane[d])
            n[at], f[at], bs[at] = g["n"], self.f[g["kernel"]], \
                self.bs[g["kernel"]]
            lane[d] += 1
            slots.append(at)
        return n, f, bs, slots

    def check(self, window: dict, *, control: bool = False):
        """Widest gap between the kept lines' bandwidths and the
        reference's, beside what the client saw of every line.
        ``control`` puts the reference in float32 in the program's
        place."""
        from bench.reference import sharing as ref

        worst, failed = 0.0, 0
        for line, bw in window["kept"]:
            n, f, bs, slots = self._grid(line)
            want = ref.solve(n, f, bs, p0_factor=self.p0)
            if control:
                got = ref.solve(n, f, bs, p0_factor=self.p0,
                                dtype=np.float32)
            else:
                got = np.zeros_like(want)
                if len(bw) != len(slots):
                    got[...] = np.nan
                for at, value in zip(slots, bw):
                    got[at] = value
            gap = float(ref.gaps(got[None], want[None])[0])
            worst = max(worst, gap)
            failed += not gap <= self.limits["bw_gap"]
        if not window["kept"]:
            worst = math.inf
        checks = {"bw_gap": {"value": worst,
                             "limit": self.limits["bw_gap"]}}
        for name in ("error_lines", "unanswered_lines", "order_errors"):
            checks[name] = {"value": window[name],
                            "limit": self.limits[name]}
            failed += window[name]
        return checks, window["sent_lines"], failed


if __name__ == "__main__":
    sys.exit(client_main())
