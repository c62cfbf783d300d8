"""Placed design-space sweeps: one compiled ``PlacedBatchPlan`` over B
thread placements on a multi-domain node, re-run many times.

Set-up draws placements from the seed (1 to ``max_groups`` Table II
kernel groups of 1 to ``max_threads`` threads per scenario, each on a
random domain within its core capacity), compiles the first B of them
once with ``api.compile`` and warms the solver.  What each window call
swaps into the plan is the traffic's ``swap``:

* ``numbers``: set-up also draws pools of new numbers for the same
  lanes: ``pool`` arrays each of thread counts (within each domain's
  capacity), request fractions in (0, 1] and saturated bandwidths within
  the node's Table II range.  Call ``c`` runs ``plan.run(cores=, f=,
  b_s=)`` with the ``c``-th combination of the pools, so no two calls of
  a window (up to ``pool**3`` of them) solve the same batch.
* ``placements``: set-up draws a pool of ``placement_pool`` placements.
  Call ``c`` runs ``plan.run(placement=)`` on the B consecutive ones
  from an offset of ``c * STEP`` into the pool, taken round, so no two
  of ``placement_pool`` calls repack the same batch.

Each call keeps the bandwidths of ``check_scenarios`` scenarios drawn
from the seed.  Once the window has closed, each kept scenario is solved
again by the plain reference and the widest gap is compared with the
traffic's limit.
"""

from __future__ import annotations

import time

import numpy as np

from bench.drivers import annotation
from bench.reference import sharing as ref


#: Offset between the placement batches of two successive calls: odd, so
#: that it steps through every offset of a power-of-two pool.
STEP = 40503


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro import api

        node = config["node"]
        self.domains = list(node["domains"])
        self.cap = int(node["cores_per_domain"])
        self.p0 = float(config["utilization"]["p0_factor"])
        self.B = int(traffic["scenarios"])
        self.swap = traffic.get("swap", "numbers")
        self.check_scenarios = int(traffic["check_scenarios"])
        self.limits = traffic["limits"]
        self.seed = seed
        arch = node["arch"]
        self.kernels = sorted(config["kernels"])
        self.f_k = np.array([config["kernels"][k]["f"][arch]
                             for k in self.kernels])
        self.bs_k = np.array([config["kernels"][k]["b_s"][arch]
                              for k in self.kernels])
        rng = np.random.default_rng(seed)

        # Placements, and the (B, D, K) lanes they occupy: groups on one
        # domain take its lanes in placement order.
        count = self.B if self.swap == "numbers" else \
            int(traffic["placement_pool"])
        base = api.Scenario.on(arch).using(node["name"])
        D = len(self.domains)
        lanes = np.zeros((count, D), np.int64)
        threads = np.zeros((count, D), np.int64)
        drawn, first, scenarios = [], [0], []
        for b in range(count):
            sc = base
            for _ in range(rng.integers(1, traffic["max_groups"] + 1)):
                d = int(rng.integers(D))
                free = self.cap - threads[b, d]
                if not free:
                    continue
                n = int(rng.integers(1, min(traffic["max_threads"], free)
                                     + 1))
                threads[b, d] += n
                lanes[b, d] += 1
                k = int(rng.integers(len(self.kernels)))
                drawn.append((k, n, d))
                sc = sc.placed(self.kernels[k], n, self.domains[d])
            first.append(len(drawn))
            scenarios.append(sc)
        # The (kernel, threads, domain) of every group, scenario b's from
        # row first[b] on: arrays, so that the benchmark's own record adds
        # no objects for the program's garbage collector to walk.
        self.drawn = np.array(drawn, np.int64).reshape(-1, 3)
        self.first = np.array(first, np.int64)
        head = lanes[:self.B]
        self.mask = np.arange(head.max())[None, None, :] < head[:, :, None]
        self.plan = api.compile(api.ScenarioBatch.of(scenarios[:self.B]))
        if not np.array_equal(self.plan.grid.mask, self.mask):
            raise RuntimeError("the plan packed the placements onto other "
                               "lanes than the benchmark laid out")
        if self.swap == "numbers":
            self._numbers(traffic, lanes, rng)
            self.call(self.pool ** 3 - 1)   # compile and warm
        else:
            self._placements(scenarios, lanes, threads)
            self.call(-1)                   # compile and warm

    def _numbers(self, traffic, lanes, rng):
        """Pools of new numbers for the same lanes."""
        from repro.core import backend

        self.pool = P = int(traffic["pool"])
        shape = self.mask.shape
        per_lane = np.minimum(traffic["max_threads"],
                              self.cap // np.maximum(lanes, 1))[:, :, None]
        self.cores = [np.where(self.mask, rng.integers(1, per_lane + 1,
                                                       size=shape), 0)
                      .astype(np.float64) for _ in range(P)]
        self.f = [np.where(self.mask, 1.0 - rng.random(shape), 0.0)
                  for _ in range(P)]
        self.bs = [np.where(self.mask, rng.uniform(self.bs_k.min(),
                                                   self.bs_k.max(), shape),
                            0.0)
                   for _ in range(P)]
        # Every pool array must land in the solver program the warm-up
        # compiles: the recursion bound is bucketed from the largest
        # thread count of any domain.
        buckets = {backend.bucket(int(c.sum(axis=-1).max()))
                   for c in self.cores}
        if len(buckets) != 1:
            raise RuntimeError(f"the thread-count pools span the solver "
                               f"buckets {sorted(buckets)}")

    def _placements(self, scenarios, lanes, threads):
        """The pool of placement lists, doubled so that every call's B
        consecutive ones are one slice."""
        from repro import api
        from repro.core import backend

        placed = list(api.ScenarioBatch.of(scenarios).placements)
        self.pool = P = len(placed)
        if P & (P - 1) or P < self.B:
            raise ValueError(f"placement_pool {P} is not a power of two "
                             f"of at least the {self.B} scenarios")
        self.placed = placed + placed[:self.B]
        # Every call's batch must land in the solver program the warm-up
        # compiles: the same group count K and the same recursion bucket
        # of the largest thread count on a domain.  Where each comes back
        # within every B consecutive placements of the pool taken round,
        # every batch holds it.
        top = lanes.max(axis=1) == lanes.max()
        most = backend.bucket(int(threads.max()))
        full = np.array([backend.bucket(int(t)) for t in
                         threads.max(axis=1)]) == most
        for name, hit in (("group count", top), ("thread bucket", full)):
            at = np.flatnonzero(hit)
            gap = np.diff(np.concatenate([at, at[:1] + P]), prepend=0)
            if not at.size or gap[1:].max() > self.B:
                raise RuntimeError(f"some batch of the placement pool "
                                   f"misses its largest {name}")

    def start(self, c: int) -> int:
        """Offset of call ``c``'s placements in the pool."""
        return (c * STEP) % self.pool

    def arrays(self, c: int):
        """The (cores, f, b_s) arrays of window call ``c``."""
        P = self.pool
        return (self.cores[c % P], self.f[(c // P) % P],
                self.bs[(c // (P * P)) % P])

    def call(self, c: int) -> np.ndarray:
        if self.swap == "numbers":
            n, f, bs = self.arrays(c)
            pred = self.plan.run(cores=n, f=f, b_s=bs)
        else:
            i = self.start(c)
            pred = self.plan.run(placement=self.placed[i:i + self.B])
        return pred.raw.shares.bw_group

    def grids(self, c: int, idx, K: int):
        """The (n, f, b_s) lanes of scenarios ``idx`` of call ``c``, with
        ``K`` lanes to a domain, as the configuration gives them."""
        if self.swap == "numbers":
            return tuple(a[idx] for a in self.arrays(c))
        n, f, bs = (np.zeros((len(idx), len(self.domains), K))
                    for _ in range(3))
        for row, j in enumerate((self.start(c) + idx) % self.pool):
            lane = np.zeros(len(self.domains), np.int64)
            for k, threads, d in self.drawn[self.first[j]:
                                            self.first[j + 1]]:
                at = (row, d, lane[d])
                n[at], f[at], bs[at] = threads, self.f_k[k], self.bs_k[k]
                lane[d] += 1
        return n, f, bs

    def window(self, seconds: float, *, annotate: bool) -> dict:
        kept = []
        calls = 0
        t0 = time.perf_counter()
        while True:
            with annotation("bench.plan.run", annotate):
                bw = self.call(calls)
            idx = np.random.default_rng([self.seed, calls]).integers(
                0, self.B, self.check_scenarios)
            kept.append((idx, bw[idx].copy()))
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        return {"calls": calls, "seconds": time.perf_counter() - t0,
                "kept": kept}

    def end_to_end(self, window: dict) -> dict:
        return {"scenarios_per_s":
                window["calls"] * self.B / window["seconds"]}

    def info(self, window: dict) -> dict:
        return {"calls": window["calls"], "scenarios": self.B,
                "rows": self.B * len(self.domains),
                "groups": self.mask.shape[-1]}

    def release(self) -> None:
        self.plan = None

    def check(self, window: dict, *, control: bool = False):
        """Widest gap between the kept bandwidths and the reference's.
        ``control`` puts the reference in float32 in the program's place."""
        worst, failed, compared = 0.0, 0, 0
        for c, (idx, got) in enumerate(window["kept"]):
            n, f, bs = self.grids(c, idx, got.shape[-1])
            want = ref.solve(n, f, bs, p0_factor=self.p0)
            if control:
                got = ref.solve(n, f, bs, p0_factor=self.p0,
                                dtype=np.float32)
            gaps = ref.gaps(got, want)
            worst = max(worst, float(gaps.max(initial=0.0)))
            failed += int((~(gaps <= self.limits["bw_gap"])).sum())
            compared += len(idx)
        if not compared:
            worst = float("inf")
        checks = {"bw_gap": {"value": worst,
                             "limit": self.limits["bw_gap"]}}
        return checks, window["calls"] * self.B, failed
