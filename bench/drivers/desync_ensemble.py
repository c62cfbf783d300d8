"""Desync ensembles of an MPI-style rank program, one ``api.compile`` and
one run per window call.

Set-up builds the configuration's program (its iteration repeated
``iterations`` times on ``ranks`` ranks placed over the node's domains)
and runs one ensemble outside the window, which compiles the engine.
Window call ``c`` adds a noise ensemble of ``ensemble`` members on a seed
drawn from ``(--seed, c)``, compiles it with ``api.compile`` (member
expansion and program encoding on the host) and runs it on the traffic's
``backend``.  Each call keeps the records of ``check_members`` members
drawn from the seed, and every call counts its members that left an item
unretired, which the configuration rules out.  Once the window has
closed, ``check_members`` of all the kept members, drawn from the seed,
are simulated again by the plain reference and the widest gap of their
records is compared with the limit; so a window of one call compares as
many members as a window of many.
"""

from __future__ import annotations

import time

import numpy as np

from bench.drivers import annotation
from bench.reference import desync as ref

#: Item codes of the reference's program arrays.
_OPS = {"work": ref.WORK, "barrier": ref.ALLREDUCE}


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro import api

        self.config = config
        self.ensemble = int(traffic["ensemble"])
        self.check_members = int(traffic["check_members"])
        self.limits = traffic["limits"]
        self.seed = seed
        node = config["node"]
        R = int(config["ranks"])
        domains = list(node["domains"])
        self.domain = np.array([r * len(domains) // R for r in range(R)])
        self.noise = config["noise"]

        sc = (api.Scenario.on(config["arch"]).using(node["name"]).ranks(R)
              .on_domains([domains[d] for d in self.domain])
              .options(backend=traffic["backend"], t_max=config["t_max_s"]))
        for _ in range(int(config["iterations"])):
            for step in config["iteration"]:
                if step["op"] == "work":
                    sc = sc.step(step["kernel"], step["bytes"],
                                 tag=step["tag"])
                else:
                    sc = sc.barrier(step["cost_s"], tag=step["tag"])
        self.template = sc
        self.call(1 << 40, annotate=False)   # compile and warm

    def noise_seed(self, c: int) -> int:
        return int(np.random.default_rng([self.seed, c]).integers(1 << 62))

    def call(self, c: int, *, annotate: bool):
        from repro import api

        sc = self.template.with_noise(
            self.noise["exp_mean_s"], seed=self.noise_seed(c),
            ensemble=self.ensemble, tag=self.noise["tag"])
        with annotation("bench.api.compile", annotate):
            plan = api.compile(sc)
        with annotation("bench.plan.run", annotate):
            return plan.run().raw

    def window(self, seconds: float, *, annotate: bool) -> dict:
        kept, records, incomplete, steps, dead = [], 0, 0, 0, 0
        calls = 0
        t0 = time.perf_counter()
        while True:
            raw = self.call(calls, annotate=annotate)
            members = np.random.default_rng([self.seed, calls, 1]).choice(
                self.ensemble, min(self.check_members, self.ensemble),
                replace=False)
            kept += [(calls, int(m), raw.start[m].copy(), raw.end[m].copy(),
                      bool(raw.failed[m]) if raw.failed.size else False)
                     for m in members]
            retired = np.isfinite(raw.end)
            records += int(retired.sum())
            incomplete += int((~retired.all(axis=(1, 2))).sum())
            steps += int(raw.n_steps)
            dead += int(raw.failed.sum())
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        return {"calls": calls, "seconds": time.perf_counter() - t0,
                "records": records, "incomplete": incomplete,
                "steps": steps, "deadlocked": dead,
                "kept": kept}

    def end_to_end(self, window: dict) -> dict:
        return {"sim_records_per_s": window["records"] / window["seconds"]}

    def info(self, window: dict) -> dict:
        return {"calls": window["calls"], "records": window["records"],
                "steps": window["steps"]}

    def release(self) -> None:
        self.template = None

    def program(self, c: int, member: int):
        """The reference's ``(R, L)`` arrays of one member's program."""
        cfg = self.config
        kernels = sorted(cfg["kernels"])
        lead = ref.member_noise(self.noise_seed(c), member, len(self.domain),
                                self.noise["exp_mean_s"])
        items = [(_OPS[s["op"]],
                  s["bytes"] if s["op"] == "work" else s["cost_s"],
                  kernels.index(s["kernel"]) if s["op"] == "work" else 0)
                 for s in cfg["iteration"]] * int(cfg["iterations"])
        R, L = len(self.domain), len(items) + 1
        kind = np.empty((R, L), np.int64)
        qty = np.empty((R, L))
        kern = np.zeros((R, L), np.int64)
        kind[:, 0], qty[:, 0] = ref.IDLE, lead
        kind[:, 1:] = [k for k, _, _ in items]
        qty[:, 1:] = [q for _, q, _ in items]
        kern[:, 1:] = [k for _, _, k in items]
        f_k = [cfg["kernels"][k]["f"] for k in kernels]
        bs_k = [cfg["kernels"][k]["b_s"] for k in kernels]
        return kind, qty, kern, f_k, bs_k

    def check(self, window: dict, *, control: bool = False):
        """Widest record gap of the kept members against the reference.
        ``control`` puts the reference in float32 in the program's place."""
        kept = window["kept"]
        pick = np.random.default_rng([self.seed, 2]).permutation(
            len(kept))[:self.check_members]
        p0 = self.config["utilization"]["p0_factor"]
        t_max = self.config["t_max_s"]
        worst, failed = 0.0, 0
        for i in sorted(pick):
            c, m, got_s, got_e, dead = kept[i]
            kind, qty, kern, f_k, bs_k = self.program(c, m)
            want_s, want_e = ref.simulate(kind, qty, kern, self.domain, f_k,
                                          bs_k, t_max=t_max, p0_factor=p0)
            if control:
                got_s, got_e = ref.simulate(kind, qty, kern, self.domain,
                                            f_k, bs_k, t_max=t_max,
                                            p0_factor=p0, dtype=np.float32)
            gap = float("inf") if dead else ref.member_gap(
                got_s, got_e, want_s, want_e)
            worst = max(worst, gap)
            failed += not gap <= self.limits["record_gap"]
        if not len(pick):
            worst = float("inf")
        # Every member retires every item of its program: a member with a
        # record missing is wrong, whether or not it was drawn above.
        checks = {"record_gap": {"value": worst,
                                 "limit": self.limits["record_gap"]},
                  "incomplete_members": {"value": window["incomplete"],
                                         "limit": 0}}
        return checks, window["calls"] * self.ensemble, \
            failed + window["incomplete"]

