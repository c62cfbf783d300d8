"""Plain reference of the rank-level desync simulation: one ensemble
member at a time, its ranks as arrays.

The semantics are those of the paper's outlook (arXiv:2011.00243, §VI)
as the system implements them: every rank runs a program of memory-bound
kernels (``work``, bytes), global collectives (``allreduce``, a cost in
seconds) and fixed delays (``idle``); kernels in flight on one contention
domain share its interface by Eqs. 4-5 (:mod:`bench.reference.sharing`),
each rank of a group progressing at ``bw_group / n_group``; the clock
jumps from one retirement to the next.  An allreduce retires when every
rank has reached it, ``cost`` after the clock at that moment.

Independent of the code under test: nothing is imported from ``repro``.
``dtype`` is the precision of every time, byte count and rate; the
float32 run is the benchmark's control.
"""

from __future__ import annotations

import math

import numpy as np

from . import sharing

WORK, ALLREDUCE, IDLE = 0, 1, 3
#: Retirement tolerance of the event loop, in seconds and in bytes.
EPS = 1e-15


def simulate(kind, qty, kern, domain, f_k, bs_k, *, t_max: float,
             p0_factor: float = 0.5, dtype=np.float64):
    """Run one member.  ``kind``/``qty``/``kern``: ``(R, L)`` item codes,
    quantities (bytes or seconds) and kernel indices into ``f_k``/``bs_k``;
    ``domain``: ``(R,)`` domain index of each rank.  Returns the ``(R, L)``
    start and end times of every retired item (NaN where none)."""
    kind = np.asarray(kind)
    qty = np.asarray(qty, dtype)
    kern = np.asarray(kern)
    domain = np.asarray(domain)
    f_k = np.asarray(f_k, dtype)
    bs_k = np.asarray(bs_k, dtype)
    R, L = kind.shape
    D, K = int(domain.max()) + 1, len(f_k)
    eps = dtype(EPS)
    ranks = np.arange(R)

    pc = np.zeros(R, np.int64)
    rem = np.zeros(R, dtype)
    ready = np.zeros(R, dtype)
    started = np.zeros(R, dtype)
    blocked = np.zeros(R, bool)
    start = np.full((R, L), np.nan, dtype)
    end = np.full((R, L), np.nan, dtype)
    t = dtype(0)

    def begin(rs, now):
        k = kind[rs, pc[rs]]
        q = qty[rs, pc[rs]]
        started[rs] = now
        rem[rs] = np.where(k == WORK, q, rem[rs])
        ready[rs] = np.where(k == IDLE, now + q, ready[rs])
        blocked[rs] = k == ALLREDUCE

    def finish(rs, now):
        start[rs, pc[rs]] = started[rs]
        end[rs, pc[rs]] = now
        pc[rs] += 1
        blocked[rs] = False
        go = rs[pc[rs] < L]
        if go.size:
            begin(go, now)

    begin(ranks, t)
    while t < t_max and not (pc >= L).all():
        live = pc < L
        ck = np.where(live, kind[ranks, np.minimum(pc, L - 1)], -1)
        cq = qty[ranks, np.minimum(pc, L - 1)]
        at_ar = (ck == ALLREDUCE) & blocked
        if at_ar.all():
            t = t + cq.max()
            finish(ranks, t)
            continue
        working = ck == WORK
        rate = np.zeros(R, dtype)
        if working.any():
            kc = kern[ranks, np.minimum(pc, L - 1)]
            counts = np.zeros((D, K), dtype)
            np.add.at(counts, (domain[working], kc[working]), 1)
            bw = sharing.solve(counts, np.broadcast_to(f_k, (D, K)),
                               np.broadcast_to(bs_k, (D, K)),
                               p0_factor=p0_factor, dtype=dtype)
            per_core = np.where(counts > 0,
                                bw / np.maximum(counts, dtype(1)), dtype(0))
            rate = np.where(working, per_core[domain, kc] * dtype(1e9),
                            dtype(0))
        idle = ck == IDLE
        cand = np.full(R, np.inf, dtype)
        moving = working & (rate > 0)
        cand[moving] = rem[moving] / rate[moving]
        cand[idle] = np.maximum(ready[idle] - t, dtype(0))
        dt = cand.min()
        if not math.isfinite(dt):
            raise RuntimeError(f"reference deadlock at t={t}")
        dt = max(dt, eps)
        t = t + dt
        rem = np.where(working, rem - rate * dt, rem)
        fin = (working & (rem <= eps * np.maximum(dtype(1), cq))) \
            | (idle & (t >= ready - eps))
        if fin.any():
            finish(ranks[fin], t)
    return start, end


def member_gap(got_start, got_end, want_start, want_end) -> float:
    """Widest gap between a member's records and the reference's, relative
    to the reference's makespan; ``inf`` where the two retired different
    items."""
    got = np.stack([np.asarray(got_start, np.float64),
                    np.asarray(got_end, np.float64)])
    want = np.stack([np.asarray(want_start, np.float64),
                     np.asarray(want_end, np.float64)])
    if got.shape != want.shape or \
            not np.array_equal(np.isnan(got), np.isnan(want)):
        return float("inf")
    makespan = float(np.nanmax(want, initial=0.0))
    if makespan <= 0:
        return float("inf")
    return float(np.nanmax(np.abs(got - want), initial=0.0) / makespan)


_M64 = (1 << 64) - 1


def member_noise(seed: int, member: int, ranks: int,
                 exp_mean_s: float) -> list[float]:
    """The start delays of one ensemble member, as ``with_noise`` defines
    them: each member draws, in rank order, from Python's
    ``random.Random`` seeded by the SplitMix64 finalizer of
    ``seed * 0x9E3779B97F4A7C15 + member + 1``."""
    import random
    z = (seed * 0x9E3779B97F4A7C15 + member + 1) & _M64
    z ^= z >> 30
    z = (z * 0xBF58476D1CE4E5B9) & _M64
    z ^= z >> 27
    z = (z * 0x94D049BB133111EB) & _M64
    rng = random.Random(z ^ (z >> 31))
    return [rng.expovariate(1.0 / exp_mean_s) for _ in range(ranks)]
