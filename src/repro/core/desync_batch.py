"""Batched desync engine: B independent scenarios × R ranks in one run.

The scalar :class:`repro.core.desync.DesyncSimulator` advances one scenario
at a time, calling the Eq. 4–5 solver once per event step.  Every ensemble
study (noise-seed sweeps in ``runtime/straggler.py``, candidate-plan
comparisons in ``runtime/overlap_schedule.py``, the Fig. 1/3 seed averages)
re-runs it scenario by scenario, so the solver-call count — the dominant
per-step cost — scales with B.  This module keeps the *same* event
semantics but holds the state of all B scenarios in ``(B, R)`` arrays:

* per-scenario clocks ``t[b]`` advance independently (scenarios do not
  synchronize with each other — batching is purely an execution layout);
* each event step groups the in-flight kernels of *every* progressing
  scenario by ``(scenario, domain, kernel)`` and issues **one**
  :func:`repro.core.sharing.solve_batch` call for all populated
  ``(scenario, domain)`` pairs;
* retirement, collective resolution, and neighbor releases are vectorized
  masks over ``(B, R)``.

With ``B = 1`` the numpy engine performs bit-identical arithmetic in the
same order as the scalar engine and reproduces its record list exactly —
that equivalence is a tested invariant, so the scalar engine stays the
readable reference implementation.

An optional jax path (``backend="jax"``) runs the whole event loop as a
jitted ``lax.while_loop`` over fixed-shape state, for large fleets where
the per-step Python cost of the numpy path dominates.  It returns the same
``(B, R, L)`` start/end arrays (records are materialized sorted by
``(end, rank, index)``; floating-point results match the numpy path to
solver tolerance, not bitwise).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from . import backend as backend_mod
from .backend import HAVE_JAX
from .desync import (EPS, Allreduce, Idle, Item, Record, WaitNeighbors,
                     Work, durations_by_tag, skewness)
from .sharing import solve_batch
from .table2 import TABLE2, KernelSpec
from .topology import Topology
from ..obs import metrics, trace

_WORK, _ALLREDUCE, _WAITNB, _IDLE, _PAD = 0, 1, 2, 3, -1


# --------------------------------------------------------------------------
# Program encoding
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Encoded:
    """Array form of B × R programs, padded to the longest program L."""

    kind: np.ndarray      # (B, R, L) int8: item kind, _PAD past the end
    qty: np.ndarray       # (B, R, L) float64: bytes / duration_s / cost_s
    kern: np.ndarray      # (B, R, L) int32: index into kernels, -1 if none
    plen: np.ndarray      # (B, R) int32 program lengths
    tags: list            # [B][R][L] record tag strings
    kernels: tuple[str, ...]  # kernel names, sorted (index order == name order)


def _encode(programs_batch: Sequence[Sequence[Sequence[Item]]],
            specs: dict[str, KernelSpec]) -> _Encoded:
    B = len(programs_batch)
    R = len(programs_batch[0])
    L = max((len(p) for sc in programs_batch for p in sc), default=0)
    kinds = np.full((B, R, max(L, 1)), _PAD, dtype=np.int8)
    qty = np.zeros((B, R, max(L, 1)))
    kern = np.full((B, R, max(L, 1)), -1, dtype=np.int32)
    plen = np.zeros((B, R), dtype=np.int32)
    used: set[str] = set()
    for sc in programs_batch:
        for prog in sc:
            for item in prog:
                if isinstance(item, Work):
                    used.add(item.kernel)
    # Sorted by name, so sorting kernel indices == the scalar engine's
    # sort over kernel name strings.
    kernels = tuple(sorted(used))
    kern_idx = {k: i for i, k in enumerate(kernels)}
    for k in kernels:
        if k not in specs:
            raise KeyError(f"program references unknown kernel {k!r}")
    tags: list = []
    for b, sc in enumerate(programs_batch):
        sc_tags = []
        for r, prog in enumerate(sc):
            plen[b, r] = len(prog)
            row_tags = []
            for j, item in enumerate(prog):
                tag = item.tag or getattr(item, "kernel",
                                          type(item).__name__)
                row_tags.append(tag)
                if isinstance(item, Work):
                    kinds[b, r, j] = _WORK
                    qty[b, r, j] = item.bytes
                    kern[b, r, j] = kern_idx[item.kernel]
                elif isinstance(item, Allreduce):
                    kinds[b, r, j] = _ALLREDUCE
                    qty[b, r, j] = item.cost_s
                elif isinstance(item, WaitNeighbors):
                    kinds[b, r, j] = _WAITNB
                    qty[b, r, j] = item.cost_s
                elif isinstance(item, Idle):
                    kinds[b, r, j] = _IDLE
                    qty[b, r, j] = item.duration_s
                else:
                    raise TypeError(f"unknown program item {item!r}")
            sc_tags.append(row_tags)
        tags.append(sc_tags)
    return _Encoded(kind=kinds, qty=qty, kern=kern, plen=plen, tags=tags,
                    kernels=kernels)


# --------------------------------------------------------------------------
# Result
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BatchRunResult:
    """Outcome of a batched run.

    ``records[b]`` is scenario b's record list; on the numpy backend it is
    in engine emission order (identical to the scalar engine for B = 1), on
    the jax backend sorted by ``(end, rank, index)``.  ``start``/``end``
    are dense ``(B, R, L)`` views of the same data (NaN where the item was
    never retired within ``t_max``).
    """

    records: list[list[Record]]
    start: np.ndarray     # (B, R, L)
    end: np.ndarray       # (B, R, L)
    t_end: np.ndarray     # (B,) final per-scenario clocks
    n_steps: int          # event-loop iterations executed
    backend: str
    #: Per-scenario deadlock mask (``on_deadlock="mask"``): ``failed[b]``
    #: is True when scenario b deadlocked; its records stop at the
    #: deadlock point while every other scenario ran to completion.
    failed: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, dtype=bool))

    @property
    def n_scenarios(self) -> int:
        return len(self.records)

    @property
    def n_failed(self) -> int:
        return int(self.failed.sum())

    @property
    def n_ranks(self) -> int:
        return self.start.shape[1]

    @property
    def n_events(self) -> int:
        """Total retirements across the batch (the benchmark's 'events')."""
        return sum(len(rs) for rs in self.records)

    def _is_failed(self, b: int) -> bool:
        return bool(self.failed[b]) if b < self.failed.size else False

    def durations_by_tag(self, b: int, tag: str, *, missing: float = 0.0,
                         allow_failed: bool = False) -> list[float]:
        """Per-rank accumulated ``tag`` time in scenario ``b`` (all R ranks,
        never silently truncated).  A deadlocked scenario's records stop
        at the deadlock point, so aggregating them would silently skew
        downstream statistics — asking for one raises unless
        ``allow_failed=True``."""
        if self._is_failed(b) and not allow_failed:
            raise ValueError(
                f"scenario {b} deadlocked (see BatchRunResult.failed); "
                f"its records are partial — pass allow_failed=True to "
                f"aggregate them anyway")
        return durations_by_tag(self.records[b], tag,
                                n_ranks=self.n_ranks, missing=missing)

    def skew_by_tag(self, tag: str) -> np.ndarray:
        """Fisher skewness of per-rank accumulated ``tag`` time, one entry
        per scenario — the paper's desync/resync indicator over the whole
        ensemble.  Deadlocked scenarios yield NaN (their records are
        partial), so they cannot silently bias an ensemble mean."""
        return np.array([
            float("nan") if self._is_failed(b)
            else skewness(self.durations_by_tag(b, tag))
            for b in range(self.n_scenarios)])


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------


def run_batch(programs_batch: Sequence[Sequence[Sequence[Item]]], arch: str,
              specs: dict[str, KernelSpec] | None = None, *,
              topology: Topology | None = None,
              placement: Sequence[str] | None = None,
              t_max: float = 10.0, backend: str = "numpy",
              on_deadlock: str = "mask") -> BatchRunResult:
    """Simulate B scenarios of R ranks each in one batched run.

    Arguments mirror :class:`repro.core.desync.DesyncSimulator` plus the
    leading batch axis: ``programs_batch[b][r]`` is rank r's program in
    scenario b.  All scenarios share R, ``topology``, and ``placement``
    (vary programs — noise draws, phase mixes, skew injections — across
    scenarios; a placement sweep is a topology-per-batch concern that the
    per-scenario clocks do not require).

    ``backend="numpy"`` (default) is the reference batched engine;
    ``"jax"`` lowers the event loop to a jitted ``lax.while_loop``.

    ``on_deadlock`` controls what a deadlocked scenario does to the rest
    of the batch: ``"mask"`` (default) freezes only the deadlocked
    scenario — its records stop at the deadlock point and its entry in
    :attr:`BatchRunResult.failed` is set — while every other scenario
    runs to completion; ``"raise"`` aborts the whole run with
    :class:`RuntimeError`, like the scalar engine (callers whose
    downstream statistics would be silently skewed by a missing scenario
    opt into this).
    """
    specs = dict(TABLE2 if specs is None else specs)
    programs_batch = [list(sc) for sc in programs_batch]
    if not programs_batch:
        if on_deadlock not in ("mask", "raise"):
            raise ValueError(f"unknown on_deadlock mode {on_deadlock!r}")
        return BatchRunResult(records=[], start=np.zeros((0, 0, 1)),
                              end=np.zeros((0, 0, 1)), t_end=np.zeros(0),
                              n_steps=0,
                              backend=backend_mod.resolve(
                                  backend, 0, prefer="numpy"),
                              failed=np.zeros(0, dtype=bool))
    placement = validate_batch(programs_batch, topology, placement)
    enc = _encode(programs_batch, specs)
    return run_encoded(enc, arch, specs, placement=placement, t_max=t_max,
                       backend=backend, on_deadlock=on_deadlock)


def validate_batch(programs_batch: Sequence[Sequence[Sequence[Item]]],
                   topology: Topology | None,
                   placement: Sequence[str] | None) -> tuple[str, ...]:
    """Shared input validation for :func:`run_batch` and the compiled
    plans (:mod:`repro.api.plan`): the batch must be rectangular,
    topology and placement come together, and every placed domain must
    exist.  Returns the normalized placement (the anonymous single
    domain when unplaced) — the one contract both entry paths enforce,
    so a rule added here applies to both."""
    n_ranks = len(programs_batch[0])
    for b, sc in enumerate(programs_batch):
        if len(sc) != n_ranks:
            raise ValueError(
                f"scenario {b} has {len(sc)} ranks, scenario 0 has "
                f"{n_ranks}; the batch must be rectangular")
    if (topology is None) != (placement is None):
        raise ValueError("topology and placement must be given together")
    if topology is not None:
        if len(placement) != n_ranks:
            raise ValueError(
                f"placement names {len(placement)} domains for "
                f"{n_ranks} ranks")
        for dom in placement:
            topology.domain(dom)
    return (tuple(placement) if placement is not None
            else ("domain0",) * n_ranks)


def run_encoded(enc: _Encoded, arch: str,
                specs: dict[str, KernelSpec], *,
                placement: Sequence[str], t_max: float = 10.0,
                backend: str = "numpy",
                on_deadlock: str = "mask") -> BatchRunResult:
    """Run an already-encoded program batch (the compiled-plan entry).

    :func:`run_batch` validates, encodes, and delegates here; a
    compiled execution plan (:mod:`repro.api.plan`) keeps the
    :class:`_Encoded` arrays from its trace and re-enters here on every
    ``run()``, skipping the per-call Python encoding walk.  ``backend``
    accepts ``"auto"`` and resolves through the substrate with the
    numpy-preferring policy (the numpy event loop is the reference
    implementation; jax runs on explicit request).
    """
    if on_deadlock not in ("mask", "raise"):
        raise ValueError(f"unknown on_deadlock mode {on_deadlock!r}")
    resolved = backend_mod.resolve(backend, enc.kind.shape[0],
                                   prefer="numpy")
    placement = tuple(placement)
    engine = _run_numpy if resolved == "numpy" else _run_jax
    if not trace.enabled():  # hot path: no span bookkeeping
        return engine(enc, arch, specs, placement, t_max, on_deadlock)
    B, R, L = enc.kind.shape
    with trace.span("desync.run", backend=resolved, B=B, R=R, L=L) as sp:
        result = engine(enc, arch, specs, placement, t_max, on_deadlock)
        deadlocked = int(result.failed.sum())
        sp.set(n_steps=result.n_steps, deadlocked=deadlocked)
        metrics.counter("desync.steps").inc(result.n_steps)
        metrics.counter("desync.runs").inc()
        if deadlocked:
            metrics.counter("desync.deadlocked_scenarios").inc(deadlocked)
        return result


# --------------------------------------------------------------------------
# numpy engine
# --------------------------------------------------------------------------


def _arch_vectors(kernels: Sequence[str], specs, arch
                  ) -> tuple[np.ndarray, np.ndarray]:
    f_vec = np.array([specs[k].f[arch] for k in kernels], dtype=np.float64)
    bs_vec = np.array([specs[k].bs[arch] for k in kernels],
                      dtype=np.float64)
    return f_vec, bs_vec


def _domain_order(placement: Sequence[str]) -> np.ndarray:
    """Rank → domain index, indices assigned in sorted-name order (the
    scalar engine sorts domains by name when building solver rows)."""
    dom_names = sorted(set(placement))
    dom_idx = {d: i for i, d in enumerate(dom_names)}
    return np.array([dom_idx[p] for p in placement], dtype=np.int64)


def _run_numpy(enc: _Encoded, arch: str, specs, placement, t_max: float,
               on_deadlock: str = "mask") -> BatchRunResult:
    B, R, L = enc.kind.shape
    K = len(enc.kernels)
    f_vec, bs_vec = _arch_vectors(enc.kernels, specs, arch)
    dom_of_rank = _domain_order(placement)
    D = int(dom_of_rank.max()) + 1 if R else 1

    pc = np.zeros((B, R), dtype=np.int64)
    rem = np.zeros((B, R))
    ready = np.zeros((B, R))
    started = np.zeros((B, R))
    blocked = np.zeros((B, R), dtype=bool)
    releasing = np.zeros((B, R), dtype=bool)
    t = np.zeros(B)
    dead = np.zeros(B, dtype=bool)
    start_arr = np.full((B, R, L), np.nan)
    end_arr = np.full((B, R, L), np.nan)
    records: list[list[Record]] = [[] for _ in range(B)]
    n_steps = 0

    def cur(arr):
        return np.take_along_axis(
            arr, np.minimum(pc, L - 1)[..., None], axis=2)[..., 0]

    def finish(b: int, r: int, now: float) -> None:
        """Retire (b, r)'s current item at ``now`` and begin the next —
        the batched twin of the scalar engine's finish_item/begin_item."""
        l = pc[b, r]
        records[b].append(
            Record(rank=r, index=int(l), tag=enc.tags[b][r][l],
                   start=float(started[b, r]), end=float(now)))
        start_arr[b, r, l] = started[b, r]
        end_arr[b, r, l] = now
        pc[b, r] += 1
        blocked[b, r] = False
        releasing[b, r] = False
        if pc[b, r] < enc.plen[b, r]:
            started[b, r] = now
            k = enc.kind[b, r, pc[b, r]]
            q = enc.qty[b, r, pc[b, r]]
            if k == _WORK:
                rem[b, r] = q
            elif k == _IDLE:
                ready[b, r] = now + q
            else:
                blocked[b, r] = True

    # Begin every rank's first item at t = 0 (empty programs start done).
    done = pc >= enc.plen
    k0 = cur(enc.kind)
    q0 = cur(enc.qty)
    begin = ~done
    rem = np.where(begin & (k0 == _WORK), q0, rem)
    ready = np.where(begin & (k0 == _IDLE), q0, ready)
    blocked = begin & ((k0 == _ALLREDUCE) | (k0 == _WAITNB))

    active = (t < t_max) & ~done.all(axis=1) & ~dead

    while active.any():
        n_steps += 1
        done = pc >= enc.plen
        ck = np.where(done, _PAD, cur(enc.kind))
        cq = cur(enc.qty)

        # -- allreduce resolution: every rank (incl. finished ones, which
        # can never rejoin the communicator) must be blocked at one.  The
        # scenario's clock advances by the collective's cost; the scenario
        # skips this step's integration phase (the scalar `continue`).
        is_ar = (ck == _ALLREDUCE) & blocked
        resolve = active & (is_ar.sum(axis=1) == R)
        for b in np.nonzero(resolve)[0]:
            cost = cq[b][is_ar[b]].max()
            t[b] = t[b] + cost
            for r in np.nonzero(is_ar[b])[0]:
                finish(int(b), int(r), t[b])
        prog = active & ~resolve
        if not prog.any():
            done = pc >= enc.plen
            active = (t < t_max) & ~done.all(axis=1) & ~dead
            continue

        # -- satisfied neighbor waits start draining their p2p cost
        is_wn = (ck == _WAITNB) & blocked & prog[:, None]
        if is_wn.any():
            ok_left = np.ones((B, R), dtype=bool)
            ok_left[:, 1:] = (pc[:, :-1] >= pc[:, 1:]) | done[:, :-1]
            ok_right = np.ones((B, R), dtype=bool)
            ok_right[:, :-1] = (pc[:, 1:] >= pc[:, :-1]) | done[:, 1:]
            released = is_wn & ok_left & ok_right
            ready = np.where(released, t[:, None] + cq, ready)
            blocked &= ~released
            releasing |= released

        # -- one Eq. 4–5 solve across every populated (scenario, domain)
        working = (ck == _WORK) & prog[:, None]
        rate = np.zeros((B, R))
        if working.any():
            kern_c = cur(enc.kern)
            b_ix, r_ix = np.nonzero(working)
            key = (b_ix * D + dom_of_rank[r_ix]) * K + kern_c[b_ix, r_ix]
            ukeys, inv, counts = np.unique(
                key, return_inverse=True, return_counts=True)
            g_row_key = ukeys // K          # scenario*D + domain, sorted
            g_kern = ukeys % K              # sorted within each row
            rows, row_of_group = np.unique(g_row_key, return_inverse=True)
            first_of_row = np.searchsorted(g_row_key, rows)
            col_of_group = np.arange(len(ukeys)) - first_of_row[row_of_group]
            g_cols = int(col_of_group.max()) + 1
            n_arr = np.zeros((len(rows), g_cols))
            f_arr = np.zeros((len(rows), g_cols))
            bs_arr = np.zeros((len(rows), g_cols))
            n_arr[row_of_group, col_of_group] = counts
            f_arr[row_of_group, col_of_group] = f_vec[g_kern]
            bs_arr[row_of_group, col_of_group] = bs_vec[g_kern]
            batch = solve_batch(n_arr, f_arr, bs_arr, backend="numpy")
            per_core = batch.bw_per_core
            rate[b_ix, r_ix] = per_core[row_of_group[inv],
                                        col_of_group[inv]] * 1e9  # bytes/s

        # -- next event time, per scenario
        cand = np.full((B, R), np.inf)
        w_pos = working & (rate > 0)
        cand[w_pos] = rem[w_pos] / rate[w_pos]
        idle_like = ((ck == _IDLE) | releasing) & prog[:, None]
        cand = np.where(idle_like, np.maximum(ready - t[:, None], 0.0),
                        cand)
        dt = cand.min(axis=1) if R else np.full(B, np.inf)
        stuck = prog & ~np.isfinite(dt)
        if stuck.any():
            if on_deadlock == "raise":
                b = int(np.nonzero(stuck)[0][0])
                raise RuntimeError(
                    f"desync simulator deadlock at t={t[b]:.6f}s "
                    f"(scenario {b}): pcs={pc[b].tolist()}")
            dead |= stuck       # freeze only the deadlocked scenarios
            prog &= ~stuck
        dt = np.where(prog, np.maximum(dt, EPS), 0.0)
        t = np.where(prog, t + dt, t)

        # -- advance work and retire finished items
        rem = np.where(working, rem - rate * dt[:, None], rem)
        fin = np.where(prog[:, None],
                       (working & (rem <= EPS * np.maximum(1.0, cq)))
                       | (idle_like & (t[:, None] >= ready - EPS)),
                       False)
        for b, r in zip(*np.nonzero(fin)):
            finish(int(b), int(r), t[b])

        done = pc >= enc.plen
        active = (t < t_max) & ~done.all(axis=1) & ~dead

    return BatchRunResult(records=records, start=start_arr, end=end_arr,
                          t_end=t, n_steps=n_steps, backend="numpy",
                          failed=dead)


# --------------------------------------------------------------------------
# jax engine: the same event loop as a jitted lax.while_loop
# --------------------------------------------------------------------------


def _records_from_arrays(enc: _Encoded, start_arr: np.ndarray,
                         end_arr: np.ndarray) -> list[list[Record]]:
    """Materialize per-scenario record lists from dense start/end arrays,
    sorted by (end, rank, index) — a deterministic order that coincides
    with engine emission order except for exact end-time ties."""
    B, R, L = start_arr.shape
    records: list[list[Record]] = []
    for b in range(B):
        recs = []
        for r in range(R):
            for l in range(int(enc.plen[b, r])):
                if math.isfinite(end_arr[b, r, l]):
                    recs.append(Record(rank=r, index=l,
                                       tag=enc.tags[b][r][l],
                                       start=float(start_arr[b, r, l]),
                                       end=float(end_arr[b, r, l])))
        recs.sort(key=lambda rec: (rec.end, rec.rank, rec.index))
        records.append(recs)
    return records


def _build_jax_runner(B: int, R: int, L: int, K: int, D: int):
    """One jitted desync event loop for one ``(B, R, L, K, D)`` shape
    bucket.

    Every array the loop consumes — programs, placement, and the
    per-kernel ``(f, b_s)`` vectors — is an *argument* of the jitted
    runner, not a closure capture, so the substrate can cache the
    compiled executable process-wide: repeated straggler ensembles,
    pod-plan searches on one topology, and plans re-run with swapped
    kernel specs all reuse one compilation.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .sharing import _solve_single_jax

    def take(arr, pcs):
        return jnp.take_along_axis(
            arr, jnp.minimum(pcs, L - 1)[..., None], axis=2)[..., 0]

    # Every (scenario, domain) pair is one Eq. 4–5 instance over the K
    # kernels; reuse the sharing module's single-scenario jax solver
    # (the same code path solve_batch vmaps) so the two engines cannot
    # drift.  n_max = R is the static recursion bound: iterations past
    # a row's n_tot are masked no-ops, as in _solve_arrays_np.
    solver = jax.vmap(
        lambda n_, f_, bs_: _solve_single_jax(
            n_, f_, bs_, 0.5, R, mode="recursion"))

    def runner(kind, qty, kern, plen, dom, f_k, bs_k, t_max, max_steps):

        def rates_of(working, kern_c):
            """Per-rank progress rates from one batched Eq. 4–5 solve over
            the (B, D, K) occupancy tensor (engine defaults:
            utilization='recursion', p0_factor=0.5)."""
            seg = dom[None, :] * K + kern_c          # (B, R)
            seg = jnp.where(working, seg, 0)
            occ = jnp.zeros((B, D * K), jnp.float64).at[
                jnp.arange(B)[:, None], seg].add(
                    working.astype(jnp.float64))
            n = occ.reshape(B, D, K)
            _, _, _, bw = solver(
                n.reshape(B * D, K),
                jnp.broadcast_to(f_k, (B * D, K)),
                jnp.broadcast_to(bs_k, (B * D, K)))
            bw = bw.reshape(B, D, K)
            per_core = jnp.where(n > 0, bw / jnp.maximum(n, 1.0), 0.0)
            rate = per_core[jnp.arange(B)[:, None], dom[None, :],
                            jnp.clip(kern_c, 0, K - 1)] * 1e9
            return jnp.where(working, rate, 0.0)

        def step(state):
            (t, pc, rem, ready, started, blocked, releasing,
             start_a, end_a, steps, dead) = state
            done = pc >= plen
            alldone = done.all(axis=1)
            active = (t < t_max) & ~alldone & ~dead
            ck = jnp.where(done, _PAD, take(kind, pc))
            cq = take(qty, pc)

            # allreduce resolution (skips the integration phase below)
            is_ar = (ck == _ALLREDUCE) & blocked
            resolve = active & (is_ar.sum(axis=1) == R)
            cost = jnp.where(is_ar, cq, -jnp.inf).max(axis=1)
            t = jnp.where(resolve, t + cost, t)
            prog = active & ~resolve

            # neighbor releases
            is_wn = (ck == _WAITNB) & blocked & prog[:, None]
            ok_left = jnp.concatenate(
                [jnp.ones((B, 1), bool),
                 (pc[:, :-1] >= pc[:, 1:]) | done[:, :-1]], axis=1)
            ok_right = jnp.concatenate(
                [(pc[:, 1:] >= pc[:, :-1]) | done[:, 1:],
                 jnp.ones((B, 1), bool)], axis=1)
            released = is_wn & ok_left & ok_right
            ready = jnp.where(released, t[:, None] + cq, ready)
            blocked = blocked & ~released
            releasing = releasing | released

            # rates, next event, integration
            working = (ck == _WORK) & prog[:, None]
            kern_c = take(kern, pc)
            rate = rates_of(working, kern_c)
            cand = jnp.where(working & (rate > 0),
                             rem / jnp.where(rate > 0, rate, 1.0), jnp.inf)
            idle_like = ((ck == _IDLE) | releasing) & prog[:, None]
            cand = jnp.where(idle_like,
                             jnp.maximum(ready - t[:, None], 0.0), cand)
            dt = cand.min(axis=1)
            newly_dead = prog & ~jnp.isfinite(dt)
            dead = dead | newly_dead
            prog = prog & ~newly_dead
            dt = jnp.maximum(jnp.where(jnp.isfinite(dt), dt, 0.0), EPS)
            t = jnp.where(prog, t + dt, t)
            rem = jnp.where(working & prog[:, None],
                            rem - rate * dt[:, None], rem)

            # retire + record
            fin = jnp.where(prog[:, None],
                            (working & (rem <= EPS * jnp.maximum(1.0, cq)))
                            | (idle_like & (t[:, None] >= ready - EPS)),
                            False)
            fin = fin | (resolve[:, None] & is_ar)
            onehot = jnp.arange(L)[None, None, :] == pc[:, :, None]
            write = onehot & fin[:, :, None]
            start_a = jnp.where(write, started[:, :, None], start_a)
            end_a = jnp.where(write, t[:, None, None], end_a)

            # begin next items
            pc = pc + fin.astype(pc.dtype)
            done2 = pc >= plen
            began = fin & ~done2
            k2 = take(kind, pc)
            q2 = take(qty, pc)
            started = jnp.where(began, t[:, None], started)
            rem = jnp.where(began & (k2 == _WORK), q2, rem)
            ready = jnp.where(began & (k2 == _IDLE), t[:, None] + q2,
                              ready)
            blocked = jnp.where(fin,
                                began & ((k2 == _ALLREDUCE)
                                         | (k2 == _WAITNB)), blocked)
            releasing = releasing & ~fin
            return (t, pc, rem, ready, started, blocked, releasing,
                    start_a, end_a, steps + 1, dead)

        def cond(state):
            (t, pc, _, _, _, _, _, _, _, steps, dead) = state
            done = (pc >= plen).all(axis=1)
            active = (t < t_max) & ~done & ~dead
            return active.any() & (steps < max_steps)

        pc0 = jnp.zeros((B, R), jnp.int32)
        done0 = pc0 >= plen
        k0 = take(kind, pc0)
        q0 = take(qty, pc0)
        begin0 = ~done0
        state = (
            jnp.zeros(B, jnp.float64),                          # t
            pc0,
            jnp.where(begin0 & (k0 == _WORK), q0, 0.0),          # rem
            jnp.where(begin0 & (k0 == _IDLE), q0, 0.0),          # ready
            jnp.zeros((B, R), jnp.float64),                      # started
            begin0 & ((k0 == _ALLREDUCE) | (k0 == _WAITNB)),     # blocked
            jnp.zeros((B, R), bool),                             # releasing
            jnp.full((B, R, L), jnp.nan, jnp.float64),           # start
            jnp.full((B, R, L), jnp.nan, jnp.float64),           # end
            jnp.int64(0),
            jnp.zeros(B, bool),                                  # deadlock
        )
        t, pc, _, _, _, _, _, start_a, end_a, steps, dead = \
            lax.while_loop(cond, step, state)
        return t, pc, start_a, end_a, steps, dead

    return jax.jit(runner)


def _run_jax(enc: _Encoded, arch: str, specs, placement, t_max: float,
             on_deadlock: str = "mask") -> BatchRunResult:
    import jax
    import jax.numpy as jnp

    B, R, L = enc.kind.shape
    K = max(len(enc.kernels), 1)
    f_vec, bs_vec = _arch_vectors(enc.kernels, specs, arch)
    if not len(f_vec):
        f_vec = np.zeros(1)
        bs_vec = np.zeros(1)
    dom_of_rank = _domain_order(placement)
    D = int(dom_of_rank.max()) + 1 if R else 1
    # Each retiring step retires >= 1 item per active scenario (and pure
    # allreduce-resolution steps retire a full wavefront), so R*L bounds
    # the loop up to EPS-sized stutter steps near large clock values
    # (ulp(t) > EPS); the 2x margin absorbs those, and exhausting the
    # budget anyway is reported as an error below, never as silently
    # truncated records.
    max_steps = 2 * R * L + 16

    # Shape-bucket the batch and program axes so nearby ensemble / plan
    # sizes reuse one compiled executable: padded scenarios have empty
    # programs (plen 0, immediately done) and padded program slots are
    # _PAD items past every plen — both exactly neutral to the loop.
    Bb = backend_mod.bucket(B)
    Lb = backend_mod.bucket(L)
    runner = backend_mod.jitted(
        ("desync.run_batch", Bb, R, Lb, K, D),
        lambda: _build_jax_runner(Bb, R, Lb, K, D))
    with backend_mod.x64():
        with trace.span("desync.jax.put"):
            kind_p = np.full((Bb, R, Lb), _PAD, dtype=enc.kind.dtype)
            kind_p[:B, :, :L] = enc.kind
            qty_p = np.zeros((Bb, R, Lb))
            qty_p[:B, :, :L] = enc.qty
            kern_p = np.full((Bb, R, Lb), -1, dtype=enc.kern.dtype)
            kern_p[:B, :, :L] = enc.kern
            plen_p = np.zeros((Bb, R), dtype=enc.plen.dtype)
            plen_p[:B] = enc.plen
            # Waiting for the inputs to arrive keeps their copies under
            # ``put``; the runner could not start before them anyway.
            args = jax.block_until_ready((
                jnp.asarray(kind_p, jnp.int32),
                jnp.asarray(qty_p, jnp.float64),
                jnp.asarray(kern_p, jnp.int32),
                jnp.asarray(plen_p, jnp.int32),
                jnp.asarray(dom_of_rank, jnp.int32),
                jnp.asarray(f_vec, jnp.float64),
                jnp.asarray(bs_vec, jnp.float64),
                jnp.float64(t_max), jnp.int64(max_steps)))
        with trace.span("desync.jax.wait"):
            out = jax.block_until_ready(runner(*args))
        with trace.span("desync.jax.get"):
            t, pc, start_a, end_a, steps, dead = \
                tuple(np.asarray(x) for x in out)
            t, pc, dead = t[:B], pc[:B], dead[:B]
            start_a, end_a = start_a[:B, :, :L], end_a[:B, :, :L]

    if dead.any() and on_deadlock == "raise":
        b = int(np.nonzero(dead)[0][0])
        raise RuntimeError(
            f"desync simulator deadlock at t={t[b]:.6f}s "
            f"(scenario {b}): pcs={pc[b].tolist()}")
    still_active = (t < t_max) & ~dead \
        & ~(pc >= np.asarray(enc.plen)).all(axis=1)
    if still_active.any():
        b = int(np.nonzero(still_active)[0][0])
        raise RuntimeError(
            f"desync jax backend exhausted its step budget "
            f"({max_steps}) with scenario {b} unfinished at "
            f"t={t[b]:.6f}s — records would be truncated; use the "
            f"numpy backend or report this as an engine bug")
    with trace.span("desync.records"):
        records = _records_from_arrays(enc, start_a, end_a)
    return BatchRunResult(
        records=records, start=start_a, end=end_a, t_end=t,
        n_steps=int(steps), backend="jax", failed=dead)


# --------------------------------------------------------------------------
# Differentiable timing twin
# --------------------------------------------------------------------------

# The event engines advance state with data-dependent control flow (the
# numpy loop branches per step; the jax path is a ``lax.while_loop``,
# which is not reverse-differentiable), so gradients cannot flow through
# a full simulation.  But each *event step's* timing is pure arithmetic
# on the Eq. 4–5 solve: a rank of group g progresses at
# ``bw_g / n_g * 1e9`` bytes/s (see ``rates_of`` above), so co-running
# groups with no intervening retirement finish their work items after
#
#     t_g = bytes_g * n_g / (bw_g * 1e9)  seconds.
#
# The helpers below expose that step-timing map — and its exact jacobian
# through the share solve via :func:`repro.core.sharing.
# solve_arrays_and_grad` — for gradient-based co-design on top of the
# engine's own arithmetic.


def work_durations(n, f, bs, bytes_, **solver_kwargs) -> np.ndarray:
    """Per-rank seconds for each group to stream ``bytes_`` while all
    groups co-run — one event step of the desync engine, vectorized over
    scenarios.  All arguments broadcast to ``(B, G)``; ``solver_kwargs``
    forward to :func:`repro.core.sharing.solve_arrays` (engine defaults:
    ``utilization="recursion"``, ``p0_factor=0.5``)."""
    from .sharing import solve_arrays
    n, f, bs, bytes_ = np.broadcast_arrays(
        *(np.asarray(a, dtype=np.float64)
          for a in (n, f, bs, bytes_)))
    _, _, _, bw = solve_arrays(n, f, bs, **solver_kwargs)
    active = (n > 0) & (bytes_ > 0)
    return np.where(active,
                    bytes_ * n / (np.maximum(bw, _DUR_TINY) * 1e9), 0.0)


_DUR_TINY = 1e-300


def work_durations_and_grad(n, f, bs, bytes_, *, wrt=("f", "b_s"),
                            **grad_kwargs
                            ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """:func:`work_durations` plus exact jacobians of every duration in
    the requested solver inputs.

    Chains ``d t_i / d θ_j = -bytes_i * n_i / (bw_i**2 * 1e9) *
    d bw_i / d θ_j`` through :func:`repro.core.sharing.
    solve_arrays_and_grad` (implicit-function-theorem vjp for the
    fixed-point law, forward-mode elsewhere).  Returns ``(t, grads)``
    with ``t`` of shape ``(B, G)`` and ``grads[name][b, i, j] =
    ∂t[b, i]/∂name[b, j]``; ``grad_kwargs`` forward to the solver
    (``utilization=``, ``softmin_beta=``, ...).  Requires jax."""
    from .sharing import solve_arrays_and_grad
    n, f, bs, bytes_ = np.broadcast_arrays(
        *(np.asarray(a, dtype=np.float64)
          for a in (n, f, bs, bytes_)))
    (_, _, _, bw), bw_grads = solve_arrays_and_grad(
        n, f, bs, wrt=wrt, **grad_kwargs)
    active = (n > 0) & (bytes_ > 0)
    safe_bw = np.where(active, np.maximum(bw, _DUR_TINY), 1.0)
    t = np.where(active, bytes_ * n / (safe_bw * 1e9), 0.0)
    scale = np.where(active, -bytes_ * n / (safe_bw ** 2 * 1e9), 0.0)
    grads = {name: scale[:, :, None] * g for name, g in bw_grads.items()}
    if "cores" in grads:
        # t depends on n both through the share solve (chained above) and
        # explicitly in the numerator — the per-rank slice of the group's
        # work shrinks as agents are added.
        direct = np.where(active, bytes_ / (safe_bw * 1e9), 0.0)
        B, G = t.shape
        grads["cores"] = grads["cores"] + direct[:, :, None] * np.eye(G)
    return t, grads
