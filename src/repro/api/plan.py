"""Compiled execution plans: trace a scenario once, run it many times.

The paper's model is cheap per evaluation but is consumed in bulk —
Fig. 6–9 sweeps, calibration's profile least squares, pod-plan searches
all solve the same Eqs. 4–5 / desync structures thousands of times with
only the numbers changing.  ``predict``/``simulate`` pay the full trace
on every call: kernel-spec resolution, provenance collection, array
packing, backend resolution, (for simulations) the per-item program
encoding walk.  A *plan* pays it once::

    plan = api.compile(batch)          # trace: resolve, pack, pick backend
    pred = plan.run()                  # re-execute: just the solve
    pred = plan.run(f=f2, b_s=bs2)     # same structure, new numbers
    pred = plan.run(cores=n2)          # swap thread counts

``plan.run()`` is bit-for-bit ``api.predict(x)`` / ``api.simulate(x)``
— the one-shot verbs are themselves sugar that compiles and runs — and
``plan.run(f=..., b_s=..., cores=...)`` equals a fresh compile of the
modified scenarios, without re-tracing.

Five plan shapes mirror the engine dispatch table:

================  ========================  ============================
plan kind         compiled from             runs on
================  ========================  ============================
``scalar``        single unplaced scenario  ``sharing.predict``
                                            (reference)
``placed``        single placed scenario    ``topology.predict_placed``
``batch``         :class:`ScenarioBatch`    ``sharing.solve_arrays`` —
                                            numpy or the substrate's
                                            cached jitted solver
``placed-batch``  placed ScenarioBatch      ``sharing.
                  (one shared topology)     solve_placed_batch`` over
                                            the packed (B, D, K) grid
``simulate``      any (programs encoded;    ``desync_batch.run_encoded``
                  batch × ensemble fused)
================  ========================  ============================

Backend + jit selection happens at compile time through
:func:`repro.core.backend.resolve` (the tree's only backend policy);
the jitted solvers live in the substrate's process-wide cache keyed by
padded shape bucket, so two plans of the same bucket share one XLA
executable — see ``docs/plans.md`` for the cache-key anatomy and when
compiling pays off.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Mapping, Sequence

import numpy as np

from ..core import backend as backend_mod
from ..core import desync_batch, sharing
from ..core import topology as topology_mod
from ..core.desync import Allreduce, Idle, Item, WaitNeighbors, Work
from ..core.sharing import Group
from ..core.table2 import KernelSpec
from ..obs import trace
from .results import (BatchPrediction, PlacedBatchPrediction, Prediction,
                      Sensitivities, SimulationResult,
                      from_share_prediction, from_topology_prediction)
from .scenario import Scenario, ScenarioBatch

# ---------------------------------------------------------------------------
# Deterministic seed splitting for noise ensembles
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15  # 2^64 / phi: SplitMix64's stream constant


def derive_member_seed(seed: int, member: int) -> int:
    """Derive ensemble member ``member``'s RNG seed from the scenario's
    declared ``seed`` via a splittable counter (SplitMix64 finalizer
    over ``seed * golden + member``).

    The historical convention ``Random(seed + member)`` made adjacent
    ensembles share streams — ``(seed=0, member=1)`` and ``(seed=1,
    member=0)`` drew identical noise, silently correlating studies that
    differ only in their base seed.  The split keeps every
    ``(seed, member)`` pair on an independent, reproducible stream:
    repeated ``simulate()`` calls are deterministic by default, and two
    base seeds never alias.
    """
    z = (seed * _GOLDEN + member + 1) & _M64
    z ^= z >> 30
    z = (z * 0xBF58476D1CE4E5B9) & _M64
    z ^= z >> 27
    z = (z * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


def _noise_items(scenario: Scenario, member: int,
                 R: int) -> list[Item | None]:
    """Per-rank leading Idle items for ensemble member ``member`` —
    drawn in rank order from ``Random(derive_member_seed(seed,
    member))``, one independent stream per member."""
    noise = scenario.noise
    if noise is None:
        return [None] * R
    rng = random.Random(derive_member_seed(noise.seed, member))
    return [Idle(rng.expovariate(1.0 / noise.exp_mean_s), tag=noise.tag)
            for _ in range(R)]


def _programs_for(scenario: Scenario, member: int
                  ) -> tuple[list[list[Item]], Sequence[str] | None]:
    """One ensemble member's per-rank programs + placement."""
    if scenario.steps:
        R = scenario.n_ranks
        if R is None:
            raise ValueError("program-mode scenario never called .ranks(R)")
        lead = _noise_items(scenario, member, R)
        progs: list[list[Item]] = []
        for r in range(R):
            prog: list[Item] = [lead[r]] if lead[r] is not None else []
            for s in scenario.steps:
                if s.kind == "work":
                    prog.append(Work(s.resolved.name, s.bytes_for(r),
                                     tag=s.tag))
                elif s.kind == "barrier":
                    prog.append(Allreduce(cost_s=s.cost_s, tag=s.tag))
                elif s.kind == "halo":
                    prog.append(WaitNeighbors(cost_s=s.cost_s, tag=s.tag))
                else:
                    prog.append(Idle(s.cost_s, tag=s.tag))
            progs.append(prog)
        return progs, scenario.rank_domains
    # Group mode: each run contributes n ranks, one Work each.
    if not scenario.runs:
        raise ValueError("nothing to simulate: scenario has no groups or "
                         "steps")
    R = scenario.total_threads
    lead = _noise_items(scenario, member, R)
    progs = []
    placement: list[str] = []
    r = 0
    for run in scenario.runs:
        for _ in range(run.n):
            prog = [lead[r]] if lead[r] is not None else []
            prog.append(Work(run.resolved.name, run.bytes, tag=run.tag))
            progs.append(prog)
            placement.append(run.domain or "")
            r += 1
    has_domains = any(placement)
    if has_domains and not all(placement):
        raise ValueError(
            "either every group or no group must be placed on a domain")
    return progs, (tuple(placement) if has_domains else None)


def _collect_specs(scenarios: Sequence[Scenario]) -> dict[str, KernelSpec]:
    specs: dict[str, KernelSpec] = {}
    for sc in scenarios:
        for res in ([s.resolved for s in sc.steps if s.resolved is not None]
                    + [r.resolved for r in sc.runs]):
            prev = specs.get(res.name)
            if prev is not None and prev is not res.spec \
                    and prev != res.spec:
                raise ValueError(
                    f"two different specs named {res.name!r} in one "
                    f"simulation batch")
            specs[res.name] = res.spec
    return specs


# ---------------------------------------------------------------------------
# Plan shapes
# ---------------------------------------------------------------------------


class Plan:
    """A frozen, re-runnable trace of one scenario (or batch).

    Subclasses implement :meth:`run`; every plan exposes ``kind`` (the
    dispatch row it compiled to) and ``engine`` (the backend it will
    run on, resolved at compile time)."""

    kind: str = ""

    @property
    def engine(self) -> str:
        raise NotImplementedError

    def run(self, **overrides):
        """Re-execute the plan; see the subclass for accepted swaps."""
        raise NotImplementedError

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None):
        """Run the plan *and* differentiate it: the returned prediction
        carries a :class:`repro.api.results.Sensitivities` block with
        exact jacobians ``∂bw/∂wrt`` through the Eq. 1–5 chain (see the
        prediction-plan subclasses).  Simulation plans cannot be
        differentiated — see :meth:`SimulatePlan.grad`."""
        raise NotImplementedError(
            f"plan kind {self.kind!r} does not support grad()")


def _sensitivities_for(solver_options: Mapping, grads: dict,
                       wrt, softmin_beta) -> Sensitivities:
    return Sensitivities(
        wrt=tuple(wrt), jacobians=grads,
        utilization=solver_options.get("utilization", "recursion"),
        softmin_beta=softmin_beta)


def _swap_scalar(value, name: str, G: int):
    if value is None:
        return [None] * G
    values = list(value) if isinstance(value, (Sequence, np.ndarray)) \
        else [value] * G
    if len(values) != G:
        raise ValueError(
            f"{name} gives {len(values)} values for the plan's {G} "
            f"groups")
    return values


def _swap_groups(groups: tuple[Group, ...], cores, f, b_s
                 ) -> tuple[Group, ...]:
    G = len(groups)
    ns = _swap_scalar(cores, "cores", G)
    fs = _swap_scalar(f, "f", G)
    bss = _swap_scalar(b_s, "b_s", G)
    out = []
    for g, n_, f_, bs_ in zip(groups, ns, fs, bss):
        if n_ is not None or f_ is not None or bs_ is not None:
            g = dataclasses.replace(
                g, n=int(n_) if n_ is not None else g.n,
                f=float(f_) if f_ is not None else g.f,
                bs=float(bs_) if bs_ is not None else g.bs)
        out.append(g)
    return tuple(out)


@dataclasses.dataclass(frozen=True, eq=False)
class ScalarPlan(Plan):
    """Single unplaced scenario → the scalar reference solver."""

    kind = "scalar"
    arch: str
    groups: tuple[Group, ...]
    provenance: tuple[str, ...]
    solver_options: dict

    @property
    def engine(self) -> str:
        return "scalar"

    def run(self, *, cores=None, f=None, b_s=None, backend=None,
            jax_cutoff=None, chunk=None) -> Prediction:
        """Re-solve; ``cores``/``f``/``b_s`` swap per-group numbers
        (scalar or length-G sequence).  ``backend`` is accepted for
        signature uniformity — the scalar path *is* the reference
        implementation and always runs it."""
        with trace.span("api.plan.run", kind=self.kind, engine="scalar"):
            groups = self.groups \
                if cores is None and f is None and b_s is None \
                else _swap_groups(self.groups, cores, f, b_s)
            pred = sharing.predict(groups, **self.solver_options)
            return from_share_prediction(pred, arch=self.arch,
                                         provenance=self.provenance,
                                         engine="scalar")

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None) -> Prediction:
        """Solve and differentiate: jacobians ``∂bw_i/∂wrt_j`` of shape
        ``(G, G)`` per requested input, attached as
        ``prediction.sensitivities`` (forward values are the unchanged
        scalar solve).  Requires jax; ``softmin_beta`` smooths the
        saturation min on the gradient path only."""
        n = np.array([[float(g.n) for g in self.groups]])
        f = np.array([[g.f for g in self.groups]])
        bs = np.array([[g.bs for g in self.groups]])
        _, grads = sharing.solve_arrays_and_grad(
            n, f, bs, wrt=wrt, softmin_beta=softmin_beta,
            **self.solver_options)
        sens = _sensitivities_for(
            self.solver_options, {k: v[0] for k, v in grads.items()},
            wrt, softmin_beta)
        return dataclasses.replace(self.run(), sensitivities=sens)


@dataclasses.dataclass(frozen=True, eq=False)
class PlacedPlan(Plan):
    """Single topology-placed scenario → the per-domain solver."""

    kind = "placed"
    arch: str
    topo: topology_mod.Topology
    placements: tuple[topology_mod.Placed, ...]
    provenance: tuple[str, ...]
    solver_kwargs: dict        # utilization/p0/saturated/backend/strict

    @property
    def engine(self) -> str:
        return "topology"

    def run(self, *, cores=None, f=None, b_s=None, backend=None,
            jax_cutoff=None, chunk=None) -> Prediction:
        with trace.span("api.plan.run", kind=self.kind, engine="topology"):
            placements = self.placements
            if cores is not None or f is not None or b_s is not None:
                groups = _swap_groups(
                    tuple(p.group for p in placements), cores, f, b_s)
                placements = tuple(
                    topology_mod.Placed(g, p.domain)
                    for g, p in zip(groups, placements))
            kwargs = dict(self.solver_kwargs)
            if backend is not None:
                kwargs["backend"] = backend
            if jax_cutoff is not None:
                kwargs["jax_cutoff"] = jax_cutoff
            if chunk is not None:
                kwargs["chunk"] = chunk
            pred = topology_mod.predict_placed(self.topo, placements,
                                               **kwargs)
            return from_topology_prediction(pred, arch=self.arch,
                                            provenance=self.provenance)

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None) -> Prediction:
        """Solve and differentiate the placed scenario: jacobians of
        shape ``(D, K, K)`` in grid coordinates (domain, occupancy
        slot — the packing order of :func:`repro.core.topology.
        pack_placed`), attached as ``prediction.sensitivities``.
        Requires jax."""
        grid = topology_mod.pack_placed(
            self.topo, [self.placements],
            strict=self.solver_kwargs.get("strict", True))
        solver_options = {k: v for k, v in self.solver_kwargs.items()
                          if k in ("utilization", "p0_factor", "saturated")}
        _, grads = sharing.solve_placed_and_grad(
            grid.n, grid.f, grid.bs, mask=grid.mask, wrt=wrt,
            softmin_beta=softmin_beta, **solver_options)
        sens = _sensitivities_for(
            solver_options, {k: v[0] for k, v in grads.items()},
            wrt, softmin_beta)
        return dataclasses.replace(self.run(), sensitivities=sens)


def _swap_array(base: np.ndarray, value, name: str) -> np.ndarray:
    if value is None:
        return base
    arr = np.asarray(value, dtype=np.float64)
    try:
        return np.broadcast_to(arr, base.shape)
    except ValueError:
        raise ValueError(
            f"{name} has shape {arr.shape}, not broadcastable to the "
            f"plan's (B, G) = {base.shape}") from None


@dataclasses.dataclass(frozen=True, eq=False)
class BatchPlan(Plan):
    """B scenarios packed once → the batched array solver.

    The trace froze the padded ``(B, G)`` arrays, the per-row arch /
    provenance labels, and the resolved backend; ``run`` goes straight
    to :func:`repro.core.sharing.solve_arrays` — no re-validation, no
    re-packing, and on the jax backend the substrate's cached jitted
    solver (one compile per padded shape bucket, process-wide).
    """

    kind = "batch"
    archs: tuple[str, ...]
    n: np.ndarray
    f: np.ndarray
    bs: np.ndarray
    names: tuple[tuple[str, ...], ...]
    provenance: tuple[tuple[str, ...], ...]
    solver_options: dict
    backend: str               # resolved at compile time
    requested_backend: str     # what the scenarios asked for
    jax_cutoff: int | None
    chunk: int | None

    def __len__(self) -> int:
        return self.n.shape[0]

    @property
    def engine(self) -> str:
        return self.backend

    @property
    def bucket(self) -> tuple[int, int]:
        """The padded jit-cache shape bucket this plan solves in."""
        return (backend_mod.bucket(len(self)), self.n.shape[1])

    def run(self, *, cores=None, f=None, b_s=None, backend=None,
            jax_cutoff=None, chunk=None) -> BatchPrediction:
        """Re-solve the batch.  ``cores``/``f``/``b_s`` swap the packed
        arrays (anything broadcastable to ``(B, G)``); ``backend`` /
        ``jax_cutoff`` / ``chunk`` re-resolve dispatch for this run
        only.  Equal to a fresh ``compile(...).run()`` of the modified
        scenarios, bit for bit."""
        with trace.span("api.plan.run", kind=self.kind, B=len(self)) as sp:
            n_arr = _swap_array(self.n, cores, "cores")
            f_arr = _swap_array(self.f, f, "f")
            bs_arr = _swap_array(self.bs, b_s, "b_s")
            if backend is None and jax_cutoff is None:
                resolved = self.backend
            else:
                resolved = backend_mod.resolve(
                    backend or self.requested_backend, len(self),
                    jax_cutoff=jax_cutoff if jax_cutoff is not None
                    else self.jax_cutoff)
            sp.set(engine=resolved)
            b, alphas, util, bw = sharing.solve_arrays(
                n_arr, f_arr, bs_arr, backend=resolved,
                chunk=chunk if chunk is not None else self.chunk,
                **self.solver_options)
            raw = sharing.BatchSharePrediction(
                n=n_arr, f=f_arr, bs=bs_arr, b_overlap=b, alphas=alphas,
                util=util, bw_group=bw, names=self.names)
            return BatchPrediction(archs=self.archs, engine=resolved,
                                   raw=raw, provenance=self.provenance)

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None
             ) -> BatchPrediction:
        """Solve and differentiate the whole batch: jacobians
        ``∂bw[b, i]/∂wrt[b, j]`` of shape ``(B, G, G)`` per requested
        input, attached as ``prediction.sensitivities``.  Runs on the
        substrate's jit-bucket cache (requires jax); ``softmin_beta``
        smooths the saturation min on the gradient path only."""
        _, grads = sharing.solve_arrays_and_grad(
            self.n, self.f, self.bs, wrt=wrt, softmin_beta=softmin_beta,
            **self.solver_options)
        sens = _sensitivities_for(self.solver_options, grads, wrt,
                                  softmin_beta)
        return dataclasses.replace(self.run(), sensitivities=sens)


@dataclasses.dataclass(frozen=True, eq=False)
class PlacedBatchPlan(Plan):
    """B placements on one topology packed once → the grid solver.

    The trace paid placement validation and the ``(B, D, K)`` grid
    packing (:func:`repro.core.topology.pack_placed`); ``run`` goes
    straight to :func:`repro.core.sharing.solve_placed_batch`, which
    flattens to ``(B·D, K)`` rows — the same padded power-of-two
    buckets (and the same process-wide jitted solver cache) the
    unplaced :class:`BatchPlan` uses.
    """

    kind = "placed-batch"
    archs: tuple[str, ...]
    grid: topology_mod.PlacedGrid
    provenance: tuple[tuple[str, ...], ...]
    solver_options: dict
    backend: str               # resolved at compile time
    requested_backend: str
    strict: bool
    jax_cutoff: int | None
    chunk: int | None

    def __len__(self) -> int:
        return len(self.grid)

    @property
    def topo(self) -> topology_mod.Topology:
        return self.grid.topology

    @property
    def engine(self) -> str:
        return self.backend

    @property
    def bucket(self) -> tuple[int, int]:
        """The padded jit-cache shape bucket of the flattened solve:
        ``(bucket(B·D), K)`` — two placed sweeps of different raggedness
        that land in one bucket share one compiled solver."""
        B, D, K = self.grid.n.shape
        return (backend_mod.bucket(B * D), K)

    def _dispatch(self, backend, jax_cutoff) -> str:
        if backend is None and jax_cutoff is None:
            return self.backend
        B, D, _ = self.grid.n.shape
        return backend_mod.resolve(
            backend or self.requested_backend, B * D,
            jax_cutoff=jax_cutoff if jax_cutoff is not None
            else self.jax_cutoff)

    def run(self, *, cores=None, f=None, b_s=None, placement=None,
            backend=None, jax_cutoff=None, chunk=None
            ) -> PlacedBatchPrediction:
        """Re-solve the placed batch.

        ``cores``/``f``/``b_s`` swap grid numbers (anything
        broadcastable to the padded ``(B, D, K)``; padding lanes stay
        masked out regardless of what the broadcast writes there).
        ``placement`` swaps the whole placement batch — a sequence of
        B placement lists (:class:`repro.core.topology.Placed`) on the
        plan's topology, re-packed without re-tracing the scenarios onto
        at least the plan's K lanes per domain, so a batch of fewer
        groups per domain runs the solver the plan compiled for.
        ``backend``/``jax_cutoff``/``chunk`` re-resolve dispatch for
        this run only.
        """
        with trace.span("api.plan.run", kind=self.kind,
                        B=len(self)) as sp:
            return self._run_traced(sp, cores, f, b_s, placement, backend,
                                    jax_cutoff, chunk)

    def _run_traced(self, sp, cores, f, b_s, placement, backend,
                    jax_cutoff, chunk) -> PlacedBatchPrediction:
        grid = self.grid
        if placement is not None:
            placement = [tuple(p) for p in placement]
            if len(placement) != len(self):
                raise ValueError(
                    f"placement gives {len(placement)} scenarios for the "
                    f"plan's {len(self)}")
            with trace.span("api.plan.pack"):
                grid = topology_mod.pack_placed(
                    self.topo, placement, strict=self.strict,
                    lanes=self.grid.n.shape[2])
        n_arr = _swap_array(grid.n, cores, "cores")
        f_arr = _swap_array(grid.f, f, "f")
        bs_arr = _swap_array(grid.bs, b_s, "b_s")
        resolved = self._dispatch(backend, jax_cutoff)
        sp.set(engine=resolved)
        shares = sharing.solve_placed_batch(
            n_arr, f_arr, bs_arr, mask=grid.mask, backend=resolved,
            chunk=chunk if chunk is not None else self.chunk,
            **self.solver_options)
        raw = topology_mod.TopologyBatchPrediction(grid=grid, shares=shares)
        prov = self.provenance
        if placement is not None:
            # Swapped placements may change per-scenario group counts;
            # keep labels where they still line up, "" beyond.
            prov = tuple(
                tuple(row[j] if j < len(row) else ""
                      for j in range(len(pl)))
                for row, pl in zip(prov, placement))
        return PlacedBatchPrediction(archs=self.archs, engine=resolved,
                                     raw=raw, provenance=prov)

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None
             ) -> PlacedBatchPrediction:
        """Solve and differentiate the placed batch: jacobians of shape
        ``(B, D, K, K)`` in grid coordinates per requested input, with
        masked (padding) lanes exactly zero, attached as
        ``prediction.sensitivities``.  Requires jax."""
        grid = self.grid
        _, grads = sharing.solve_placed_and_grad(
            grid.n, grid.f, grid.bs, mask=grid.mask, wrt=wrt,
            softmin_beta=softmin_beta, **self.solver_options)
        sens = _sensitivities_for(self.solver_options, grads, wrt,
                                  softmin_beta)
        return dataclasses.replace(self.run(), sensitivities=sens)


@dataclasses.dataclass(frozen=True, eq=False)
class SimulatePlan(Plan):
    """B member programs encoded once → the desync event engine.

    The trace paid the member expansion (noise draws included — a plan
    re-runs the *same* draws), the per-item encoding walk, and the
    placement/topology validation; ``run`` re-enters the engine through
    :func:`repro.core.desync_batch.run_encoded`.  On the jax backend
    the compiled ``lax.while_loop`` runner is shared process-wide per
    shape bucket, so re-running (or re-compiling a same-shaped
    ensemble) never recompiles.
    """

    kind = "simulate"
    arch: str
    enc: "desync_batch._Encoded"
    specs: dict[str, KernelSpec]
    placement: tuple[str, ...]
    t_max_default: float
    t_max_conflict: tuple | None   # (i, t_i, t_0) of first mismatch
    requested_backend: str
    n_members: int
    #: Fused batch×ensemble row origin: ``members[b] == (scenario,
    #: member)``; None when rows map 1:1 to input scenarios.
    members: tuple[tuple[int, int], ...] | None = None

    def __len__(self) -> int:
        return self.n_members

    @property
    def engine(self) -> str:
        resolved = backend_mod.resolve(self.requested_backend,
                                       self.n_members, prefer="numpy")
        return f"desync-{resolved}"

    def run(self, *, t_max: float | None = None, backend: str | None = None,
            on_deadlock: str = "mask",
            specs: Mapping[str, object] | None = None) -> SimulationResult:
        """Re-simulate.  ``t_max`` / ``backend`` / ``on_deadlock``
        override the compiled defaults; ``specs`` swaps kernel
        ``(f, b_s)`` numbers by name (a :class:`KernelSpec`, an
        ``(f, bs)`` pair, or a calibration mapping — anything the
        registry resolves) without re-encoding the programs."""
        with trace.span("api.plan.run", kind=self.kind,
                        B=self.n_members) as sp:
            if t_max is None:
                if self.t_max_conflict is not None:
                    i, t_i, t_0 = self.t_max_conflict
                    raise ValueError(
                        f"scenario {i} sets t_max={t_i} but scenario 0 "
                        f"sets {t_0}; a batch runs on one clock horizon "
                        f"(or pass t_max= to simulate() explicitly)")
                t_max = self.t_max_default
            merged = self.specs
            if specs:
                from .registry import resolve as registry_resolve
                from .registry import unknown_key_error
                merged = dict(self.specs)
                for name, ref in specs.items():
                    if name not in merged:
                        # A typo'd kernel name would otherwise make the
                        # swap a silent no-op.
                        raise unknown_key_error("kernel", name,
                                                sorted(merged))
                    merged[name] = registry_resolve(
                        ref, arch=self.arch, name=name).spec
            resolved = backend_mod.resolve(
                backend or self.requested_backend, self.n_members,
                prefer="numpy")
            sp.set(engine=f"desync-{resolved}")
            res = desync_batch.run_encoded(
                self.enc, self.arch, merged, placement=self.placement,
                t_max=t_max, backend=resolved, on_deadlock=on_deadlock)
            return SimulationResult(arch=self.arch,
                                    engine=f"desync-{resolved}", raw=res,
                                    members=self.members)

    def grad(self, *, wrt=("f", "b_s"), softmin_beta=None):
        """Simulations are not reverse-differentiable: the event loop
        branches on data (the jax engine is a ``lax.while_loop``), so
        no gradient flows through a full run.  Differentiate a
        prediction plan instead, or use :func:`repro.core.desync_batch.
        work_durations_and_grad` for the timing of one event step."""
        raise NotImplementedError(
            "simulate plans cannot be differentiated: the desync event "
            "loop branches on data (lax.while_loop on the jax backend). "
            "Use a predict plan's grad(), or "
            "repro.core.desync_batch.work_durations_and_grad for "
            "one event step's timing jacobians.")


# ---------------------------------------------------------------------------
# Cache-key hooks: structural fingerprints for plan reuse
# ---------------------------------------------------------------------------


def infer_verb(scenario: "Scenario | ScenarioBatch") -> str:
    """The engine family :func:`compile` would pick for ``scenario`` when
    no ``verb`` is given: ``"simulate"`` for program-mode scenarios and
    noise ensembles, ``"predict"`` for group-mode scenarios.  Exposed so
    callers that route requests *before* compiling — the serving
    subsystem's coalescer (:mod:`repro.serve`) — cannot drift from the
    compile-time inference."""
    if isinstance(scenario, ScenarioBatch):
        is_program = any(sc.steps or sc.noise is not None
                         for sc in scenario.scenarios)
    else:
        is_program = isinstance(scenario, Scenario) and (
            bool(scenario.steps) or scenario.noise is not None)
    return "simulate" if is_program else "predict"


def _topology_fingerprint(topo) -> tuple | None:
    """Hashable stand-in for a topology in structure keys.

    ``Topology`` objects embed machine models with dict-valued fields,
    so they are not hashable themselves; everything the *solvers* read
    from a topology is the ordered set of domain names and capacities,
    which is exactly what the fingerprint keeps."""
    if topo is None:
        return None
    return (topo.name, tuple((d.name, int(d.n_cores))
                             for d in topo.domains))


def _options_signature(sc: "Scenario") -> tuple:
    return (tuple(sorted(sc.solver_options().items())), sc.backend,
            sc.jax_cutoff, sc.chunk, sc.strict)


def node_key(scenario: "Scenario", *, verb: str | None = None) -> tuple:
    """The leading part of :func:`structure_key`: ``(verb, arch,
    options, topology fingerprint)``, which a placed plan's
    ``placement=`` swap leaves fixed, so every placed prediction with
    an equal node key can run on one placed plan."""
    if verb is None:
        verb = infer_verb(scenario)
    return (verb, scenario.arch, _options_signature(scenario),
            _topology_fingerprint(scenario.topo))


def structure_key(scenario: "Scenario | ScenarioBatch", *,
                  verb: str | None = None) -> tuple:
    """A hashable fingerprint of everything :func:`compile` *traces* —
    the plan-cache hook behind :mod:`repro.serve`.

    Two scenarios with equal keys compile to interchangeable plans:

    * ``verb="predict"`` keys record the structure only — arch, solver /
      dispatch options, topology fingerprint, and per-group ``(tag,
      kernel name, provenance, domain)`` — and deliberately **exclude
      the numeric payload** (``n``, ``f``, ``b_s``).  A cached plan for
      the key serves any same-structured scenario through
      ``plan.run(cores=..., f=..., b_s=...)`` (or a ``placement=`` swap
      on the placed path), which is the serving plan cache's contract.
    * ``verb="simulate"`` keys include the numbers, byte counts, noise
      block, and step sequence: the desync engine encodes programs (and
      draws noise) at compile time, so only structurally *identical*
      scenarios share a simulation plan.

    A :class:`ScenarioBatch` keys as the tuple of its scenarios' keys.
    ``verb=None`` infers the engine family via :func:`infer_verb`.
    """
    if isinstance(scenario, ScenarioBatch):
        return tuple(structure_key(sc, verb=verb)
                     for sc in scenario.scenarios)
    if not isinstance(scenario, Scenario):
        raise TypeError(
            f"structure_key takes a Scenario or ScenarioBatch, got "
            f"{type(scenario).__name__}")
    sc = scenario
    if verb is None:
        verb = infer_verb(sc)
    if verb not in ("predict", "simulate"):
        raise ValueError(
            f"unknown verb {verb!r}; expected 'predict' or 'simulate'")
    node = node_key(sc, verb=verb)
    if verb == "predict":
        rows = tuple((r.tag, r.resolved.name, r.resolved.provenance,
                      r.domain) for r in sc.runs)
        return node + (rows,)
    runs = tuple(
        (r.tag, r.resolved.name, r.resolved.provenance, r.domain,
         int(r.n), float(r.bytes), float(r.spec.f[sc.arch]),
         float(r.spec.bs[sc.arch])) for r in sc.runs)
    steps = tuple(
        (s.kind, s.tag,
         s.resolved.name if s.resolved is not None else None,
         s.resolved.provenance if s.resolved is not None else None,
         s.bytes, s.cost_s,
         float(s.resolved.spec.f[sc.arch])
         if s.resolved is not None else None,
         float(s.resolved.spec.bs[sc.arch])
         if s.resolved is not None else None) for s in sc.steps)
    noise = None if sc.noise is None else (
        sc.noise.exp_mean_s, sc.noise.seed, sc.noise.ensemble,
        sc.noise.tag)
    return node + (runs, steps, noise, sc.n_ranks, sc.rank_domains,
                   sc.t_max)


# ---------------------------------------------------------------------------
# compile(): the one-time trace
# ---------------------------------------------------------------------------


def _compile_predict(scenario) -> Plan:
    if isinstance(scenario, ScenarioBatch):
        with trace.span("api.compile.validate"):
            scenario.predictable  # cached O(B) validation; raises on misuse
        first = scenario.scenarios[0]
        if scenario.is_placed:
            with trace.span("api.compile.pack", B=len(scenario)):
                grid = topology_mod.pack_placed(
                    first.topo, scenario.placements, strict=first.strict)
            B, D, _ = grid.n.shape
            resolved = backend_mod.resolve(first.backend, B * D,
                                           jax_cutoff=first.jax_cutoff)
            return PlacedBatchPlan(
                archs=scenario.archs, grid=grid,
                provenance=scenario.provenance,
                solver_options=first.solver_options(),
                backend=resolved, requested_backend=first.backend,
                strict=first.strict, jax_cutoff=first.jax_cutoff,
                chunk=first.chunk)
        with trace.span("api.compile.pack", B=len(scenario)):
            n, f, bs, names = scenario.arrays
        resolved = backend_mod.resolve(first.backend, len(scenario),
                                       jax_cutoff=first.jax_cutoff)
        return BatchPlan(archs=scenario.archs, n=n, f=f, bs=bs,
                         names=names, provenance=scenario.provenance,
                         solver_options=first.solver_options(),
                         backend=resolved,
                         requested_backend=first.backend,
                         jax_cutoff=first.jax_cutoff, chunk=first.chunk)
    if not isinstance(scenario, Scenario):
        raise TypeError(
            f"predict() takes a Scenario or ScenarioBatch, got "
            f"{type(scenario).__name__}")
    if scenario.steps:
        raise ValueError(
            "this scenario describes rank programs (.step); use "
            "simulate(scenario) for the event engine, or .run groups "
            "for predict()")
    if scenario.is_placed or scenario.topo is not None:
        if scenario.topo is None:
            raise ValueError(
                "scenario has .placed groups but no topology; add "
                ".using(<topology or preset name>)")
        missing = [r.tag for r in scenario.runs if r.domain is None]
        if missing:
            raise ValueError(
                f"groups {missing} have no domain but the scenario has a "
                f"topology; place every group with .placed(kernel, n, "
                f"domain)")
        placements = tuple(
            topology_mod.Placed(r.group(scenario.arch), r.domain)
            for r in scenario.runs)
        kwargs = scenario.solver_options()
        kwargs["backend"] = scenario.backend
        kwargs["strict"] = scenario.strict
        kwargs["jax_cutoff"] = scenario.jax_cutoff
        kwargs["chunk"] = scenario.chunk
        return PlacedPlan(arch=scenario.arch, topo=scenario.topo,
                          placements=placements,
                          provenance=scenario.provenance,
                          solver_kwargs=kwargs)
    return ScalarPlan(arch=scenario.arch, groups=scenario.groups,
                      provenance=scenario.provenance,
                      solver_options=scenario.solver_options())


def _compile_simulate(scenario, *,
                      fuse_ensembles: bool = True) -> SimulatePlan:
    member_map: tuple[tuple[int, int], ...] | None = None
    if isinstance(scenario, Scenario):
        members = [(scenario, b)
                   for b in range(scenario.noise.ensemble
                                  if scenario.noise else 1)]
        scenarios = [scenario]
    elif isinstance(scenario, ScenarioBatch):
        scenarios = list(scenario.scenarios)
        if fuse_ensembles:
            # Batch × ensemble composition: scenario i's E_i noise
            # members flatten to adjacent rows of one (Σ E_i) run, each
            # member on its own SplitMix64-derived seed stream.
            members = [(sc, m) for sc in scenarios
                       for m in range(sc.noise.ensemble if sc.noise
                                      else 1)]
            if len(members) != len(scenarios):
                member_map = tuple(
                    (i, m) for i, sc in enumerate(scenarios)
                    for m in range(sc.noise.ensemble if sc.noise else 1))
        else:
            for i, sc in enumerate(scenarios):
                if sc.noise is not None and sc.noise.ensemble != 1:
                    raise ValueError(
                        f"scenario {i} asks for a noise ensemble inside "
                        f"a ScenarioBatch but fuse_ensembles=False "
                        f"forces the legacy one-row-per-scenario path; "
                        f"drop fuse_ensembles=False to run the whole "
                        f"batch × ensemble grid in one call, or set "
                        f"ensemble=1 on the scenario")
            members = [(sc, 0) for sc in scenarios]
    else:
        raise TypeError(
            f"simulate() takes a Scenario or ScenarioBatch, got "
            f"{type(scenario).__name__}")

    first = scenarios[0]
    t_max_conflict = None
    programs_batch = []
    placement0: Sequence[str] | None = None
    with trace.span("api.compile.expand", members=len(members)):
        for i, (sc, member) in enumerate(members):
            if sc.arch != first.arch:
                raise ValueError(
                    "all simulated scenarios must share one arch")
            if t_max_conflict is None and sc.t_max != first.t_max:
                t_max_conflict = (i, sc.t_max, first.t_max)
            if sc.topo != first.topo:
                raise ValueError(
                    f"scenario {i} uses a different topology than "
                    f"scenario 0; a batch shares one topology")
            progs, placement = _programs_for(sc, member)
            if i == 0:
                placement0 = placement
            elif placement != placement0:
                raise ValueError(
                    "all simulated scenarios must share one placement")
            programs_batch.append(progs)

    topo = first.topo
    if placement0 is not None and topo is None:
        raise ValueError(
            "scenario places ranks on domains but has no topology; add "
            ".using(<topology or preset name>)")
    if topo is not None and placement0 is None:
        topo = None  # unplaced scenario on a topology: single shared domain

    # The engine-side contract (rectangularity, placement length,
    # domain existence, anonymous-domain default) — shared with
    # run_batch so the two entry paths cannot drift.
    with trace.span("api.compile.validate", members=len(members)):
        placement = desync_batch.validate_batch(programs_batch, topo,
                                                placement0)

    specs = _collect_specs(scenarios)
    with trace.span("api.compile.encode", members=len(members)):
        enc = desync_batch._encode(programs_batch, specs)
    return SimulatePlan(arch=first.arch, enc=enc, specs=specs,
                        placement=placement, t_max_default=first.t_max,
                        t_max_conflict=t_max_conflict,
                        requested_backend=first.backend,
                        n_members=len(members), members=member_map)


def compile(scenario: Scenario | ScenarioBatch, *,
            verb: str | None = None,
            fuse_ensembles: bool = True) -> Plan:
    """Trace a scenario (or batch) into a frozen, re-runnable plan.

    ``verb`` picks the engine family — ``"predict"`` (the Eq. 4–5
    sharing solvers) or ``"simulate"`` (the desync event engine).  By
    default it is inferred from the scenario's shape: program-mode
    scenarios (``.step``/``.ranks``) and noise ensembles compile to a
    simulation plan, group-mode scenarios to a prediction plan (pass
    ``verb="simulate"`` to run groups through the event engine, exactly
    like calling :func:`repro.api.simulate` on them).

    ``fuse_ensembles`` (simulate only, default on) expands each batch
    scenario's ``with_noise(ensemble=E)`` members into the compiled
    run — B scenarios × E seeds as one ``(Σ E_i)``-row engine call,
    with the row origin recorded on ``plan.members`` /
    ``result.members``.  ``fuse_ensembles=False`` forces the legacy
    one-row-per-scenario contract, which rejects inner ensembles.

    All build-time work happens here — registry resolution already
    happened when the scenario was built; this adds validation, array
    packing / program encoding, and backend + jit selection through the
    substrate — so ``plan.run()`` is just the solve.
    """
    if verb is None:
        verb = infer_verb(scenario)
    if verb not in ("predict", "simulate"):
        raise ValueError(
            f"unknown verb {verb!r}; expected 'predict' or 'simulate'")
    with trace.span("api.compile", verb=verb) as sp:
        if verb == "predict":
            plan = _compile_predict(scenario)
        else:
            plan = _compile_simulate(scenario,
                                     fuse_ensembles=fuse_ensembles)
        sp.set(kind=plan.kind, engine=plan.engine)
        return plan
