"""Declarative scenarios: state *what* runs where, not *how* to solve it.

A :class:`Scenario` is a frozen, composable description of one
contention experiment::

    Scenario.on("CLX").run("DCOPY", 12).run("DDOT2", 8)

Every builder method returns a new frozen scenario, so partial scenarios
are safely shareable templates.  Two shapes exist:

* **group mode** (``.run`` / ``.placed``) — concurrent thread groups,
  the paper's Eqs. 4–5 setting.  ``api.predict`` solves it; with
  ``.using(topology)`` and per-run domains it becomes a multi-domain
  placement solve.
* **program mode** (``.ranks`` + ``.step`` / ``.barrier`` / ``.halo`` /
  ``.idle``) — every rank executes the step sequence; ``api.simulate``
  runs it through the desync event engine.  ``.with_noise`` prepends a
  per-rank exponential jitter (the paper's Fig. 1/3 perturbation) and
  can request a whole seed ensemble in one scenario.

:class:`ScenarioBatch` packs B scenarios into the rectangular ``(B, G)``
arrays the batched solvers consume — ragged group lists are padded with
the neutral ``n = 0`` entries — and the sweep constructors
(:meth:`ScenarioBatch.split_sweep`, :meth:`ScenarioBatch.symmetric_sweep`,
:meth:`ScenarioBatch.pairing_matrix`, :meth:`Scenario.batch`) build the
common paper sweeps in one line.

Kernel references are resolved **at build time** through
:mod:`repro.api.registry` (Table II name → calibrated mapping →
``(f, bs)`` pair → explicit spec), so typos fail immediately with a
suggestion, and every group carries its spec provenance into the result.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

from ..core.sharing import Group
from ..core.table2 import KernelSpec
from ..core.topology import Placed, Topology
from ..core.topology import preset as topology_preset
from .registry import ResolvedSpec, resolve

#: Default per-run transfer volume for ``simulate`` on group-mode
#: scenarios (the HPCG study's SymGS scale: enough work that sharing
#: dynamics, not startup transients, dominate).
DEFAULT_WORK_BYTES = 32e6


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """One concurrent thread group of a group-mode scenario."""

    resolved: ResolvedSpec
    n: int
    domain: str | None
    bytes: float
    tag: str

    @property
    def spec(self) -> KernelSpec:
        return self.resolved.spec

    def group(self, arch: str) -> Group:
        return Group.of(self.spec, arch, self.n)


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """One program item executed (in order) by every rank."""

    kind: str                         # "work" | "barrier" | "halo" | "idle"
    tag: str
    resolved: ResolvedSpec | None = None
    bytes: tuple[float, ...] | float | None = None  # scalar or per-rank
    cost_s: float = 0.0

    def bytes_for(self, rank: int) -> float:
        if isinstance(self.bytes, tuple):
            return self.bytes[rank]
        return float(self.bytes)


@dataclasses.dataclass(frozen=True)
class Noise:
    """Per-rank exponential start jitter, optionally as a seed ensemble."""

    exp_mean_s: float
    seed: int = 0
    ensemble: int = 1
    tag: str = "noise"


def _resolve_ref(kernel, arch: str, name: str | None) -> ResolvedSpec:
    if isinstance(kernel, ResolvedSpec):
        if arch not in kernel.spec.f:
            from .registry import known_archs, unknown_key_error
            raise unknown_key_error("architecture", arch,
                                    known_archs(kernel.spec))
        return kernel
    return resolve(kernel, arch=arch, name=name)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A frozen, declarative contention scenario.  See module doc."""

    arch: str
    runs: tuple[RunSpec, ...] = ()
    steps: tuple[StepSpec, ...] = ()
    n_ranks: int | None = None
    topo: Topology | None = None
    rank_domains: tuple[str, ...] | None = None
    noise: Noise | None = None
    # Solver options, forwarded verbatim to the engines.
    utilization: str | float = "recursion"
    p0_factor: float = 0.5
    saturated: bool | None = None
    backend: str = "auto"
    t_max: float = 10.0
    strict: bool = True   # topology solves: reject overcommitted domains

    # -- constructors -------------------------------------------------------

    @classmethod
    def on(cls, arch: str, **options) -> "Scenario":
        """Start a scenario on architecture ``arch`` (a Table II column
        like ``"CLX"``, or any arch your specs carry, e.g. ``"TPU"``)."""
        return cls(arch=arch, **options)

    # -- group mode ---------------------------------------------------------

    def run(self, kernel, n: int, *, domain: str | None = None,
            bytes: float = DEFAULT_WORK_BYTES, tag: str | None = None,
            name: str | None = None) -> "Scenario":
        """Add a group of ``n`` threads all executing ``kernel``.

        ``kernel`` is anything :func:`repro.api.registry.resolve`
        accepts: a Table II name, a :class:`KernelSpec`, an ``(f, bs)``
        pair, a calibration mapping, or a pre-labelled
        :class:`ResolvedSpec`.  ``domain`` pins the group to a
        contention domain of the scenario's topology (see
        :meth:`using`); ``bytes`` only matters when the scenario is
        *simulated* rather than predicted.
        """
        if self.steps:
            raise ValueError(
                "cannot mix .run() groups with .step() programs in one "
                "scenario; use a second scenario")
        if not isinstance(n, (int, np.integer)) or n < 0:
            raise ValueError(f"thread count must be a non-negative int, "
                             f"got {n!r}")
        res = _resolve_ref(kernel, self.arch, name)
        run = RunSpec(resolved=res, n=int(n), domain=domain,
                      bytes=float(bytes), tag=tag or res.name)
        return dataclasses.replace(self, runs=self.runs + (run,))

    def placed(self, kernel, n: int, domain: str, **kwargs) -> "Scenario":
        """:meth:`run` with a required contention-domain placement."""
        return self.run(kernel, n, domain=domain, **kwargs)

    def using(self, topology: "Topology | str") -> "Scenario":
        """Attach a machine topology (a :class:`Topology` or a preset
        name like ``"CLX-2S"``) for ``.placed`` groups / rank domains."""
        if isinstance(topology, str):
            topology = topology_preset(topology)
        return dataclasses.replace(self, topo=topology)

    # -- program mode -------------------------------------------------------

    def ranks(self, n_ranks: int) -> "Scenario":
        """Switch to program mode: ``n_ranks`` ranks each execute the
        subsequent :meth:`step`/:meth:`barrier`/... sequence."""
        if self.runs:
            raise ValueError(
                "cannot mix .ranks() programs with .run() groups in one "
                "scenario; use a second scenario")
        if n_ranks < 1:
            raise ValueError(f"n_ranks must be >= 1, got {n_ranks}")
        return dataclasses.replace(self, n_ranks=int(n_ranks))

    def _need_ranks(self) -> int:
        if self.n_ranks is None:
            raise ValueError(
                "call .ranks(R) before adding program steps")
        return self.n_ranks

    def step(self, kernel, bytes, *, tag: str | None = None,
             name: str | None = None) -> "Scenario":
        """Every rank executes ``kernel`` over ``bytes`` (a scalar, or a
        per-rank sequence for imbalanced work)."""
        R = self._need_ranks()
        res = _resolve_ref(kernel, self.arch, name)
        if isinstance(bytes, (Sequence, np.ndarray)):
            per_rank = tuple(float(b) for b in bytes)
            if len(per_rank) != R:
                raise ValueError(
                    f"step gives {len(per_rank)} byte counts for {R} "
                    f"ranks")
            bspec: tuple[float, ...] | float = per_rank
        else:
            bspec = float(bytes)
        s = StepSpec(kind="work", tag=tag or res.name, resolved=res,
                     bytes=bspec)
        return dataclasses.replace(self, steps=self.steps + (s,))

    def barrier(self, cost_s: float = 5e-6,
                tag: str = "allreduce") -> "Scenario":
        """A global collective: blocks until every rank reaches it."""
        self._need_ranks()
        s = StepSpec(kind="barrier", tag=tag, cost_s=float(cost_s))
        return dataclasses.replace(self, steps=self.steps + (s,))

    def halo(self, cost_s: float = 2e-6, tag: str = "p2p") -> "Scenario":
        """A neighbor wait (halo exchange) between adjacent ranks."""
        self._need_ranks()
        s = StepSpec(kind="halo", tag=tag, cost_s=float(cost_s))
        return dataclasses.replace(self, steps=self.steps + (s,))

    def idle(self, duration_s: float, tag: str = "idle") -> "Scenario":
        """A fixed-duration delay on every rank."""
        self._need_ranks()
        s = StepSpec(kind="idle", tag=tag, cost_s=float(duration_s))
        return dataclasses.replace(self, steps=self.steps + (s,))

    def on_domains(self, placement: Sequence[str]) -> "Scenario":
        """Pin rank r to contention domain ``placement[r]`` of the
        scenario's topology (program mode)."""
        R = self._need_ranks()
        placement = tuple(placement)
        if len(placement) != R:
            raise ValueError(
                f"placement names {len(placement)} domains for {R} ranks")
        return dataclasses.replace(self, rank_domains=placement)

    def with_noise(self, exp_mean_s: float = 5e-5, *, seed: int = 0,
                   ensemble: int = 1, tag: str = "noise") -> "Scenario":
        """Prepend per-rank exponential start jitter; ``ensemble > 1``
        simulates that many independent seeds in one batched run."""
        if ensemble < 1:
            raise ValueError(f"ensemble must be >= 1, got {ensemble}")
        return dataclasses.replace(
            self, noise=Noise(exp_mean_s=float(exp_mean_s), seed=int(seed),
                              ensemble=int(ensemble), tag=tag))

    # -- options ------------------------------------------------------------

    def options(self, **kwargs) -> "Scenario":
        """Override solver options: ``utilization``, ``p0_factor``,
        ``saturated``, ``backend``, ``t_max``, ``strict``."""
        allowed = {"utilization", "p0_factor", "saturated", "backend",
                   "t_max", "strict"}
        bad = set(kwargs) - allowed
        if bad:
            raise TypeError(
                f"unknown scenario options {sorted(bad)}; allowed: "
                f"{sorted(allowed)}")
        return dataclasses.replace(self, **kwargs)

    # -- derived views ------------------------------------------------------

    @property
    def groups(self) -> tuple[Group, ...]:
        """The scenario's thread groups as the scalar solver sees them."""
        return tuple(r.group(self.arch) for r in self.runs)

    @property
    def provenance(self) -> tuple[str, ...]:
        return tuple(r.resolved.provenance for r in self.runs)

    @property
    def is_placed(self) -> bool:
        return any(r.domain is not None for r in self.runs)

    @property
    def total_threads(self) -> int:
        return sum(r.n for r in self.runs)

    def solver_options(self) -> dict:
        return dict(utilization=self.utilization,
                    p0_factor=self.p0_factor, saturated=self.saturated)

    # -- batching -----------------------------------------------------------

    def batch(self, counts) -> "ScenarioBatch":
        """Sweep this scenario's thread counts: ``counts`` is ``(B, G)``
        (one column per ``.run`` group); each row becomes one scenario."""
        counts = np.asarray(counts)
        if counts.ndim != 2 or counts.shape[1] != len(self.runs):
            raise ValueError(
                f"counts must be (B, {len(self.runs)}) for this "
                f"scenario's {len(self.runs)} groups, got "
                f"{counts.shape}")
        scens = []
        for row in counts:
            runs = tuple(dataclasses.replace(r, n=int(c))
                         for r, c in zip(self.runs, row))
            scens.append(dataclasses.replace(self, runs=runs))
        return ScenarioBatch.of(scens)


# ---------------------------------------------------------------------------
# Batches and sweeps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScenarioBatch:
    """B scenarios solved (or simulated) together.

    For ``predict``, scenarios are group-mode: unplaced batches pack
    into rectangular ``(B, G)`` arrays (ragged lists padded with
    neutral ``n = 0`` groups); batches placed on **one shared
    topology** pack into a ``(B, D, K)`` occupancy-masked grid and
    solve as one flattened call (mixing placed and unplaced scenarios
    is rejected).  For ``simulate``, scenarios must share the rank
    count, topology, and placement (the batched desync engine's
    contract); programs may differ freely, and each scenario's
    ``with_noise(ensemble=E)`` members fuse into the same batched run.
    """

    scenarios: tuple[Scenario, ...]

    @classmethod
    def of(cls, scenarios: Sequence[Scenario]) -> "ScenarioBatch":
        scenarios = tuple(scenarios)
        if not scenarios:
            raise ValueError("a ScenarioBatch needs at least one scenario")
        first = scenarios[0]
        for i, sc in enumerate(scenarios):
            if sc.solver_options() != first.solver_options() or \
                    sc.backend != first.backend:
                raise ValueError(
                    f"scenario {i} has different solver options than "
                    f"scenario 0; a batch is solved with one option set")
        return cls(scenarios=scenarios)

    def __len__(self) -> int:
        return len(self.scenarios)

    def __getitem__(self, i: int) -> Scenario:
        return self.scenarios[i]

    @functools.cached_property
    def archs(self) -> tuple[str, ...]:
        return tuple(sc.arch for sc in self.scenarios)

    @functools.cached_property
    def arrays(self) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                              tuple[tuple[str, ...], ...]]:
        """Padded ``(n, f, bs, names)`` arrays of shape ``(B, G)``."""
        scens = self.scenarios
        g_max = max((len(sc.runs) for sc in scens), default=0)
        shape = (len(scens), max(g_max, 1))
        n = np.zeros(shape)
        f = np.zeros(shape)
        bs = np.zeros(shape)
        names = [[""] * shape[1] for _ in scens]
        for i, sc in enumerate(scens):
            for j, r in enumerate(sc.runs):
                spec = r.spec
                n[i, j] = r.n
                f[i, j] = spec.f[sc.arch]
                bs[i, j] = spec.bs[sc.arch]
                names[i][j] = r.tag
        return n, f, bs, tuple(tuple(row) for row in names)

    @functools.cached_property
    def is_placed(self) -> bool:
        """True when the batch is a topology-placed solve: every scenario
        placed on **one shared topology**.  Raises on incoherent mixes —
        placed next to unplaced scenarios, differing topologies, or a
        topology with unplaced groups — because those have no meaningful
        common grid."""
        flags = [sc.is_placed or sc.topo is not None
                 for sc in self.scenarios]
        if not any(flags):
            return False
        first = self.scenarios[0]
        for i, (sc, flag) in enumerate(zip(self.scenarios, flags)):
            if not flag:
                raise ValueError(
                    f"scenario {i} is unplaced but the batch has placed "
                    f"scenarios; a batch is either all placed on one "
                    f"topology or all single-domain")
            if sc.topo is None:
                raise ValueError(
                    f"scenario {i} has .placed groups but no topology; "
                    f"add .using(<topology or preset name>)")
            if sc.topo != first.topo:
                raise ValueError(
                    f"scenario {i} uses a different topology than "
                    f"scenario 0; a placed batch shares one topology")
            missing = [r.tag for r in sc.runs if r.domain is None]
            if missing:
                raise ValueError(
                    f"scenario {i}: groups {missing} have no domain but "
                    f"the scenario has a topology; place every group "
                    f"with .placed(kernel, n, domain)")
        return True

    @functools.cached_property
    def placements(self) -> "tuple[tuple[Placed, ...], ...]":
        """Per-scenario placement lists of a placed batch (input order)."""
        if not self.is_placed:
            raise ValueError("batch has no placed scenarios")
        return tuple(
            tuple(Placed(r.group(sc.arch), r.domain) for r in sc.runs)
            for sc in self.scenarios)

    @functools.cached_property
    def predictable(self) -> bool:
        """Validate the batch for ``predict`` (cached, so repeated
        predicts on one batch pay the O(B) scan once)."""
        for i, sc in enumerate(self.scenarios):
            if sc.steps:
                raise ValueError(
                    f"scenario {i} describes rank programs; use "
                    f"simulate(batch)")
        self.is_placed  # coherence: all placed on one topology, or none
        return True

    @functools.cached_property
    def provenance(self) -> tuple[tuple[str, ...], ...]:
        """(B, G) provenance labels ("" for padding groups)."""
        _, _, _, names = self.arrays
        out = []
        for sc, row in zip(self.scenarios, names):
            prov = list(sc.provenance)
            prov += [""] * (len(row) - len(prov))
            out.append(tuple(prov))
        return tuple(out)

    # -- sweep constructors -------------------------------------------------

    @classmethod
    def split_sweep(cls, arch: str, kernel_a, kernel_b, n_total: int,
                    **options) -> "ScenarioBatch":
        """All ``(i, n_total - i)`` splits of a fully populated domain
        between two kernels (the paper's Fig. 6 sweep), one batch."""
        base = (Scenario.on(arch, **options)
                .run(kernel_a, 1).run(kernel_b, 1))
        na = np.arange(1, n_total)
        return base.batch(np.stack([na, n_total - na], axis=-1))

    @classmethod
    def symmetric_sweep(cls, arch: str, kernel_a, kernel_b, n_max: int,
                        **options) -> "ScenarioBatch":
        """Symmetric thread scaling ``n = 1 .. n_max`` per kernel (the
        paper's Fig. 7 curves), one batch."""
        base = (Scenario.on(arch, **options)
                .run(kernel_a, 1).run(kernel_b, 1))
        ns = np.arange(1, n_max + 1)
        return base.batch(np.stack([ns, ns], axis=-1))

    @classmethod
    def pairing_matrix(cls, arch: str, kernels: Sequence, n_each: int,
                       **options) -> "ScenarioBatch":
        """The Fig. 9 layout: rows ``0 .. K²-1`` are all mixed pairs
        (A with B, each on ``n_each`` threads), rows ``K² .. K²+K-1``
        the self-pairings (A with A) the gains are normalized by."""
        ks = list(kernels)
        scens = []
        for ka in ks:
            for kb in ks:
                scens.append(Scenario.on(arch, **options)
                             .run(ka, n_each).run(kb, n_each))
        for ka in ks:
            scens.append(Scenario.on(arch, **options)
                         .run(ka, n_each).run(ka, n_each))
        return cls.of(scens)
