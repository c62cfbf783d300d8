"""Contention-domain topology: machines as trees of memory domains.

The paper's sharing model (core/sharing.py, Eqs. 4–5) arbitrates bandwidth
on *one* memory contention domain.  Real machines have several: a
dual-socket Cascade Lake node has two, a dual-socket Rome in NPS4 mode has
eight ccNUMA quadrants, a TPU v5e pod slice has one HBM interface per chip.
Kerncraft-style automated analysis (Hammer et al., arXiv:1509.03778) and the
cache-topology study behind LIKWID (Treibig et al., arXiv:0910.4865) both
show that getting topology wrong is where single-domain models break down.

This module describes a machine as a tree — interior :class:`TopologyNode`
levels (node, socket, package) over leaf :class:`ContentionDomain` objects —
and solves a *placement* of thread groups onto leaves by running the Eq. 4–5
arbitration independently per domain (memory controllers of different
ccNUMA domains do not contend with each other; cross-domain traffic is out
of scope exactly as in the paper) and aggregating the results.

The per-domain solves go through the batched array solver
(:func:`repro.core.sharing.solve_batch`), so a topology solve is one
vectorized call regardless of how many domains are populated.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Mapping, Sequence

import numpy as np

from .machine import (BDW1, BDW2, CLX, ROME, TPU_V5E, MachineModel,
                      TpuModel)
from .sharing import (BatchSharePrediction, Group, PlacedBatchSharePrediction,
                      SharePrediction, groups_to_arrays, solve_batch,
                      solve_placed_batch)


# ---------------------------------------------------------------------------
# Tree description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ContentionDomain:
    """Leaf of the tree: one memory interface arbitrated by Eqs. 4–5.

    ``n_cores`` is the domain's capacity — cores on a ccNUMA domain, or
    concurrent HBM streams (compute loads, DMA prefetch, collective drains)
    on a TPU chip.  ``machine`` / ``tpu`` carry the hardware description the
    domain was derived from, when there is one; they are not needed by the
    solver itself (groups bring their own ``f`` and ``b_s``).
    """

    name: str
    n_cores: int
    machine: MachineModel | None = None
    tpu: TpuModel | None = None

    @property
    def saturated_bw_gbs(self) -> float | None:
        """Read-write saturation envelope of the domain, if known."""
        if self.machine is not None:
            return self.machine.saturated_bw_gbs["read_write"]
        if self.tpu is not None:
            return self.tpu.hbm_bw_gbs
        return None


@dataclasses.dataclass(frozen=True)
class TopologyNode:
    """Interior node: a node, socket, or package grouping domains."""

    name: str
    children: tuple["TopologyNode | ContentionDomain", ...]


@dataclasses.dataclass(frozen=True)
class Topology:
    """A machine as a tree of contention domains."""

    root: TopologyNode

    @property
    def name(self) -> str:
        return self.root.name

    @functools.cached_property
    def domains(self) -> tuple[ContentionDomain, ...]:
        """All leaves, depth-first (stable order used for batching);
        walked once per topology, which is immutable."""
        out: list[ContentionDomain] = []

        def walk(node: TopologyNode | ContentionDomain) -> None:
            if isinstance(node, ContentionDomain):
                out.append(node)
            else:
                for child in node.children:
                    walk(child)

        walk(self.root)
        return tuple(out)

    @functools.cached_property
    def domain_names(self) -> tuple[str, ...]:
        return tuple(d.name for d in self.domains)

    @functools.cached_property
    def domain_index(self) -> dict[str, int]:
        """Each domain's position in :attr:`domains`, by name."""
        return {name: i for i, name in enumerate(self.domain_names)}

    def domain(self, name: str) -> ContentionDomain:
        for d in self.domains:
            if d.name == name:
                return d
        raise KeyError(
            f"no contention domain {name!r} in topology {self.name!r}; "
            f"available: {list(self.domain_names)}")

    def __contains__(self, name: str) -> bool:
        return any(d.name == name for d in self.domains)

    @property
    def total_cores(self) -> int:
        return sum(d.n_cores for d in self.domains)


# ---------------------------------------------------------------------------
# Placement + solve
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Placed:
    """One thread group pinned to one contention domain."""

    group: Group
    domain: str


@dataclasses.dataclass(frozen=True)
class TopologyPrediction:
    """Per-domain Eq. 4–5 solutions plus cross-domain aggregates.

    ``bw_group`` is ordered like the input placements, so callers can zip
    it against what they passed in regardless of domain structure.
    """

    topology: Topology
    placements: tuple[Placed, ...]
    by_domain: Mapping[str, SharePrediction]
    bw_group: tuple[float, ...]

    @property
    def bw_per_core(self) -> tuple[float, ...]:
        return tuple(b / p.group.n if p.group.n else 0.0
                     for b, p in zip(self.bw_group, self.placements))

    @property
    def total_bw(self) -> float:
        """Aggregate attained bandwidth across every domain [GB/s]."""
        return sum(self.bw_group)

    def domain_bw(self, name: str) -> float:
        """Attained bandwidth on one domain (0 for an idle domain)."""
        return sum(self.by_domain[name].bw_group)


def predict_placed(topology: Topology, placements: Sequence[Placed], *,
                   strict: bool = True, **solver_kwargs
                   ) -> TopologyPrediction:
    """Solve every populated domain's arbitration in one batched call.

    Each leaf domain is an independent Eq. 4–5 instance; an idle domain
    trivially attains zero bandwidth.  ``strict=True`` rejects placements
    that name unknown domains or overcommit a domain's cores.

    ``solver_kwargs`` (``utilization``, ``saturated``, ``p0_factor``,
    ``backend``) are forwarded to :func:`repro.core.sharing.solve_batch`.
    """
    placements = tuple(placements)
    names = topology.domain_names
    by_index = check_placement(topology, placements, strict=strict)
    per_domain: dict[str, list[tuple[int, Group]]] = {
        n: by_index.get(i, []) for i, n in enumerate(names)}

    populated = [n for n in names if per_domain[n]]
    by_domain: dict[str, SharePrediction] = {}
    bw_flat: list[float] = [0.0] * len(placements)

    if populated:
        scenarios = [[g for _, g in per_domain[n]] for n in populated]
        batch = solve_batch(*groups_to_arrays(scenarios), **solver_kwargs)
        for row, name in enumerate(populated):
            entries = per_domain[name]
            groups = tuple(g for _, g in entries)
            bws = tuple(float(batch.bw_group[row, j])
                        for j in range(len(entries)))
            by_domain[name] = SharePrediction(
                groups=groups,
                b_overlap=float(batch.b_overlap[row]),
                alphas=tuple(float(batch.alphas[row, j])
                             for j in range(len(entries))),
                bw_group=bws)
            for (idx, _), bw in zip(entries, bws):
                bw_flat[idx] = bw
    for name in names:
        if name not in by_domain:
            by_domain[name] = SharePrediction(
                groups=(), b_overlap=0.0, alphas=(), bw_group=())

    return TopologyPrediction(topology=topology, placements=placements,
                              by_domain=by_domain, bw_group=tuple(bw_flat))


# ---------------------------------------------------------------------------
# Placement-batched solve: B placements on one topology, one flattened call
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlacedGrid:
    """B ragged placements packed onto a common ``(B, D, K)`` grid.

    ``D`` is the topology's full leaf count (depth-first, matching
    :attr:`Topology.domains`); ``K`` is the largest per-domain group count
    across the whole batch.  ``slots[b][j]`` gives the ``(d, k)`` cell
    scenario *b*'s *j*-th placement landed in, so results on the grid can
    be read back in input order.
    """

    topology: Topology
    placements: tuple[tuple[Placed, ...], ...]
    n: np.ndarray     # (B, D, K)
    f: np.ndarray     # (B, D, K)
    bs: np.ndarray    # (B, D, K)
    mask: np.ndarray  # (B, D, K) bool, True = occupied
    slots: tuple[tuple[tuple[int, int], ...], ...]

    def __len__(self) -> int:
        return len(self.placements)


def check_placement(topology: Topology, placements: Sequence[Placed], *,
                    strict: bool = True
                    ) -> dict[int, list[tuple[int, Group]]]:
    """One scenario's groups by domain, checked: ``{domain index:
    [(placement index, group), ...]}`` in placement order, the order
    every solver packs a domain's lanes in.

    A placement on a domain the topology lacks raises ``KeyError``;
    under ``strict``, a domain given more threads than it has cores
    raises ``ValueError``.  The one rule of a valid placement, for
    :func:`predict_placed`, :func:`pack_placed` and the service's
    per-request checks.
    """
    index = topology.domain_index
    per_domain: dict[int, list[tuple[int, Group]]] = {}
    for idx, p in enumerate(placements):
        d = index.get(p.domain)
        if d is None:
            raise KeyError(
                f"placement {idx} names unknown domain {p.domain!r}; "
                f"available: {list(index)}")
        per_domain.setdefault(d, []).append((idx, p.group))
    if strict:
        domains = topology.domains
        for d in sorted(per_domain):
            used = sum(g.n for _, g in per_domain[d])
            if used > domains[d].n_cores:
                raise ValueError(
                    f"domain {domains[d].name!r} overcommitted: {used:g} "
                    f"threads placed on {domains[d].n_cores} cores (pass "
                    f"strict=False to allow)")
    return per_domain


def pack_placed(topology: Topology,
                placements_batch: Sequence[Sequence[Placed]], *,
                strict: bool = True, lanes: int = 1) -> PlacedGrid:
    """Pad B heterogeneous placements to one occupancy-masked grid.

    Groups keep their placement order within each domain (the same order
    :func:`predict_placed` packs them in, so grid solves are bit-for-bit
    comparable).  Each scenario is checked by :func:`check_placement`
    (overcommits only under ``strict``).  The grid
    has at least ``lanes`` lanes per domain (trailing empty lanes are
    neutral), so batches of fewer groups can keep a solver's shape.
    """
    placements_batch = tuple(tuple(p) for p in placements_batch)
    B, D = len(placements_batch), len(topology.domains)

    per_scenario: list[dict[int, list[tuple[int, Group]]]] = []
    K = max(int(lanes), 1)
    for b, placements in enumerate(placements_batch):
        try:
            per_domain = check_placement(topology, placements,
                                         strict=strict)
        except (KeyError, ValueError) as e:
            raise type(e)(f"scenario {b}: {e.args[0]}") from None
        per_scenario.append(per_domain)
        K = max(K, *(len(v) for v in per_domain.values()), 1)

    n = np.zeros((B, D, K))
    f = np.zeros((B, D, K))
    bs = np.zeros((B, D, K))
    mask = np.zeros((B, D, K), dtype=bool)
    slots: list[tuple[tuple[int, int], ...]] = []
    for b, per_domain in enumerate(per_scenario):
        slot_of: dict[int, tuple[int, int]] = {}
        for d, entries in per_domain.items():
            for k, (idx, g) in enumerate(entries):
                n[b, d, k] = g.n
                f[b, d, k] = g.f
                bs[b, d, k] = g.bs
                mask[b, d, k] = True
                slot_of[idx] = (d, k)
        slots.append(tuple(slot_of[j]
                           for j in range(len(placements_batch[b]))))
    return PlacedGrid(topology=topology, placements=placements_batch,
                      n=n, f=f, bs=bs, mask=mask, slots=tuple(slots))


@dataclasses.dataclass(frozen=True)
class TopologyBatchPrediction:
    """B placed-topology solutions from one flattened grid solve.

    ``scenario(i)`` materializes the i-th result as the
    :class:`TopologyPrediction` a lone :func:`predict_placed` call would
    have returned — on the numpy path bit-for-bit, because padded grid
    rows and trailing zero lanes are exactly neutral.
    """

    grid: PlacedGrid
    shares: PlacedBatchSharePrediction

    def __len__(self) -> int:
        return len(self.grid)

    @property
    def topology(self) -> Topology:
        return self.grid.topology

    @property
    def total_bw(self) -> np.ndarray:
        """(B,) aggregate attained bandwidth per scenario [GB/s]."""
        return self.shares.total_bw

    @property
    def bw_group(self) -> tuple[tuple[float, ...], ...]:
        """Per scenario, attained bandwidths in input placement order."""
        return tuple(
            tuple(float(self.shares.bw_group[b, d, k])
                  for d, k in self.grid.slots[b])
            for b in range(len(self)))

    def _group_at(self, i: int, j: int) -> Group:
        """Input placement j's group, with numbers read back from the
        solved grid — so ``plan.run(f=..., cores=...)`` number swaps
        show up in materialized results, not just in the arrays."""
        d, k = self.grid.slots[i][j]
        g = self.grid.placements[i][j].group
        n_, f_, bs_ = (float(self.shares.n[i, d, k]),
                       float(self.shares.f[i, d, k]),
                       float(self.shares.bs[i, d, k]))
        if (g.n, g.f, g.bs) == (n_, f_, bs_):
            return g
        return dataclasses.replace(g, n=int(n_), f=f_, bs=bs_)

    def scenario(self, i: int) -> TopologyPrediction:
        """The i-th solution, shaped exactly like :func:`predict_placed`."""
        placements = tuple(
            dataclasses.replace(p, group=self._group_at(i, j))
            for j, p in enumerate(self.grid.placements[i]))
        names = self.topology.domain_names
        by_domain: dict[str, SharePrediction] = {}
        slot_to_idx = {s: j for j, s in enumerate(self.grid.slots[i])}
        for d, name in enumerate(names):
            ks = [k for k in range(self.grid.mask.shape[2])
                  if self.grid.mask[i, d, k]]
            if not ks:
                by_domain[name] = SharePrediction(
                    groups=(), b_overlap=0.0, alphas=(), bw_group=())
                continue
            by_domain[name] = SharePrediction(
                groups=tuple(placements[slot_to_idx[(d, k)]].group
                             for k in ks),
                b_overlap=float(self.shares.b_overlap[i, d]),
                alphas=tuple(float(self.shares.alphas[i, d, k])
                             for k in ks),
                bw_group=tuple(float(self.shares.bw_group[i, d, k])
                               for k in ks))
        return TopologyPrediction(
            topology=self.topology, placements=placements,
            by_domain=by_domain,
            bw_group=tuple(float(self.shares.bw_group[i, d, k])
                           for d, k in self.grid.slots[i]))


def predict_placed_batch(topology: Topology,
                         placements_batch: Sequence[Sequence[Placed]], *,
                         strict: bool = True, **solver_kwargs
                         ) -> TopologyBatchPrediction:
    """Solve B placements of one topology as a single flattened call.

    Packs the batch to a common ``(B, D, K)`` grid
    (:func:`pack_placed`) and runs every domain of every scenario
    through one :func:`repro.core.sharing.solve_placed_batch` — the
    grid flattens to ``(B·D, K)`` rows, so backend dispatch and the
    process-wide jit cache see the same power-of-two buckets the
    unplaced batched path uses.  ``solver_kwargs`` (``utilization``,
    ``saturated``, ``p0_factor``, ``backend``, ``jax_cutoff``,
    ``chunk``) forward to the solver.
    """
    grid = pack_placed(topology, placements_batch, strict=strict)
    shares = solve_placed_batch(grid.n, grid.f, grid.bs, mask=grid.mask,
                                **solver_kwargs)
    return TopologyBatchPrediction(grid=grid, shares=shares)


def predict_single_domain(groups: Sequence[Group],
                          domain: ContentionDomain | None = None,
                          **solver_kwargs) -> SharePrediction:
    """Single-domain compatibility wrapper: the paper's original scenario
    as a one-leaf topology solve.  With ``domain=None`` an unbounded
    anonymous domain is used (capacity checks off), which reproduces the
    historical ``sharing.predict`` behavior exactly."""
    if domain is None:
        domain = ContentionDomain(
            "domain0", n_cores=sum(int(g.n) for g in groups))
    topo = Topology(TopologyNode(domain.name, (domain,)))
    pred = predict_placed(
        topo, [Placed(g, domain.name) for g in groups], **solver_kwargs)
    return pred.by_domain[domain.name]


def spread_counts(total: int, n_domains: int) -> tuple[int, ...]:
    """Block-distribute ``total`` threads over ``n_domains`` domains
    (first domains get the remainder), the usual OpenMP ``places=sockets``
    convention."""
    base, rem = divmod(total, n_domains)
    return tuple(base + (1 if i < rem else 0) for i in range(n_domains))


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------


def single_domain(machine: MachineModel) -> Topology:
    """One ccNUMA domain — the paper's measurement setting (Table I)."""
    leaf = ContentionDomain(f"{machine.name}/d0",
                            n_cores=machine.cores_per_domain,
                            machine=machine)
    return Topology(TopologyNode(machine.name, (leaf,)))


def multi_socket(machine: MachineModel, n_sockets: int = 2, *,
                 domains_per_socket: int = 1) -> Topology:
    """A multi-socket node of identical sockets, each split into
    ``domains_per_socket`` ccNUMA domains (NPS4 Rome: 4)."""
    sockets = []
    for s in range(n_sockets):
        leaves = tuple(
            ContentionDomain(f"{machine.name}/s{s}/d{d}",
                             n_cores=machine.cores_per_domain,
                             machine=machine)
            for d in range(domains_per_socket))
        sockets.append(TopologyNode(f"{machine.name}/s{s}", leaves))
    name = f"{machine.name}-{n_sockets}S"
    if domains_per_socket > 1:
        name += f"-NPS{domains_per_socket}"
    return Topology(TopologyNode(name, tuple(sockets)))


def tpu_pod(tpu: TpuModel = TPU_V5E, n_chips: int = 4, *,
            streams_per_chip: int = 8) -> Topology:
    """A pod slice: one HBM contention domain per chip.  ``n_cores`` is the
    number of concurrent HBM stream agents modelled per chip (compute-phase
    loads, DMA prefetch, collective send/recv drains)."""
    leaves = tuple(
        ContentionDomain(f"{tpu.name}/chip{c}", n_cores=streams_per_chip,
                         tpu=tpu)
        for c in range(n_chips))
    return Topology(TopologyNode(f"{tpu.name}-pod{n_chips}", leaves))


# Ready-made machines.  x86 names follow the paper's Table I; the -2S
# variants are the dual-socket nodes the paper's HPCG runs used, and
# ROME-2S-NPS4 is the eight-quadrant layout of a dual Rome node.
PRESETS: dict[str, "Topology"] = {}


def _register(topo: Topology) -> Topology:
    PRESETS[topo.name] = topo
    return topo


for _m in (BDW1, BDW2, CLX, ROME):
    _register(single_domain(_m))
_register(multi_socket(BDW1, 2))
_register(multi_socket(BDW2, 2))
_register(multi_socket(CLX, 2))
_register(multi_socket(ROME, 2, domains_per_socket=4))
_register(tpu_pod(TPU_V5E, 4))
_register(tpu_pod(TPU_V5E, 8))


def preset(name: str) -> Topology:
    """Look up a ready-made topology by name (see :data:`PRESETS`)."""
    try:
        return PRESETS[name]
    except KeyError:
        from ..api.registry import unknown_key_error
        raise unknown_key_error("topology preset", name, PRESETS) from None
