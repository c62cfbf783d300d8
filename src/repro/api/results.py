"""Unified result family for the facade: one schema for every engine.

Before the facade callers juggled four incompatible result types —
``SharePrediction`` (scalar tuples), ``BatchSharePrediction`` (arrays),
``TopologyPrediction`` (per-domain mappings), ``BatchRunResult`` (desync
records).  Those stay as the engines' native outputs; this module wraps
them in one schema:

* :class:`Prediction` — one scenario: per-group shares (with the spec
  provenance recorded by :mod:`repro.api.registry`), per-domain
  breakdown, and ``.to_dict()`` / :func:`dump_ndjson` export;
* :class:`BatchPrediction` — B scenarios as batch-first arrays, lazily
  materializing a :class:`Prediction` per row;
* :class:`SimulationResult` — a (possibly ensemble) desync run, with the
  skew/duration/spread analysis helpers next to the records.

Round trip: ``Prediction.from_dict(p.to_dict())`` reproduces every field
(group provenance included), and ndjson files written by
:func:`dump_ndjson` load back with :func:`load_ndjson` — the export
format the "serve millions of scenarios" pipeline logs.
"""

from __future__ import annotations

import dataclasses
import json
from typing import IO, Iterable, Mapping, Sequence

import numpy as np

from ..core.desync import end_spread, start_spread
from ..core.desync_batch import BatchRunResult
from ..core.sharing import BatchSharePrediction, SharePrediction
from ..core.topology import TopologyBatchPrediction

SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class GroupShare:
    """One thread group's slice of a prediction."""

    name: str
    n: int
    f: float
    bs: float
    domain: str            # "" on a single anonymous domain
    provenance: str        # repro.api.registry.PROVENANCES
    alpha: float           # Eq. 5 request share within its domain
    bw: float              # attained bandwidth [GB/s]

    @property
    def bw_per_core(self) -> float:
        return self.bw / self.n if self.n else 0.0


@dataclasses.dataclass(frozen=True)
class DomainShare:
    """One contention domain's aggregate in a prediction."""

    domain: str
    b_overlap: float       # Eq. 4 envelope [GB/s]
    bw: float              # total attained bandwidth [GB/s]


@dataclasses.dataclass(frozen=True)
class Sensitivities:
    """Exact jacobians of a prediction's attained bandwidths.

    ``jacobians[name]`` holds ``∂bw/∂name`` for each requested input
    (``"f"``, ``"b_s"``, ``"cores"``) with the trailing two axes being
    ``(output group, input group)``: a single scenario carries
    ``(G, G)``, a batch ``(B, G, G)``, a placed solve ``(D, K, K)`` /
    ``(B, D, K, K)`` in *grid* coordinates (domain, occupancy slot — the
    same layout as :attr:`PlacedBatchPrediction.grid`).  Produced by
    ``plan.grad(...)`` through :func:`repro.core.sharing.
    solve_arrays_and_grad`; ``softmin_beta`` records whether the
    saturation min was smoothed on the gradient path (None = exact
    subgradient), ``utilization`` the law differentiated through.
    """

    wrt: tuple[str, ...]
    jacobians: Mapping[str, np.ndarray]
    utilization: str | float
    softmin_beta: float | None
    engine: str = "jax"

    def __getitem__(self, name: str) -> np.ndarray:
        try:
            return self.jacobians[name]
        except KeyError:
            from .registry import unknown_key_error
            raise unknown_key_error("gradient input", name,
                                    sorted(self.jacobians)) from None

    def to_dict(self) -> dict:
        return {
            "schema": SCHEMA_VERSION,
            "kind": "sensitivities",
            "wrt": list(self.wrt),
            "utilization": self.utilization,
            "softmin_beta": self.softmin_beta,
            "engine": self.engine,
            "jacobians": {
                name: {"shape": list(j.shape),
                       "data": np.asarray(j).ravel().tolist()}
                for name, j in self.jacobians.items()},
        }

    @classmethod
    def from_dict(cls, d: Mapping) -> "Sensitivities":
        jac = {
            name: np.asarray(e["data"], dtype=np.float64).reshape(
                e["shape"])
            for name, e in d["jacobians"].items()}
        return cls(wrt=tuple(d["wrt"]), jacobians=jac,
                   utilization=d["utilization"],
                   softmin_beta=d["softmin_beta"], engine=d["engine"])


@dataclasses.dataclass(frozen=True)
class Prediction:
    """One solved scenario, whichever engine solved it."""

    arch: str
    engine: str            # "scalar" | "topology" | "numpy" | "jax"
    groups: tuple[GroupShare, ...]
    domains: tuple[DomainShare, ...]
    #: Jacobians attached by ``plan.grad(...)``; None on plain solves.
    sensitivities: Sensitivities | None = None

    # -- the classic SharePrediction surface --------------------------------

    @property
    def bw_group(self) -> tuple[float, ...]:
        return tuple(g.bw for g in self.groups)

    @property
    def bw_per_core(self) -> tuple[float, ...]:
        return tuple(g.bw_per_core for g in self.groups)

    @property
    def alphas(self) -> tuple[float, ...]:
        return tuple(g.alpha for g in self.groups)

    @property
    def total_bw(self) -> float:
        return sum(g.bw for g in self.groups)

    @property
    def b_overlap(self) -> float:
        """Eq. 4 envelope.  On a multi-domain prediction this is the
        bandwidth-weighted notion callers usually chart — the sum of the
        populated domains' envelopes; single-domain predictions recover
        the scalar model's number exactly."""
        return sum(d.b_overlap for d in self.domains)

    def domain_bw(self, name: str) -> float:
        for d in self.domains:
            if d.domain == name:
                return d.bw
        from .registry import unknown_key_error
        raise unknown_key_error("domain", name,
                                [d.domain for d in self.domains])

    # -- export -------------------------------------------------------------

    def to_dict(self) -> dict:
        d = {
            "schema": SCHEMA_VERSION,
            "kind": "prediction",
            "arch": self.arch,
            "engine": self.engine,
            "groups": [dataclasses.asdict(g) for g in self.groups],
            "domains": [dataclasses.asdict(d) for d in self.domains],
            "total_bw": self.total_bw,
        }
        if self.sensitivities is not None:
            d["sensitivities"] = self.sensitivities.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "Prediction":
        sens = d.get("sensitivities")
        return cls(
            arch=d["arch"], engine=d["engine"],
            groups=tuple(GroupShare(**g) for g in d["groups"]),
            domains=tuple(DomainShare(**g) for g in d["domains"]),
            sensitivities=(Sensitivities.from_dict(sens)
                           if sens is not None else None))


def _group_shares(pred: SharePrediction, provenance: Sequence[str],
                  domain: str = "") -> tuple[GroupShare, ...]:
    return tuple(
        GroupShare(name=g.name, n=int(g.n), f=g.f, bs=g.bs, domain=domain,
                   provenance=prov, alpha=a, bw=bw)
        for g, prov, a, bw in zip(pred.groups, provenance, pred.alphas,
                                  pred.bw_group))


def from_share_prediction(pred: SharePrediction, *, arch: str,
                          provenance: Sequence[str],
                          engine: str = "scalar") -> Prediction:
    """Wrap a scalar-engine result (floats are copied, not recomputed —
    the facade is bit-for-bit the reference implementation)."""
    dom = DomainShare(domain="", b_overlap=pred.b_overlap,
                      bw=sum(pred.bw_group))
    return Prediction(arch=arch, engine=engine,
                      groups=_group_shares(pred, provenance),
                      domains=(dom,))


def from_topology_prediction(pred, *, arch: str,
                             provenance: Sequence[str]) -> Prediction:
    """Wrap a :class:`repro.core.topology.TopologyPrediction`."""
    alphas: list[float] = []
    for placed in pred.placements:
        sub = pred.by_domain[placed.domain]
        j = sub.groups.index(placed.group)
        alphas.append(sub.alphas[j])
    groups = tuple(
        GroupShare(name=p.group.name, n=int(p.group.n), f=p.group.f,
                   bs=p.group.bs, domain=p.domain, provenance=prov,
                   alpha=a, bw=bw)
        for p, prov, a, bw in zip(pred.placements, provenance, alphas,
                                  pred.bw_group))
    domains = tuple(
        DomainShare(domain=name, b_overlap=pred.by_domain[name].b_overlap,
                    bw=pred.domain_bw(name))
        for name in pred.topology.domain_names)
    return Prediction(arch=arch, engine="topology", groups=groups,
                      domains=domains)


@dataclasses.dataclass(frozen=True)
class BatchPrediction:
    """B solved scenarios, batch-first; each row materializes on demand.

    Scenarios of one batch may target different architectures (the
    arrays carry each row's own ``(f, b_s)`` values): ``archs`` records
    the per-row architecture and every materialized row / export line is
    labelled with its own.
    """

    archs: tuple[str, ...]  # (B,) per-scenario architecture labels
    engine: str            # "numpy" | "jax"
    raw: BatchSharePrediction
    provenance: tuple[tuple[str, ...], ...]  # (B, G), "" for padding
    #: Jacobians attached by ``plan.grad(...)`` — ``(B, G, G)`` per
    #: input; None on plain solves.
    sensitivities: Sensitivities | None = None

    @property
    def arch(self) -> str:
        """The batch's architecture, ``"mixed"`` when rows differ."""
        return self.archs[0] if len(set(self.archs)) == 1 else "mixed"

    # Array surface (delegates to the engine's native result).

    @property
    def bw_group(self) -> np.ndarray:
        return self.raw.bw_group

    @property
    def bw_per_core(self) -> np.ndarray:
        return self.raw.bw_per_core

    @property
    def alphas(self) -> np.ndarray:
        return self.raw.alphas

    @property
    def b_overlap(self) -> np.ndarray:
        return self.raw.b_overlap

    @property
    def total_bw(self) -> np.ndarray:
        return self.raw.total_bw

    def __len__(self) -> int:
        return len(self.raw)

    def __getitem__(self, i: int) -> Prediction:
        # Keep groups by provenance, not by n > 0: a scenario's genuine
        # n = 0 group (neutral in Eqs. 4–5 but present in the scalar
        # result) is indistinguishable from padding in the arrays alone.
        prov_row = self.provenance[i]
        keep = [j for j, p in enumerate(prov_row) if p]
        raw = self.raw
        groups = tuple(
            GroupShare(
                name=(raw.names[i][j] if raw.names is not None else ""),
                n=int(raw.n[i, j]), f=float(raw.f[i, j]),
                bs=float(raw.bs[i, j]), domain="",
                provenance=prov_row[j],
                alpha=float(raw.alphas[i, j]),
                bw=float(raw.bw_group[i, j]))
            for j in keep)
        dom = DomainShare(domain="", b_overlap=float(raw.b_overlap[i]),
                          bw=sum(g.bw for g in groups))
        return Prediction(arch=self.archs[i], engine=self.engine,
                          groups=groups, domains=(dom,))

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def rows(self, limit: int | None = None) -> "list[Prediction]":
        """Materialize the first ``limit`` rows (all by default) in one
        pass.  Same values as ``[self[i] for i in ...]``, but the arrays
        are converted to Python scalars with one bulk ``tolist()`` per
        field instead of a numpy scalar per element — the difference
        between ~13 us and ~4 us per row, which is what the serving
        fan-out pays on every coalesced tick."""
        B = len(self) if limit is None else min(limit, len(self))
        raw = self.raw
        n_l = np.asarray(raw.n)[:B].tolist()
        f_l = np.asarray(raw.f)[:B].tolist()
        bs_l = np.asarray(raw.bs)[:B].tolist()
        alpha_l = np.asarray(raw.alphas)[:B].tolist()
        bw_l = np.asarray(raw.bw_group)[:B].tolist()
        env_l = np.asarray(raw.b_overlap)[:B].tolist()
        names = raw.names
        engine = self.engine
        # Instances are built via __new__ + __dict__.update: the frozen
        # dataclasses store fields in __dict__, and their generated
        # __init__ pays one object.__setattr__ per field — ~3x the cost
        # of this path, per group, per row, per tick when serving.
        gs_new, ds_new = GroupShare.__new__, DomainShare.__new__
        pr_new = Prediction.__new__
        out = []
        for i in range(B):
            prov_row = self.provenance[i]
            ni, fi, bsi = n_l[i], f_l[i], bs_l[i]
            ai, bwi = alpha_l[i], bw_l[i]
            nmi = names[i] if names is not None else None
            groups = []
            bw_sum = 0.0
            for j, p in enumerate(prov_row):
                if not p:
                    continue
                g = gs_new(GroupShare)
                g.__dict__.update(
                    name=(nmi[j] if nmi is not None else ""),
                    n=int(ni[j]), f=fi[j], bs=bsi[j], domain="",
                    provenance=p, alpha=ai[j], bw=bwi[j])
                bw_sum += bwi[j]
                groups.append(g)
            dom = ds_new(DomainShare)
            dom.__dict__.update(domain="", b_overlap=env_l[i], bw=bw_sum)
            pred = pr_new(Prediction)
            pred.__dict__.update(
                arch=self.archs[i], engine=engine,
                groups=tuple(groups), domains=(dom,),
                sensitivities=None)
            out.append(pred)
        return out

    def iter_dicts(self):
        """Lazily yield one export dict per scenario — a
        million-scenario batch streams through one row of working set
        instead of one giant list (the ndjson writers are built on
        this)."""
        return (self[i].to_dict() for i in range(len(self)))

    def to_dicts(self) -> list[dict]:
        return list(self.iter_dicts())


@dataclasses.dataclass(frozen=True)
class PlacedBatchPrediction:
    """B placed-topology solves from one flattened grid solve.

    The array surface exposes the solver's padded ``(B, D, K)`` grid
    (``D`` topology domains, up to ``K`` groups each, masked occupancy);
    indexing materializes row *i* as exactly the :class:`Prediction` a
    lone placed ``predict`` would have returned — on the numpy backend
    bit-for-bit, since padded grid cells are exactly neutral.
    """

    archs: tuple[str, ...]   # (B,) per-scenario architecture labels
    engine: str              # solver backend: "numpy" | "jax"
    raw: TopologyBatchPrediction
    #: (B, J) input-order labels; a placement swap builds each row when
    #: it is read.
    provenance: Sequence[tuple[str, ...]]
    #: Jacobians attached by ``plan.grad(...)`` — ``(B, D, K, K)`` in
    #: grid coordinates; None on plain solves.
    sensitivities: Sensitivities | None = None

    @property
    def arch(self) -> str:
        return self.archs[0] if len(set(self.archs)) == 1 else "mixed"

    @property
    def topology(self):
        return self.raw.topology

    # Array surface (the solver's native padded-grid result).

    @property
    def bw_group(self) -> tuple[tuple[float, ...], ...]:
        """Per scenario, attained bandwidths in input placement order."""
        return self.raw.bw_group

    @property
    def total_bw(self) -> np.ndarray:
        return self.raw.total_bw

    @property
    def grid(self):
        """The padded ``(B, D, K)`` solver result
        (:class:`repro.core.sharing.PlacedBatchSharePrediction`)."""
        return self.raw.shares

    def __len__(self) -> int:
        return len(self.raw)

    def __getitem__(self, i: int) -> Prediction:
        return from_topology_prediction(
            self.raw.scenario(i), arch=self.archs[i],
            provenance=self.provenance[i])

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def iter_dicts(self):
        """Lazily yield one export dict per scenario (row-at-a-time
        working set, matching :meth:`BatchPrediction.iter_dicts`)."""
        return (self[i].to_dict() for i in range(len(self)))

    def to_dicts(self) -> list[dict]:
        return list(self.iter_dicts())


@dataclasses.dataclass(frozen=True)
class SimulationResult:
    """A desync run (B noise draws / candidates × R ranks), unified."""

    arch: str
    engine: str            # "desync-numpy" | "desync-jax"
    raw: BatchRunResult
    #: Flattened-row origin of a fused batch×ensemble run:
    #: ``members[b] == (scenario_index, member_index)``.  None when the
    #: run was not ensemble-expanded (every row is its own scenario).
    members: tuple[tuple[int, int], ...] | None = None

    def rows_for(self, scenario: int) -> tuple[int, ...]:
        """Flattened row indices of one input scenario's ensemble
        members (``(scenario,)`` itself when the run is unfused)."""
        if self.members is None:
            return (scenario,)
        return tuple(b for b, (s, _) in enumerate(self.members)
                     if s == scenario)

    @property
    def n_scenarios(self) -> int:
        return self.raw.n_scenarios

    @property
    def n_ranks(self) -> int:
        return self.raw.n_ranks

    @property
    def t_end(self) -> np.ndarray:
        return self.raw.t_end

    @property
    def failed(self) -> np.ndarray:
        return self.raw.failed

    def records(self, b: int = 0):
        return self.raw.records[b]

    def makespan(self, b: int = 0) -> float:
        return max((r.end for r in self.raw.records[b]), default=0.0)

    def durations(self, tag: str, b: int = 0, **kwargs) -> list[float]:
        return self.raw.durations_by_tag(b, tag, **kwargs)

    def skew(self, tag: str) -> np.ndarray:
        """Per-scenario Fisher skewness of accumulated ``tag`` time (the
        paper's desync indicator); NaN for deadlocked scenarios."""
        return self.raw.skew_by_tag(tag)

    def mean_skew(self, tag: str) -> float:
        return float(self.skew(tag).mean())

    def start_spread(self, tag: str, b: int = 0) -> float:
        return start_spread(self.raw.records[b], tag)

    def end_spread(self, tag: str, b: int = 0) -> float:
        return end_spread(self.raw.records[b], tag)

    def to_dict(self, *, tags: Sequence[str] = ()) -> dict:
        d = {
            "schema": SCHEMA_VERSION,
            "kind": "simulation",
            "arch": self.arch,
            "engine": self.engine,
            "n_scenarios": self.n_scenarios,
            "n_ranks": self.n_ranks,
            "n_events": self.raw.n_events,
            "n_failed": self.raw.n_failed,
            "t_end": [float(t) for t in self.t_end],
        }
        if tags:
            d["skew"] = {t: [float(x) for x in self.skew(t)]
                         for t in tags}
        return d


# ---------------------------------------------------------------------------
# ndjson export / import
# ---------------------------------------------------------------------------


def iter_ndjson(results: Iterable[Prediction | BatchPrediction]
                ) -> "Iterable[str]":
    """Lazily yield one serialized JSON line per *scenario* (batches
    are flattened through :meth:`BatchPrediction.iter_dicts`, one row
    of working set at a time) — the streaming half of
    :func:`dump_ndjson`, for callers that pipe lines elsewhere."""
    for res in results:
        rows = res.iter_dicts() \
            if isinstance(res, (BatchPrediction, PlacedBatchPrediction)) \
            else [res.to_dict()]
        for row in rows:
            yield json.dumps(row, sort_keys=True)


def dump_dicts(rows: Iterable[Mapping], fh: IO[str]) -> int:
    """Stream arbitrary dict records as ndjson lines (one write per
    record, nothing accumulated).  Returns the line count.  The
    benchmark driver's ``--ndjson`` mode uses this."""
    n = 0
    for row in rows:
        fh.write(json.dumps(row, sort_keys=True) + "\n")
        n += 1
    return n


def dump_ndjson(results: Iterable[Prediction | BatchPrediction],
                fh: IO[str]) -> int:
    """Write one JSON line per *scenario* (batches are flattened and
    streamed row by row — a million-scenario batch never materializes
    one giant list).  Returns the number of lines written."""
    n = 0
    for line in iter_ndjson(results):
        fh.write(line + "\n")
        n += 1
    return n


def load_ndjson(fh: IO[str]) -> list[Prediction]:
    """Load predictions written by :func:`dump_ndjson`."""
    out = []
    for line in fh:
        line = line.strip()
        if not line:
            continue
        d = json.loads(line)
        if d.get("kind") != "prediction":
            raise ValueError(
                f"ndjson line is not a prediction (kind="
                f"{d.get('kind')!r})")
        out.append(Prediction.from_dict(d))
    return out
