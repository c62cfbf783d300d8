"""Plan-cache keys and batched packing for the serving subsystem.

The cache contract mirrors the substrate's jit cache one level up: a
compiled plan is reusable for any request with the same *structure*
(:func:`repro.api.structure_key` — kernels, options, topology; not the
numeric payload), and batch sizes round up to the substrate's
power-of-two buckets (:func:`repro.core.backend.bucket`) so a tick of
B requests runs on the plan compiled for ``bucket(B)`` rows.  Padding
rows are exactly neutral (``n = 0`` groups / empty placements — the
substrate's :func:`repro.core.backend.pad_rows` invariant), which is
what keeps coalesced responses bit-for-bit equal to per-request solves.

Placed predictions are the exception to keying by structure: a
placed plan swaps whole placement batches (``plan.run(placement=)``),
so every placed request on one node shares one group, whatever its
kernels and domains, and one plan per (node, row bucket, lane bucket).
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from .. import api
from ..core import backend as backend_mod
from ..core import sharing
from ..core import topology as topology_mod
from ..core.topology import Placed


def is_placed(scenario: "api.Scenario", verb: str) -> bool:
    """True for a prediction on a topology: it is served by a placed
    batch plan whose placements are swapped per tick."""
    return verb == "predict" and (scenario.is_placed
                                  or scenario.topo is not None)


def group_key(scenario: "api.Scenario", verb: str) -> tuple:
    """The coalescing key: requests with equal keys can share one
    batched solve.  :func:`repro.api.structure_key`, except for placed
    predictions, which key by their node alone
    (:func:`repro.api.node_key`: verb, arch, options, topology)."""
    if is_placed(scenario, verb):
        return api.node_key(scenario, verb=verb)
    return api.structure_key(scenario, verb=verb)


def plan_entry(verb: str, sig: tuple, n_requests: int,
               lanes: int | None = None) -> tuple[tuple, int]:
    """Map a structure signature plus a live batch size to the cache
    entry that serves it: ``(entry_key, rows)`` where ``rows`` is the
    power-of-two bucket the plan was (or will be) compiled for.  A
    placed entry also holds its lane bucket (:func:`lane_bucket`).

    Simulation plans carry their numbers (the signature includes them),
    and one run is shared by every identical request in the tick, so
    the entry is bucket-free."""
    if verb == "simulate":
        return (sig,), 1
    rows = backend_mod.bucket(n_requests)
    if lanes is not None:
        return (sig, rows, lanes), rows
    return (sig, rows), rows


def key_label(verb: str, scenario: "api.Scenario", rows: int,
              lanes: int | None = None) -> str:
    """Short deterministic metrics label for one cache entry, in the
    same spirit as the jit cache's key labels: human-scannable prefix
    plus a structure digest."""
    sig = group_key(scenario, verb)
    digest = hashlib.blake2s(repr(sig).encode(),
                             digest_size=5).hexdigest()
    shape = f"B{rows}" if lanes is None else f"B{rows}K{lanes}"
    return f"{verb}/{scenario.arch}/{shape}/{digest}"


def placement(scenario: "api.Scenario") -> tuple[tuple[Placed, ...], int]:
    """One placed request's placement list and the most groups it puts
    on one domain, checked alone by
    :func:`repro.core.topology.check_placement` (unknown domain →
    ``KeyError``, overcommitted domain under ``strict`` → ``ValueError``),
    so a bad request fails by itself and not its tick."""
    if scenario.topo is None:
        raise ValueError("the request places groups but names no "
                         "topology")
    placed = tuple(Placed(r.group(scenario.arch), r.domain)
                   for r in scenario.runs)
    per_domain = topology_mod.check_placement(scenario.topo, placed,
                                              strict=scenario.strict)
    return placed, max(map(len, per_domain.values()), default=1)


def lane_bucket(lanes: int, topo) -> int:
    """The lane bucket of ``lanes`` groups on one domain: a power of two,
    but not past the topology's largest domain (a domain holds no more
    groups than cores), nor below ``lanes``."""
    cap = max(d.n_cores for d in topo.domains)
    return max(lanes, min(backend_mod.bucket(lanes), cap))


def _lane_shape(scenario: "api.Scenario", lanes: int) -> "api.Scenario":
    """``scenario`` with ``lanes`` one-thread groups of its first kernel
    on its node's largest domain: a placement whose packed grid has
    exactly ``lanes`` lanes per domain."""
    widest = max(scenario.topo.domains, key=lambda d: d.n_cores)
    run = dataclasses.replace(scenario.runs[0], n=1, domain=widest.name)
    return dataclasses.replace(scenario, runs=(run,) * lanes)


def compile_group(scenarios: "list[api.Scenario]", verb: str,
                  rows: int, lanes: int | None = None) -> "api.Plan":
    """Compile the plan that serves a structure group at ``rows``
    capacity: the scenarios padded (by replicating the first — every
    scenario in a group shares the structure the plan freezes) up to
    the bucket, traced once.

    Prediction groups compile through :class:`repro.api.ScenarioBatch`
    to a batch plan whose numeric payload is swapped per tick; a placed
    group (``lanes`` given) compiles ``rows`` placements of ``lanes``
    groups on one domain, whose placements every tick swaps; a
    simulation group compiles its (single, fully-specified) scenario
    directly."""
    if verb == "simulate":
        return api.compile(scenarios[0], verb="simulate")
    if lanes is not None:
        shape = _lane_shape(scenarios[0], lanes)
        return api.compile(api.ScenarioBatch.of([shape] * rows),
                           verb="predict")
    padded = list(scenarios) + [scenarios[0]] * (rows - len(scenarios))
    return api.compile(api.ScenarioBatch.of(padded), verb="predict")


def warm_placements(scenario: "api.Scenario", rows: int,
                    lanes: int) -> list[tuple]:
    """Placement batches of ``rows`` rows and ``lanes`` lanes that
    together reach every recursion bound a placed solve on the node can
    compile (the bucket of the most threads on one domain, at least
    :data:`repro.core.sharing.MIN_RECURSION_BOUND`): row 0 holds
    ``lanes`` groups on the largest domain, the other rows are empty."""
    shape = _lane_shape(scenario, lanes)
    cap = max(d.n_cores for d in scenario.topo.domains)
    base = shape.runs[0]
    batches, bounds = [], set()
    for threads in range(lanes, cap + 1):
        bound = backend_mod.bucket(threads,
                                   minimum=sharing.MIN_RECURSION_BOUND)
        if bound in bounds:
            continue
        bounds.add(bound)
        runs = (dataclasses.replace(base, n=threads - lanes + 1),
                ) + shape.runs[1:]
        row = tuple(Placed(r.group(scenario.arch), r.domain) for r in runs)
        batches.append([row] + [()] * (rows - 1))
    return batches


def swap_arrays(scenarios: "list[api.Scenario]", rows: int, G: int
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack a tick's requests into the ``(rows, G)`` number arrays a
    cached (unplaced) batch plan swaps in via ``plan.run(cores=, f=,
    b_s=)``.  Rows past the live requests stay zero — the neutral
    padding the jax path would add internally anyway, so the bucketed
    solve is bit-for-bit the direct one."""
    n = np.zeros((rows, G))
    f = np.zeros((rows, G))
    bs = np.zeros((rows, G))
    for i, sc in enumerate(scenarios):
        for j, r in enumerate(sc.runs):
            spec = r.spec
            n[i, j] = r.n
            f[i, j] = spec.f[sc.arch]
            bs[i, j] = spec.bs[sc.arch]
    return n, f, bs


def padded_placements(placements, rows: int) -> tuple:
    """Per-request placement lists padded with empty rows up to the
    bucket, for a cached placed plan's ``run(placement=...)`` swap.
    Empty rows pack to all-masked grid lanes — neutral by the grid
    solver's masking contract."""
    live = tuple(placements)
    return live + ((),) * (rows - len(live))
