"""Differential-testing layer for the placement-batched solver.

The placed batch path (``solve_placed_batch`` → ``predict_placed_batch``
→ ``PlacedBatchPlan``) re-implements nothing: it *routes* B placed
scenarios through the same flattened array solver the single-scenario
``predict_placed`` uses.  These tests prove that claim differentially:

* at B = 1 the grid solve is **bit-for-bit** the per-scenario solver on
  the numpy path (the packed grid's K equals the lone scenario's own
  group maximum, so even padding widths coincide);
* across random ragged batches, every materialized ``scenario(i)``
  equals a lone ``predict_placed`` of the same placement, exactly
  (numpy) or to 1e-12 (jax, where padding to a different bucket width
  may shift the last ulp);
* the fused batch × ensemble simulate path is row-for-row identical to
  the explicit cross-product loop the known-issues doc used to
  prescribe;
* the occupancy mask — not luck — guards the result: NaN/inf-poisoned
  padding lanes change nothing, and empty padded domains attain exactly
  zero bandwidth.

Random topologies come from the presets, placements/raggedness/(f, b_s)
from hypothesis (real or the deterministic fallback shim).
"""

import random

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import api
from repro.core import backend
from repro.core.sharing import Group, solve_batch, solve_placed_batch
from repro.api.results import from_topology_prediction
from repro.core.topology import (PRESETS, Placed, check_placement,
                                 pack_placed, predict_placed,
                                 predict_placed_batch, preset)

TOPOLOGIES = ["CLX", "CLX-2S", "ROME-2S-NPS4", "TPUv5e-pod4"]

topo_names = st.sampled_from(TOPOLOGIES)
seeds = st.integers(min_value=0, max_value=10**6)


def _random_placements(rng, topo, *, max_groups=5, max_n=4):
    """A random ragged placement list (n = 0 groups included — they are
    genuine occupants of the grid, not padding)."""
    out = []
    for j in range(rng.randint(0, max_groups)):
        out.append(Placed(
            Group(n=rng.randint(0, max_n),
                  f=rng.uniform(0.05, 1.0),
                  bs=rng.uniform(20.0, 220.0),
                  name=f"g{j}"),
            rng.choice(topo.domain_names)))
    return out


# ---------------------------------------------------------------------------
# B = 1: bit-for-bit with the single-scenario solver
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(tname=topo_names, seed=seeds)
def test_placed_batch_b1_bit_for_bit(tname, seed):
    rng = random.Random(seed)
    topo = preset(tname)
    placements = _random_placements(rng, topo)
    res = predict_placed_batch(topo, [placements], strict=False,
                               backend="numpy")
    ref = predict_placed(topo, placements, strict=False, backend="numpy")
    # Dataclass equality covers every float of every domain: b_overlap,
    # alphas, per-group bandwidths, input-order bw_group — bit-for-bit.
    assert res.scenario(0) == ref
    assert res.bw_group[0] == ref.bw_group
    # total_bw is a reduction — numpy's pairwise sum may order it
    # differently from the per-domain Python sum; the summands are
    # bit-identical (asserted above), so only the last ulp can move.
    assert float(res.total_bw[0]) == pytest.approx(ref.total_bw, rel=1e-14)


@settings(max_examples=40, deadline=None)
@given(tname=topo_names, seed=seeds,
       b=st.integers(min_value=1, max_value=9))
def test_placed_batch_rows_match_singles(tname, seed, b):
    rng = random.Random(seed)
    topo = preset(tname)
    batch = [_random_placements(rng, topo) for _ in range(b)]
    res = predict_placed_batch(topo, batch, strict=False, backend="numpy")
    for i, placements in enumerate(batch):
        assert res.scenario(i) == predict_placed(
            topo, placements, strict=False, backend="numpy")


@pytest.mark.skipif(not backend.HAVE_JAX, reason="jax not importable")
def test_placed_batch_jax_matches_numpy_tightly():
    # Cross-padding-width comparisons on jax may shift the last ulp;
    # the contract there is 1e-12, not bitwise.
    rng = random.Random(0)
    topo = preset("ROME-2S-NPS4")
    batch = [_random_placements(rng, topo) for _ in range(12)]
    ref = predict_placed_batch(topo, batch, strict=False, backend="numpy")
    got = predict_placed_batch(topo, batch, strict=False, backend="jax")
    np.testing.assert_allclose(got.shares.bw_group, ref.shares.bw_group,
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(got.shares.util, ref.shares.util,
                               rtol=1e-12, atol=0)


@settings(max_examples=40, deadline=None)
@given(seed=seeds)
def test_facade_placed_batch_matches_single_predicts(seed):
    # Same differential claim one layer up: ScenarioBatch → compiled
    # PlacedBatchPlan rows == per-scenario facade predicts.
    rng = random.Random(seed)
    kernels = ["DCOPY", "DDOT2", "DAXPY", "Schoenauer"]
    domains = ("CLX/s0/d0", "CLX/s1/d0")
    scens = []
    for _ in range(rng.randint(1, 6)):
        sc = api.Scenario.on("CLX").using("CLX-2S").options(strict=False)
        for _ in range(rng.randint(1, 4)):
            sc = sc.placed(rng.choice(kernels), rng.randint(0, 6),
                           rng.choice(domains))
        scens.append(sc)
    res = api.predict(api.ScenarioBatch.of(scens), backend="numpy")
    assert isinstance(res, api.PlacedBatchPrediction)
    for i, sc in enumerate(scens):
        assert res[i] == api.predict(sc, backend="numpy")


# ---------------------------------------------------------------------------
# Fused batch × ensemble == explicit cross-product
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(seed=seeds,
       b=st.integers(min_value=1, max_value=3),
       e=st.integers(min_value=1, max_value=4))
def test_fused_ensemble_equals_explicit_cross_product(seed, b, e):
    rng = random.Random(seed)
    ranks = rng.randint(2, 4)       # simulate batches must be rectangular
    scens = []
    for i in range(b):
        sc = (api.Scenario.on("CLX").ranks(ranks)
              .step("DCOPY", rng.uniform(0.5, 4.0) * 1e6, tag="w"))
        if rng.random() < 0.5:
            sc = sc.barrier()
        scens.append(sc.with_noise(rng.uniform(1e-6, 1e-4),
                                   seed=rng.randint(0, 100), ensemble=e))
    fused = api.simulate(api.ScenarioBatch.of(scens))
    assert fused.n_scenarios == b * e
    for i, sc in enumerate(scens):
        solo = api.simulate(sc)     # the explicit per-scenario loop
        rows = fused.rows_for(i)
        assert len(rows) == e
        for m, row in enumerate(rows):
            assert solo.records(m) == fused.records(row)
            assert solo.t_end[m] == fused.t_end[row]


# ---------------------------------------------------------------------------
# Mask correctness: the mask, not luck, guards the result
# ---------------------------------------------------------------------------


def test_empty_padded_domains_contribute_exactly_zero():
    topo = preset("ROME-2S-NPS4")          # 8 domains
    # Populate only two of the eight; six domain rows are pure padding.
    placements = [
        Placed(Group(4, 0.3, 120.0, "a"), "ROME/s0/d1"),
        Placed(Group(2, 0.8, 90.0, "b"), "ROME/s1/d3"),
    ]
    res = predict_placed_batch(topo, [placements], backend="numpy")
    dom_bw = res.shares.domain_bw[0]
    occupied = {"ROME/s0/d1", "ROME/s1/d3"}
    for d, name in enumerate(topo.domain_names):
        if name not in occupied:
            assert dom_bw[d] == 0.0                       # exactly
            assert res.shares.b_overlap[0, d] == 0.0
    # ...and never perturb the occupied domains: each matches a lone
    # single-domain solve of just its groups, bit for bit.
    lone = solve_batch(np.array([[4.0], [2.0]]),
                       np.array([[0.3], [0.8]]),
                       np.array([[120.0], [90.0]]), backend="numpy")
    d1 = topo.domain_names.index("ROME/s0/d1")
    d3 = topo.domain_names.index("ROME/s1/d3")
    assert res.shares.bw_group[0, d1, 0] == lone.bw_group[0, 0]
    assert res.shares.bw_group[0, d3, 0] == lone.bw_group[1, 0]


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf, 1e300])
def test_poisoned_padding_is_guarded_by_the_mask(poison):
    # Deliberately poison every masked-out lane of a packed grid.  If
    # the implementation multiplied by the mask (0 · NaN = NaN) or
    # simply trusted the padding to be zero, this would blow up; the
    # select-before-solve contract makes the result bit-identical.
    topo = preset("CLX-2S")
    rng = random.Random(7)
    batch = [_random_placements(rng, topo) for _ in range(6)]
    grid = pack_placed(topo, batch, strict=False)
    ref = solve_placed_batch(grid.n, grid.f, grid.bs, mask=grid.mask,
                             backend="numpy")
    bad = ~grid.mask
    n_p, f_p, bs_p = grid.n.copy(), grid.f.copy(), grid.bs.copy()
    n_p[bad] = poison
    f_p[bad] = poison
    bs_p[bad] = poison
    got = solve_placed_batch(n_p, f_p, bs_p, mask=grid.mask,
                             backend="numpy")
    np.testing.assert_array_equal(got.bw_group, ref.bw_group)
    np.testing.assert_array_equal(got.b_overlap, ref.b_overlap)
    np.testing.assert_array_equal(got.alphas, ref.alphas)
    np.testing.assert_array_equal(got.util, ref.util)
    assert np.isfinite(got.bw_group).all()


def test_default_mask_is_occupancy_by_thread_count():
    # Without an explicit mask, n > 0 defines occupancy — and masked
    # lanes are forced neutral before the solve.
    n = np.array([[[2.0, 0.0], [3.0, 0.0]]])
    f = np.array([[[0.5, np.nan], [0.25, np.nan]]])
    bs = np.array([[[100.0, np.nan], [80.0, np.nan]]])
    res = solve_placed_batch(n, f, bs, backend="numpy")
    assert np.isfinite(res.bw_group).all()
    assert res.f[0, 0, 1] == 0.0 and res.bs[0, 1, 1] == 0.0
    ref = solve_batch(np.array([[2.0], [3.0]]), np.array([[0.5], [0.25]]),
                      np.array([[100.0], [80.0]]), backend="numpy")
    np.testing.assert_array_equal(res.bw_group[0, :, 0], ref.bw_group[:, 0])


def test_genuine_zero_thread_groups_stay_occupied():
    # A placed n = 0 group is an occupant (neutral in Eqs. 4–5 but
    # present in results), distinct from padding: its (f, bs) survive
    # into the materialized scenario.
    topo = preset("CLX")
    placements = [Placed(Group(0, 0.9, 150.0, "idle"), "CLX/d0"),
                  Placed(Group(4, 0.3, 100.0, "busy"), "CLX/d0")]
    res = predict_placed_batch(topo, [placements], backend="numpy")
    assert bool(res.grid.mask[0, 0, 0]) and bool(res.grid.mask[0, 0, 1])
    sc = res.scenario(0)
    assert sc.placements[0].group == placements[0].group
    assert sc.bw_group[0] == 0.0
    assert sc == predict_placed(topo, placements, backend="numpy")


# ---------------------------------------------------------------------------
# Grid packing invariants
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(tname=topo_names, seed=seeds,
       b=st.integers(min_value=1, max_value=8))
def test_pack_placed_roundtrip(tname, seed, b):
    rng = random.Random(seed)
    topo = preset(tname)
    batch = [_random_placements(rng, topo) for _ in range(b)]
    grid = pack_placed(topo, batch, strict=False)
    D = len(topo.domain_names)
    assert grid.n.shape[0] == b and grid.n.shape[1] == D
    assert grid.mask.sum() == sum(len(p) for p in batch)
    for i, placements in enumerate(batch):
        assert len(grid.slots[i]) == len(placements)
        for j, p in enumerate(placements):
            d, k = grid.slots[i][j]
            assert topo.domain_names[d] == p.domain
            assert grid.n[i, d, k] == p.group.n
            assert grid.f[i, d, k] == p.group.f
            assert grid.bs[i, d, k] == p.group.bs
            assert bool(grid.mask[i, d, k])
    # Unmasked lanes are exactly neutral zeros.
    assert grid.n[~grid.mask].sum() == 0.0
    assert grid.f[~grid.mask].sum() == 0.0


def test_pack_placed_validation_messages():
    topo = preset("CLX")
    good = [Placed(Group(2, 0.5, 100.0), "CLX/d0")]
    with pytest.raises(KeyError, match="scenario 1.*unknown domain"):
        pack_placed(topo, [good, [Placed(Group(1, 0.5, 100.0), "nope")]])
    cap = topo.domain("CLX/d0").n_cores
    with pytest.raises(ValueError, match="overcommitted"):
        pack_placed(topo, [[Placed(Group(cap + 1, 0.5, 100.0),
                                   "CLX/d0")]])
    # strict=False allows overcommit, mirroring predict_placed.
    grid = pack_placed(topo, [[Placed(Group(cap + 1, 0.5, 100.0),
                                      "CLX/d0")]], strict=False)
    assert grid.n[0, 0, 0] == cap + 1


# ---------------------------------------------------------------------------
# Flat packing == the per-scenario packer, bit for bit
# ---------------------------------------------------------------------------


def _reference_pack(topology, placements_batch, *, strict=True, lanes=1):
    """The per-scenario packer the flat pass replaced: each scenario
    checked and grouped by :func:`check_placement`, the grid written one
    cell at a time."""
    placements_batch = tuple(tuple(p) for p in placements_batch)
    B, D = len(placements_batch), len(topology.domains)
    per_scenario = []
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
    slots = []
    for b, per_domain in enumerate(per_scenario):
        slot_of = {}
        for d, entries in per_domain.items():
            for k, (idx, g) in enumerate(entries):
                n[b, d, k] = g.n
                f[b, d, k] = g.f
                bs[b, d, k] = g.bs
                mask[b, d, k] = True
                slot_of[idx] = (d, k)
        slots.append(tuple(slot_of[j]
                           for j in range(len(placements_batch[b]))))
    return n, f, bs, mask, tuple(slots)


def _within_capacity(rng, topo, *, max_groups=5, max_n=4):
    """A random placement list that overcommits no domain."""
    free = {d.name: d.n_cores for d in topo.domains}
    out = []
    for j in range(rng.randint(0, max_groups)):
        name = rng.choice(topo.domain_names)
        n = rng.randint(0, min(max_n, free[name]))
        free[name] -= n
        out.append(Placed(Group(n, rng.uniform(0.05, 1.0),
                                rng.uniform(20.0, 220.0), f"g{j}"), name))
    return out


def _sweep_draw(rng, topo, B, *, max_groups=3, max_threads=4):
    """B placement lists drawn as the placed-sweep benchmark draws them:
    1 to ``max_groups`` groups of Table II kernels, each on a random
    domain within its free cores."""
    from repro.core.table2 import TABLE2
    kernels = sorted(TABLE2)
    out = []
    for _ in range(B):
        threads = {d.name: 0 for d in topo.domains}
        row = []
        for _ in range(rng.randint(1, max_groups)):
            dom = rng.choice(topo.domains)
            free = dom.n_cores - threads[dom.name]
            if not free:
                continue
            n = rng.randint(1, min(max_threads, free))
            threads[dom.name] += n
            row.append(Placed(Group.of(TABLE2[rng.choice(kernels)],
                                       "ROME", n), dom.name))
        out.append(row)
    return out


def _case(name):
    """``(topology, batch, strict, lanes)`` of one packing case."""
    rng = random.Random(name)
    if name.startswith("preset:"):
        topo = preset(name.split(":", 1)[1])
        return topo, [_random_placements(rng, topo, max_n=12)
                      for _ in range(40)], False, 1
    if name.startswith("strict:"):
        topo = preset(name.split(":", 1)[1])
        return topo, [_within_capacity(rng, topo)
                      for _ in range(40)], True, 1
    topo = preset("ROME-2S-NPS4")
    if name == "overcommit-not-strict":
        cap = topo.domains[0].n_cores
        batch = [_within_capacity(rng, topo) for _ in range(12)]
        batch[5] = [Placed(Group(cap, 0.5, 100.0), topo.domain_names[0]),
                    Placed(Group(cap, 0.4, 90.0), topo.domain_names[0])]
        return topo, batch, False, 1
    if name == "lanes-above-K":
        return topo, [_within_capacity(rng, topo, max_groups=2)
                      for _ in range(12)], True, 6
    if name == "empty-scenarios":
        batch = [_within_capacity(rng, topo) for _ in range(12)]
        for b in (0, 4, 11):
            batch[b] = []
        return topo, batch, True, 2
    if name == "all-empty":
        return topo, [[] for _ in range(5)], True, 1
    if name == "no-scenarios":
        return topo, [], True, 3
    if name == "idle-domains":
        # Every group on one domain: seven of eight stay idle.
        dom = topo.domain_names[5]
        return topo, [[Placed(Group(1, 0.1 * (j + 1), 50.0 + j), dom)
                       for j in range(b % 4)] for b in range(9)], True, 1
    if name == "placed-sweep-2^12":
        return topo, _sweep_draw(rng, topo, 1 << 12), True, 1
    raise KeyError(name)


PACK_CASES = ([f"preset:{t}" for t in sorted(PRESETS)]
              + [f"strict:{t}" for t in TOPOLOGIES]
              + ["overcommit-not-strict", "lanes-above-K",
                 "empty-scenarios", "all-empty", "no-scenarios",
                 "idle-domains", "placed-sweep-2^12"])


@pytest.mark.parametrize("name", PACK_CASES)
def test_flat_pack_is_the_per_scenario_pack_bit_for_bit(name):
    topo, batch, strict, lanes = _case(name)
    grid = pack_placed(topo, batch, strict=strict, lanes=lanes)
    *arrays, slots = _reference_pack(topo, batch, strict=strict,
                                     lanes=lanes)
    for want, got in zip(arrays, (grid.n, grid.f, grid.bs, grid.mask)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    assert len(grid.slots) == len(slots) == len(batch)
    for b, want in enumerate(slots):
        assert grid.slots[b] == want
        assert grid.slots[b - len(batch)] == want
    with pytest.raises(IndexError):
        grid.slots[len(batch)]


def _bad(topo, kind):
    """One scenario's placements that fail ``kind``: an unknown domain,
    an overcommitted domain, or both (the overcommit listed first)."""
    cap = topo.domains[0].n_cores
    over = [Placed(Group(cap, 0.5, 100.0), topo.domain_names[0]),
            Placed(Group(1, 0.5, 100.0), topo.domain_names[0])]
    unknown = [Placed(Group(1, 0.5, 100.0), "nowhere")]
    return {"unknown": unknown, "over": over,
            "over-then-unknown": over + unknown}[kind]


@pytest.mark.parametrize("faults,strict,want", [
    ({3: "unknown"}, True, (3, KeyError)),
    ({2: "over"}, True, (2, ValueError)),
    ({2: "over"}, False, None),
    ({3: "unknown", 5: "over"}, True, (3, KeyError)),
    ({2: "over", 4: "unknown"}, True, (2, ValueError)),
    ({2: "over", 4: "unknown"}, False, (4, KeyError)),
    ({1: "over-then-unknown"}, True, (1, KeyError)),
    ({6: "over-then-unknown", 7: "over"}, False, (6, KeyError)),
    ({0: "over", 1: "unknown"}, True, (0, ValueError)),
])
def test_flat_pack_raises_as_check_placement(faults, strict, want):
    rng = random.Random(11)
    topo = preset("ROME-2S-NPS4")
    batch = [_within_capacity(rng, topo) for _ in range(8)]
    for b, kind in faults.items():
        batch[b] = _bad(topo, kind)
    if want is None:
        grid = pack_placed(topo, batch, strict=strict)
        assert grid.mask.sum() == sum(map(len, batch))
        return
    b, kind = want
    with pytest.raises(kind) as alone:
        check_placement(topo, batch[b], strict=strict)
    message = f"scenario {b}: {alone.value.args[0]}"
    for pack in (pack_placed, _reference_pack):
        with pytest.raises(kind) as got:
            pack(topo, batch, strict=strict)
        assert type(got.value) is kind
        assert got.value.args == (message,)


def _placed_plan_and_swap(swap_groups):
    """A compiled placed batch of 6 scenarios of 1–2 groups each, and a
    swap of ``swap_groups`` groups per scenario (some scenarios empty,
    some with more groups than the plan's rows)."""
    domains = preset("CLX-2S").domain_names
    kernels = ["DCOPY", "DDOT2", "DAXPY", "Schoenauer"]
    scens = []
    for i in range(6):
        sc = (api.Scenario.on("CLX").using("CLX-2S")
              .placed(kernels[i % 4], 1 + i % 3, domains[i % 2]))
        if i % 2:
            sc = sc.placed("STREAM", 2, domains[0])
        scens.append(sc)
    plan = api.compile(api.ScenarioBatch.of(scens))
    rng = random.Random(str(swap_groups))
    from repro.core.table2 import TABLE2
    swap = [[Placed(Group.of(TABLE2[rng.choice(kernels)], "CLX",
                             rng.randint(1, 4)), domains[j % 2])
             for j in range(swap_groups[i % len(swap_groups)])]
            for i in range(6)]
    return plan, swap


@pytest.mark.parametrize("swap_groups", [(1,), (2,), (0, 3), (4, 1, 0)])
def test_placement_swap_results_and_labels(swap_groups):
    plan, swap = _placed_plan_and_swap(swap_groups)
    pred = plan.run(placement=swap, backend="numpy")
    assert len(pred.provenance) == len(swap)
    for i, placements in enumerate(swap):
        row = plan.provenance[i]
        labels = tuple(row[j] if j < len(row) else ""
                       for j in range(len(placements)))
        assert pred.provenance[i] == labels
        assert pred.provenance[i - len(swap)] == labels
        lone = predict_placed(plan.topo, placements, backend="numpy")
        want = from_topology_prediction(lone, arch="CLX",
                                        provenance=labels)
        assert pred[i].to_dict() == want.to_dict()
        assert pred.bw_group[i] == lone.bw_group
    with pytest.raises(IndexError):
        pred.provenance[len(swap)]


def test_placement_swap_pack_span_counts_groups_and_lanes():
    from repro.obs import trace
    plan, swap = _placed_plan_and_swap((4, 1, 0))
    was = trace.enabled()
    trace.enable(clear_events=True)
    try:
        pred = plan.run(placement=swap, backend="numpy")
        packs = [ev for ev in trace.events()
                 if ev[0] == "span" and ev[1] == "api.plan.pack"]
    finally:
        if not was:
            trace.disable()
    # Four groups over two domains take two lanes; the plan has one.
    assert plan.grid.n.shape[2] == 1 and pred.raw.grid.n.shape[2] == 2
    assert len(packs) == 1
    assert packs[0][6] == {"groups": sum(map(len, swap)), "K": 2}
