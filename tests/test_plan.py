"""Compiled execution plans + the backend substrate.

Acceptance gates of the plan PR: ``compile(x).run()`` must be
bit-for-bit ``predict(x)`` / ``simulate(x)`` across backends; re-running
a plan with swapped ``(f, b_s)`` / ``cores`` must match a fresh compile
of the modified scenarios; same-bucket plans must share jitted solvers
through the substrate's process-wide cache; and the ``auto`` cutoff /
chunking knobs must be honored everywhere.  Works with real hypothesis
or the deterministic fallback shim.
"""

import random

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import api
from repro.core import backend, sharing, table2

BACKENDS = ["numpy"] + (["jax"] if backend.HAVE_JAX else [])
KERNELS = sorted(table2.TABLE2)
UTILS = ["recursion", "queue", 0.7]

kernel_names = st.sampled_from(KERNELS)
archs = st.sampled_from(table2.ARCHS)
utils = st.sampled_from(UTILS)


def _scenario_from(arch, util, ks, ns):
    sc = api.Scenario.on(arch).options(utilization=util)
    for k, n in zip(ks, ns):
        sc = sc.run(k, n)
    return sc


def _sweep_batch(b, arch="CLX", **options):
    base = api.Scenario.on(arch, **options).run("DCOPY", 1).run("DDOT2", 1)
    na = 1 + np.arange(b) % 19
    return base.batch(np.stack([na, 20 - na], axis=-1))


# ---------------------------------------------------------------------------
# compile(x).run() == predict(x), bit for bit
# ---------------------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(arch=archs, util=utils,
       ks=st.lists(kernel_names, min_size=1, max_size=5),
       seed=st.integers(min_value=0, max_value=10**6))
def test_scalar_plan_bit_for_bit(arch, util, ks, seed):
    rng = random.Random(seed)
    ns = [rng.randint(0, 12) for _ in ks]
    sc = _scenario_from(arch, util, ks, ns)
    plan = api.compile(sc, verb="predict")
    assert isinstance(plan, api.ScalarPlan)
    assert plan.kind == "scalar"
    ref = api.predict(sc)
    got = plan.run()
    assert got == ref
    assert plan.run() == ref  # re-running re-executes, identically


@settings(max_examples=30, deadline=None)
@given(util=utils, seed=st.integers(min_value=0, max_value=10**6))
def test_placed_plan_bit_for_bit(util, seed):
    rng = random.Random(seed)
    from repro.core import topology
    topo = topology.preset("CLX-2S")
    sc = (api.Scenario.on("CLX").using(topo)
          .options(utilization=util, strict=False))
    for _ in range(rng.randint(1, 5)):
        sc = sc.placed(rng.choice(KERNELS), rng.randint(1, 3),
                       rng.choice(topo.domain_names))
    plan = api.compile(sc, verb="predict")
    assert isinstance(plan, api.PlacedPlan)
    assert plan.run() == api.predict(sc)


@settings(max_examples=20, deadline=None)
@given(util=utils, seed=st.integers(min_value=0, max_value=10**6),
       b=st.integers(min_value=1, max_value=12))
def test_batch_plan_bit_for_bit(util, seed, b):
    rng = random.Random(seed)
    scens = []
    for _ in range(b):
        g = rng.randint(1, 4)
        ks = [rng.choice(KERNELS) for _ in range(g)]
        ns = [rng.randint(0, 12) for _ in range(g)]
        scens.append(_scenario_from("CLX", util, ks, ns))
    batch = api.ScenarioBatch.of(scens)
    plan = api.compile(batch, verb="predict")
    assert isinstance(plan, api.BatchPlan)
    for bk in BACKENDS:
        ref = api.predict(batch, backend=bk)
        got = plan.run(backend=bk)
        assert got.engine == ref.engine == bk
        np.testing.assert_array_equal(got.bw_group, ref.bw_group)
        np.testing.assert_array_equal(got.alphas, ref.alphas)
        np.testing.assert_array_equal(got.b_overlap, ref.b_overlap)
        for i in range(b):
            assert got[i] == ref[i]


def test_predict_is_compile_and_run_sugar():
    batch = _sweep_batch(8)
    assert api.predict(batch).engine == api.compile(batch).engine
    sc = api.Scenario.on("CLX").run("DCOPY", 4)
    assert api.compile(sc).run() == api.predict(sc)


# ---------------------------------------------------------------------------
# Swapped numbers == fresh compile
# ---------------------------------------------------------------------------


def test_swap_f_bs_matches_fresh_compile():
    plan = api.compile(_sweep_batch(12))
    f2 = plan.f * 0.9
    bs2 = plan.bs * 1.15
    for bk in BACKENDS:
        got = plan.run(f=f2, b_s=bs2, backend=bk)
        ref = sharing.solve_batch(plan.n, f2, bs2, backend=bk)
        np.testing.assert_array_equal(got.bw_group, ref.bw_group)
        np.testing.assert_array_equal(got.alphas, ref.alphas)
        np.testing.assert_array_equal(got.b_overlap, ref.b_overlap)
    # And against a genuinely re-built scenario batch (synthetic specs
    # carrying the swapped numbers).
    scens = [api.Scenario.on("CLX")
             .run((f2[i, 0], bs2[i, 0]), int(plan.n[i, 0]))
             .run((f2[i, 1], bs2[i, 1]), int(plan.n[i, 1]))
             for i in range(len(plan))]
    fresh = api.predict(api.ScenarioBatch.of(scens), backend="numpy")
    np.testing.assert_array_equal(
        plan.run(f=f2, b_s=bs2, backend="numpy").bw_group, fresh.bw_group)


def test_swap_cores_matches_fresh_compile():
    base = api.Scenario.on("CLX").run("DCOPY", 1).run("DDOT2", 1)
    plan = api.compile(base.batch(np.stack(
        [1 + np.arange(10), 11 - np.arange(10)], axis=-1)))
    counts2 = np.stack([2 + np.arange(10), 12 - np.arange(10)], axis=-1)
    got = plan.run(cores=counts2, backend="numpy")
    ref = api.predict(base.batch(counts2), backend="numpy")
    np.testing.assert_array_equal(got.bw_group, ref.bw_group)
    for i in range(10):
        assert got[i] == ref[i]


def test_scalar_plan_swaps():
    sc = api.Scenario.on("CLX").run("DCOPY", 6).run("DDOT2", 6)
    plan = api.compile(sc)
    got = plan.run(cores=[4, 8])
    ref = api.predict(api.Scenario.on("CLX").run("DCOPY", 4)
                      .run("DDOT2", 8))
    assert got.bw_group == ref.bw_group
    got2 = plan.run(f=[0.3, 0.4], b_s=[100.0, 90.0])
    ref2 = api.predict(api.Scenario.on("CLX")
                       .run((0.3, 100.0), 6).run((0.4, 90.0), 6))
    assert got2.bw_group == ref2.bw_group


def test_swap_shape_errors():
    plan = api.compile(_sweep_batch(6))
    with pytest.raises(ValueError, match="broadcastable"):
        plan.run(f=np.ones((3, 5)))
    sc_plan = api.compile(api.Scenario.on("CLX").run("DCOPY", 4))
    with pytest.raises(ValueError, match="1 groups"):
        sc_plan.run(cores=[1, 2, 3])


# ---------------------------------------------------------------------------
# compile(x).run() == simulate(x)
# ---------------------------------------------------------------------------


def _sim_scenario():
    MB = 1e6
    return (api.Scenario.on("CLX").ranks(6)
            .with_noise(6e-5, seed=0, ensemble=4)
            .step("Schoenauer", 8 * MB, tag="symgs")
            .step("DDOT2", 2 * MB, tag="ddot2")
            .barrier()
            .step("DAXPY", 6 * MB, tag="daxpy"))


@pytest.mark.parametrize("bk", BACKENDS)
def test_simulate_plan_bit_for_bit(bk):
    sc = _sim_scenario()
    plan = api.compile(sc)           # noise/programs => simulate inferred
    assert isinstance(plan, api.SimulatePlan)
    assert plan.kind == "simulate"
    ref = api.simulate(sc, t_max=60.0, backend=bk)
    got = plan.run(t_max=60.0, backend=bk)
    assert got.engine == ref.engine == f"desync-{bk}"
    assert got.n_scenarios == ref.n_scenarios == 4
    for b in range(4):
        assert got.records(b) == ref.records(b)
    np.testing.assert_array_equal(got.t_end, ref.t_end)
    # The trace froze the noise draws: re-running is deterministic.
    again = plan.run(t_max=60.0, backend=bk)
    for b in range(4):
        assert again.records(b) == got.records(b)


def test_group_mode_compiles_to_simulate_on_request():
    sc = (api.Scenario.on("CLX")
          .run("DCOPY", 2, bytes=1e6).run("DDOT2", 2, bytes=1e6))
    plan = api.compile(sc, verb="simulate")
    ref = api.simulate(sc)
    assert plan.run().records(0) == ref.records(0)
    # Without a verb, group mode means predict.
    assert isinstance(api.compile(sc), api.ScalarPlan)
    # ...but declared noise means simulate — for single scenarios AND
    # batches (a noisy batch must not silently drop its noise).
    noisy = sc.with_noise(5e-5, seed=3)
    assert isinstance(api.compile(noisy), api.SimulatePlan)
    nb = api.ScenarioBatch.of([noisy, sc.with_noise(5e-5, seed=4)])
    assert isinstance(api.compile(nb), api.SimulatePlan)


def test_simulate_plan_swap_specs():
    MB = 1e6
    sc = (api.Scenario.on("CLX").ranks(4)
          .with_noise(5e-5, seed=2, ensemble=2)
          .step((0.3, 100.0), 4 * MB, name="phase")
          .step("DDOT2", MB))
    plan = api.compile(sc)
    got = plan.run(specs={"phase": (0.5, 80.0)})
    sc2 = (api.Scenario.on("CLX").ranks(4)
           .with_noise(5e-5, seed=2, ensemble=2)
           .step((0.5, 80.0), 4 * MB, name="phase")
           .step("DDOT2", MB))
    ref = api.simulate(sc2)
    for b in range(2):
        assert got.records(b) == ref.records(b)
    # A typo'd kernel name must not become a silent no-op swap.
    with pytest.raises(KeyError, match="did you mean 'phase'"):
        plan.run(specs={"phse": (0.5, 80.0)})


def test_simulate_batch_must_be_rectangular():
    a = api.Scenario.on("CLX").ranks(8).step("DCOPY", 4e6)
    b = api.Scenario.on("CLX").ranks(4).step("DCOPY", 4e6)
    with pytest.raises(ValueError, match="rectangular"):
        api.simulate(api.ScenarioBatch.of([a, b]))
    with pytest.raises(ValueError, match="rectangular"):
        api.compile(api.ScenarioBatch.of([b, a]), verb="simulate")


def test_simulate_mixed_t_max_raises_at_run_without_override():
    a = api.Scenario.on("CLX").ranks(2).step("DCOPY", 1e6)
    b = a.options(t_max=1.0)
    plan = api.compile(api.ScenarioBatch.of([a, b]), verb="simulate")
    with pytest.raises(ValueError, match="t_max"):
        plan.run()
    assert plan.run(t_max=5.0).n_scenarios == 2


# ---------------------------------------------------------------------------
# Deterministic splittable seeds
# ---------------------------------------------------------------------------


def test_member_seed_streams_are_independent():
    # The old convention Random(seed + member) aliased adjacent
    # ensembles: (0, 1) and (1, 0) shared a stream.  The split must not.
    assert api.derive_member_seed(0, 1) != api.derive_member_seed(1, 0)
    seen = {api.derive_member_seed(s, m)
            for s in range(8) for m in range(64)}
    assert len(seen) == 8 * 64


def test_repeated_simulate_is_reproducible():
    sc = (api.Scenario.on("CLX").ranks(3).step("DCOPY", 1e6)
          .with_noise(1e-5, seed=7, ensemble=5))
    r1 = api.simulate(sc)
    r2 = api.simulate(sc)
    np.testing.assert_array_equal(r1.t_end, r2.t_end)
    # Different base seeds give different draws.
    r3 = api.simulate(sc.with_noise(1e-5, seed=8, ensemble=5))
    assert not np.array_equal(r1.t_end, r3.t_end)


# ---------------------------------------------------------------------------
# Substrate: resolve policy, cutoff knob, jit cache, chunking
# ---------------------------------------------------------------------------


def test_resolve_explicit_backends():
    assert backend.resolve("numpy") == "numpy"
    assert backend.resolve("auto", 4, prefer="numpy") == "numpy"
    with pytest.raises(ValueError, match="unknown backend"):
        backend.resolve("bogus")
    if backend.HAVE_JAX:
        assert backend.resolve("jax") == "jax"
        assert backend.resolve("auto", None) == "jax"
    else:
        with pytest.raises(RuntimeError, match="jax"):
            backend.resolve("jax")
        assert backend.resolve("auto", None) == "numpy"


def test_cutoff_env_and_override(monkeypatch):
    monkeypatch.delenv(backend.JAX_CUTOFF_ENV, raising=False)
    assert backend.jax_cutoff() == backend.DEFAULT_JAX_CUTOFF
    monkeypatch.setenv(backend.JAX_CUTOFF_ENV, "4")
    assert backend.jax_cutoff() == 4
    assert backend.jax_cutoff(16) == 16           # per-call wins over env
    if backend.HAVE_JAX:
        assert backend.resolve("auto", 8) == "jax"
        assert backend.resolve("auto", 8, jax_cutoff=16) == "numpy"
    monkeypatch.setenv(backend.JAX_CUTOFF_ENV, "not-a-number")
    with pytest.raises(ValueError, match="REPRO_JAX_CUTOFF"):
        backend.jax_cutoff()


@pytest.mark.skipif(not backend.HAVE_JAX, reason="jax not importable")
def test_cutoff_honored_by_facade_and_solvers(monkeypatch):
    batch = _sweep_batch(8)
    assert api.predict(batch).engine == "numpy"            # below 64
    assert api.predict(batch, jax_cutoff=4).engine == "jax"
    monkeypatch.setenv(backend.JAX_CUTOFF_ENV, "4")
    assert api.predict(batch).engine == "jax"
    monkeypatch.delenv(backend.JAX_CUTOFF_ENV)
    # Scenario-level knob flows through compile — and survives a run
    # that re-resolves (backend="auto" must not discard it).
    small = _sweep_batch(8, jax_cutoff=2)
    plan = api.compile(small)
    assert plan.engine == "jax"
    assert plan.run(backend="auto").engine == "jax"
    # Placed scenarios honor the knob too (their topology solve is a
    # batched solve_batch call like any other).
    placed = (api.Scenario.on("CLX").using("CLX-2S")
              .placed("DCOPY", 4, "CLX/s0/d0"))
    ref = api.predict(placed)
    got = api.predict(placed, jax_cutoff=1)
    assert got.bw_group == pytest.approx(ref.bw_group, rel=1e-9)
    pplan = api.compile(placed.options(jax_cutoff=1, chunk=4))
    assert pplan.solver_kwargs["jax_cutoff"] == 1
    assert pplan.solver_kwargs["chunk"] == 4
    # And the pre-facade batched paths resolve through the same policy.
    assert sharing.resolve_backend("auto", 8) == "numpy"
    assert sharing.resolve_backend("auto", 8, jax_cutoff=2) == "jax"
    from repro.calibrate import fit as fit_mod
    from repro.calibrate.traces import synthesize_scaling_trace
    traces = [synthesize_scaling_trace(k, "CLX", seed=0)
              for k in ("DCOPY", "DDOT2")]
    assert fit_mod.fit_scaling(traces).backend == "numpy"   # 2 < 64
    assert fit_mod.fit_scaling(traces, jax_cutoff=1).backend == "jax"


@pytest.mark.skipif(not backend.HAVE_JAX, reason="jax not importable")
def test_jit_cache_shared_across_same_bucket_plans():
    # B = 130 and B = 200 both pad into the 256-row bucket (G = 2,
    # same n_max bucket), so the second plan's run must reuse the
    # first's compiled solver: hits grow, misses don't.
    p1 = api.compile(_sweep_batch(130))
    p1.run(backend="jax")
    assert p1.bucket == (256, 2)
    s1 = backend.cache_stats()
    p2 = api.compile(_sweep_batch(200))
    assert p2.bucket == p1.bucket
    p2.run(backend="jax")
    s2 = backend.cache_stats()
    assert s2["misses"] == s1["misses"]
    assert s2["hits"] == s1["hits"] + 1
    ref = api.predict(_sweep_batch(200), backend="jax")
    np.testing.assert_array_equal(
        p2.run(backend="jax").bw_group, ref.bw_group)


def _placed_sweep(b, *, ragged=False, arch="CLX", **options):
    """B placed scenarios on CLX-2S; ``ragged=True`` varies the
    per-scenario group count (1–2 per domain) without changing the
    padded grid bucket."""
    scens = []
    for i in range(b):
        sc = (api.Scenario.on(arch, **options).using("CLX-2S")
              .placed("DCOPY", 1 + i % 8, "CLX/s0/d0")
              .placed("DDOT2", 1 + (i * 3) % 8, "CLX/s1/d0"))
        if not ragged or i % 2:
            sc = sc.placed("DAXPY", 1 + i % 4, "CLX/s0/d0")
        scens.append(sc)
    return api.ScenarioBatch.of(scens)


@pytest.mark.skipif(not backend.HAVE_JAX, reason="jax not importable")
def test_jit_cache_shared_across_placement_axis_buckets():
    # Two placed batches of different raggedness flatten to (B·D, K)
    # rows that pad into one bucket — the second run must reuse the
    # first's compiled solver through the substrate cache.
    p1 = api.compile(_placed_sweep(70, ragged=True))
    assert isinstance(p1, api.PlacedBatchPlan)
    p1.run(backend="jax")
    # B·D = 70·2 = 140 -> 256-row bucket; K = 2 groups per domain max.
    assert p1.bucket == (256, 2)
    s1 = backend.cache_stats()
    p2 = api.compile(_placed_sweep(100))
    assert p2.bucket == p1.bucket
    p2.run(backend="jax")
    s2 = backend.cache_stats()
    assert s2["misses"] == s1["misses"]
    assert s2["hits"] == s1["hits"] + 1
    # And an *unplaced* batch of the same flattened bucket (256 rows,
    # 2 groups, same n_max bucket of 16) shares the very same compiled
    # solver: placement adds no cache axis.
    base = api.Scenario.on("CLX").run("DCOPY", 1).run("DDOT2", 1)
    na = 1 + np.arange(150) % 8
    unplaced = api.compile(base.batch(np.stack(
        [na, np.full_like(na, 8)], axis=-1)))
    assert unplaced.bucket == (256, 2)
    s3 = backend.cache_stats()
    unplaced.run(backend="jax")
    s4 = backend.cache_stats()
    assert s4["misses"] == s3["misses"]


def test_placed_batch_plan_bit_for_bit():
    batch = _placed_sweep(9, ragged=True)
    plan = api.compile(batch)
    assert plan.kind == "placed-batch"
    res = plan.run(backend="numpy")
    for i, sc in enumerate(batch.scenarios):
        assert res[i] == api.predict(sc, backend="numpy")
    # run() == predict(batch), and re-running is deterministic.
    again = api.predict(batch, backend="numpy")
    for i in range(len(batch)):
        assert again[i] == res[i]


def test_placed_batch_plan_swaps():
    batch = _placed_sweep(6)
    plan = api.compile(batch)
    ref = plan.run(backend="numpy")
    got = plan.run(f=0.4, backend="numpy")
    assert all(g.f == 0.4 for g in got[0].groups)
    assert got[0] != ref[0]
    # Swapping the placement re-packs on the same topology without
    # re-tracing: moving every group to one domain matches a fresh
    # compile of so-placed scenarios.
    from repro.core.topology import Placed
    moved = [[Placed(p.group, "CLX/s1/d0") for p in row]
             for row in batch.placements]
    got2 = plan.run(placement=moved, backend="numpy")
    fresh = api.ScenarioBatch.of([
        api.Scenario.on("CLX").using("CLX-2S").options(strict=False)
        .placed("DCOPY", sc.runs[0].n, "CLX/s1/d0")
        .placed("DDOT2", sc.runs[1].n, "CLX/s1/d0")
        .placed("DAXPY", sc.runs[2].n, "CLX/s1/d0")
        for sc in batch.scenarios])
    # strict differs between plan (True) and fresh batch; capacity
    # holds here, so numbers must agree exactly.
    ref2 = api.predict(fresh, backend="numpy")
    for i in range(len(batch)):
        assert got2[i].bw_group == ref2[i].bw_group
    with pytest.raises(ValueError, match="scenarios for the plan's"):
        plan.run(placement=moved[:2])


def test_placed_swap_keeps_the_plans_lanes():
    # A swap of fewer groups per domain packs onto the plan's K lanes
    # (neutral trailing lanes), so it runs the solver the plan compiled.
    batch = _placed_sweep(6)
    plan = api.compile(batch)
    K = plan.grid.n.shape[2]
    from repro.core.topology import Placed
    single = [[Placed(row[0].group, row[0].domain)]
              for row in batch.placements]
    got = plan.run(placement=single, backend="numpy")
    assert got.grid.bw_group.shape[2] == K > 1
    for i, row in enumerate(single):
        ref = api.predict(api.Scenario.on("CLX").using("CLX-2S").placed(
            "DCOPY", row[0].group.n, row[0].domain), backend="numpy")
        assert got[i].bw_group == ref.bw_group


def test_fused_ensemble_seed_stability():
    # Pinned member results: the fused batch×ensemble path must keep
    # every (scenario, member) row bit-identical to the explicit
    # cross-product the known-issues doc used to prescribe (one
    # single-scenario ensemble simulate per batch row).
    scens = [(api.Scenario.on("CLX").ranks(3)
              .step("DCOPY", 1e6 * (i + 1), tag="w")
              .barrier()
              .with_noise(2e-5, seed=11 + i, ensemble=3))
             for i in range(3)]
    fused = api.simulate(api.ScenarioBatch.of(scens))
    assert fused.n_scenarios == 9
    for i, sc in enumerate(scens):
        solo = api.simulate(sc)          # explicit cross-product row
        rows = fused.rows_for(i)
        assert len(rows) == 3
        for m, b in enumerate(rows):
            assert solo.records(m) == fused.records(b)
            assert solo.t_end[m] == fused.t_end[b]


def test_chunked_solve_bit_for_bit(monkeypatch):
    rng = np.random.default_rng(5)
    n = rng.integers(0, 12, size=(23, 3)).astype(float)
    f = rng.uniform(0.05, 1.0, size=(23, 3))
    bs = rng.uniform(50, 200, size=(23, 3))
    for bk in BACKENDS:
        ref = sharing.solve_batch(n, f, bs, backend=bk)
        got = sharing.solve_batch(n, f, bs, backend=bk, chunk=7)
        np.testing.assert_array_equal(got.bw_group, ref.bw_group)
        np.testing.assert_array_equal(got.b_overlap, ref.b_overlap)
        np.testing.assert_array_equal(got.util, ref.util)
    monkeypatch.setenv(backend.CHUNK_ENV, "5")
    got = sharing.solve_batch(n, f, bs, backend="numpy")
    ref2 = sharing.solve_batch(n, f, bs, backend="numpy", chunk=1000)
    np.testing.assert_array_equal(got.bw_group, ref2.bw_group)


def test_chunked_plan_run_bit_for_bit():
    plan = api.compile(_sweep_batch(40))
    ref = plan.run(backend="numpy")
    got = plan.run(backend="numpy", chunk=16)
    np.testing.assert_array_equal(got.bw_group, ref.bw_group)
    # Scenario-level chunk option compiles into the plan.
    chunky = api.compile(_sweep_batch(40, chunk=8))
    np.testing.assert_array_equal(chunky.run(backend="numpy").bw_group,
                                  ref.bw_group)


def test_bucket_and_pad_rows():
    assert [backend.bucket(x) for x in (1, 2, 3, 64, 65, 200)] == \
        [1, 2, 4, 64, 128, 256]
    a = np.arange(6, dtype=float).reshape(3, 2)
    padded = backend.pad_rows(a, 8)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(padded[:3], a)
    assert padded[3:].sum() == 0.0
    assert backend.pad_rows(a, 3) is a
    with pytest.raises(ValueError, match="cannot pad"):
        backend.pad_rows(a, 2)


def test_exactly_one_resolution_implementation():
    """No HAVE_JAX dispatch forks outside core/backend.py: the probe is
    defined exactly once, and every `backend == "auto"` decision routes
    through repro.core.backend.resolve."""
    import pathlib
    src = pathlib.Path(sharing.__file__).resolve().parent.parent
    offenders = []
    for path in src.rglob("*.py"):
        text = path.read_text()
        if path.name == "backend.py":
            continue
        if "HAVE_JAX = " in text:
            offenders.append(f"{path.name}: defines HAVE_JAX")
        if 'backend = "jax" if' in text or "'jax' if HAVE_JAX" in text \
                or '"jax" if HAVE_JAX' in text:
            offenders.append(f"{path.name}: private auto-dispatch fork")
    assert not offenders, offenders
