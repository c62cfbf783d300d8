"""Batched desync engine vs. the scalar reference engine.

Acceptance gate of the batched-engine PR: with B = 1 the numpy batch path
must reproduce the scalar engine's record list *exactly* (same order, same
floats); multi-scenario batches must match per-scenario scalar runs to
solver tolerance; and randomly generated barrier-complete programs must
satisfy the engine invariants on both paths.
"""

import random

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.desync import (Allreduce, DesyncSimulator, Idle,
                               WaitNeighbors, Work, durations_by_tag,
                               skewness)
from repro.core.desync_batch import run_batch
from repro.core.sharing import HAVE_JAX
from repro.core.table2 import TABLE2
from repro.core.topology import preset
from repro.runtime.straggler import StepPhase, StragglerMonitor

MB = 1e6


def _programs(tail, seed, n=12):
    rng = random.Random(seed)
    return [[Idle(rng.expovariate(1 / 6e-5), tag="noise"),
             Work("Schoenauer", 20 * MB, tag="symgs"),
             Work("DDOT2", 4 * MB, tag="ddot2"),
             *tail]
            for _ in range(n)]


TAILS = {
    "allreduce": [Allreduce(), Work("DAXPY", 15 * MB, tag="daxpy")],
    "p2p": [WaitNeighbors(), Work("Schoenauer", 20 * MB, tag="spmv")],
    "daxpy": [Work("DAXPY", 15 * MB, tag="daxpy")],
}


# ---------------------------------------------------------------------------
# B = 1 exact equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tail", sorted(TAILS), ids=sorted(TAILS))
def test_b1_reproduces_scalar_records_exactly(tail):
    """Record-for-record, bitwise: same ranks, indices, tags, floats, and
    emission order as the scalar engine."""
    progs = _programs(TAILS[tail], seed=2)
    scalar = DesyncSimulator(progs, "CLX").run(t_max=60)
    batch = run_batch([progs], "CLX", t_max=60)
    assert batch.records[0] == scalar


def test_b1_exact_on_multi_domain_topology():
    topo = preset("CLX-2S")
    place = [topo.domain_names[i % 2] for i in range(8)]
    progs = _programs(TAILS["allreduce"], seed=5, n=8)
    scalar = DesyncSimulator(progs, "CLX", topology=topo,
                             placement=place).run(t_max=60)
    batch = run_batch([progs], "CLX", topology=topo, placement=place,
                      t_max=60)
    assert batch.records[0] == scalar


def test_b1_truncated_run_matches_scalar():
    """t_max cuts both engines at the same point."""
    progs = _programs(TAILS["daxpy"], seed=0)
    t_max = 5e-4
    scalar = DesyncSimulator(progs, "CLX").run(t_max=t_max)
    batch = run_batch([progs], "CLX", t_max=t_max)
    assert batch.records[0] == scalar


# ---------------------------------------------------------------------------
# Multi-scenario batches
# ---------------------------------------------------------------------------


def test_batch_matches_per_scenario_scalar_runs():
    """Every scenario of a heterogeneous batch matches its own scalar run
    (tolerance-level: only padding widths differ numerically)."""
    batch_progs = [_programs(TAILS[k], seed=s)
                   for s, k in enumerate(("allreduce", "daxpy", "p2p",
                                          "allreduce"))]
    res = run_batch(batch_progs, "CLX", t_max=60)
    for b, progs in enumerate(batch_progs):
        scalar = DesyncSimulator(progs, "CLX").run(t_max=60)
        got = res.records[b]
        assert [(r.rank, r.index, r.tag) for r in got] == \
            [(r.rank, r.index, r.tag) for r in scalar]
        np.testing.assert_allclose([r.start for r in got],
                                   [r.start for r in scalar], rtol=1e-9)
        np.testing.assert_allclose([r.end for r in got],
                                   [r.end for r in scalar], rtol=1e-9)


def test_batch_deadlock_masks_by_default():
    """A deadlocked scenario no longer poisons the batch: it is reported
    in the ``failed`` mask with its partial records, and every healthy
    scenario still runs to completion (regression for the former
    whole-batch RuntimeError abort)."""
    deadlocked = [[Allreduce()], [Allreduce(), Allreduce()]]
    healthy = [[Work("DDOT2", MB, tag="d")], [Work("DAXPY", MB, tag="x")]]
    res = run_batch([deadlocked, healthy, deadlocked], "CLX", t_max=1.0)
    assert res.failed.tolist() == [True, False, True]
    assert res.n_failed == 2
    # the healthy scenario matches its own scalar run, record-for-record
    scalar = DesyncSimulator(healthy, "CLX").run(t_max=1.0)
    assert res.records[1] == scalar
    # the deadlocked scenarios froze at the rendezvous: the lone-rank
    # allreduce of scenario 0 retired (rank 1 is parked at its second),
    # but nothing past the deadlock point exists
    assert all(r.index == 0 for r in res.records[0])
    # ensemble statistics cannot silently absorb the partial scenarios:
    # skew is NaN for failed entries, per-scenario aggregation raises
    sk = res.skew_by_tag("d")
    assert np.isnan(sk[0]) and np.isnan(sk[2]) and not np.isnan(sk[1])
    with pytest.raises(ValueError, match="deadlocked"):
        res.durations_by_tag(0, "Allreduce")
    assert res.durations_by_tag(0, "Allreduce", allow_failed=True)
    assert res.durations_by_tag(1, "d")  # healthy scenario unaffected


def test_batch_deadlock_raise_mode():
    with pytest.raises(RuntimeError, match="deadlock"):
        run_batch([[[Allreduce()], [Allreduce(), Allreduce()]]], "CLX",
                  t_max=1.0, on_deadlock="raise")
    with pytest.raises(ValueError, match="on_deadlock"):
        run_batch([[[Work("DDOT2", MB)]]], "CLX", on_deadlock="ignore")


def test_healthy_batch_has_clean_failed_mask():
    progs = _programs(TAILS["allreduce"], seed=1, n=4)
    res = run_batch([progs, progs], "CLX", t_max=60)
    assert res.failed.tolist() == [False, False]
    assert res.n_failed == 0


def test_batch_validation_errors():
    with pytest.raises(ValueError, match="rectangular"):
        run_batch([[[Allreduce()]], [[Allreduce()], [Allreduce()]]], "CLX")
    with pytest.raises(ValueError, match="backend"):
        run_batch([[[Work("DDOT2", MB)]]], "CLX", backend="fortran")
    topo = preset("CLX-2S")
    with pytest.raises(ValueError, match="placement"):
        run_batch([[[Work("DDOT2", MB)]]], "CLX", topology=topo)


# ---------------------------------------------------------------------------
# Property test: random barrier-complete programs
# ---------------------------------------------------------------------------


def _random_programs(rng: random.Random, n_ranks: int):
    """Random small deadlock-free programs.

    Every rank passes the same number of allreduces (each release retires
    one allreduce per rank, so equal counts keep the rendezvous complete).
    Neighbor waits are only generated in barrier-free programs: a waiter
    needs its neighbors to *reach its pc*, and a neighbor parked at an
    allreduce that cannot assemble (because the waiter is not at one) is a
    genuine deadlock the simulator must — and does — report.
    """
    n_barriers = rng.randint(0, 2)
    kernels = ["DDOT2", "DAXPY", "STREAM"]

    def filler():
        items = [Work(rng.choice(kernels), rng.uniform(0.1, 4.0) * MB),
                 Idle(rng.uniform(1e-6, 1e-4))]
        if n_barriers == 0:
            items.append(WaitNeighbors())
        return rng.choice(items)

    progs = []
    for _ in range(n_ranks):
        prog = [filler() for _ in range(rng.randint(0, 3))]
        for _ in range(n_barriers):
            prog.append(Allreduce())
            for _ in range(rng.randint(0, 2)):
                prog.append(Work(rng.choice(kernels),
                                 rng.uniform(0.1, 4.0) * MB))
        progs.append(prog)
    return progs


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=2, max_value=6),
       st.integers(min_value=1, max_value=4))
def test_random_programs_invariants(seed, n_ranks, n_scenarios):
    rng = random.Random(seed)
    batch_progs = [_random_programs(rng, n_ranks)
                   for _ in range(n_scenarios)]
    res = run_batch(batch_progs, "CLX", t_max=120.0)  # no deadlock raised
    for b, progs in enumerate(batch_progs):
        by_rank = {}
        for rec in res.records[b]:
            by_rank.setdefault(rec.rank, []).append(rec)
        for r, prog in enumerate(progs):
            recs = sorted(by_rank.get(r, []), key=lambda x: x.index)
            # barrier-complete + generous t_max => every item retires once
            assert len(recs) == len(prog)
            assert [x.index for x in recs] == list(range(len(prog)))
            for a, c in zip(recs, recs[1:]):
                assert c.start == a.end
                assert c.end >= c.start
            # total bytes conserved: each Work item's record must last at
            # least bytes / b_s — even owning the whole interface, the
            # kernel cannot move its bytes faster than saturation
            for item, rec in zip(prog, recs):
                if isinstance(item, Work) and item.bytes > 0:
                    bs = TABLE2[item.kernel].bs["CLX"] * 1e9
                    assert rec.duration >= item.bytes / bs * (1 - 1e-9)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_random_programs_b1_exactness(seed):
    rng = random.Random(seed)
    progs = _random_programs(rng, 5)
    scalar = DesyncSimulator(progs, "CLX").run(t_max=120.0)
    assert run_batch([progs], "CLX", t_max=120.0).records[0] == scalar


# ---------------------------------------------------------------------------
# jax backend
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")
def test_jax_backend_matches_numpy():
    batch_progs = [_programs(TAILS[k], seed=s, n=6)
                   for s, k in enumerate(("allreduce", "p2p", "daxpy"))]
    rn = run_batch(batch_progs, "CLX", t_max=60, backend="numpy")
    rj = run_batch(batch_progs, "CLX", t_max=60, backend="jax")
    np.testing.assert_allclose(rn.start, rj.start, rtol=1e-9, atol=1e-15)
    np.testing.assert_allclose(rn.end, rj.end, rtol=1e-9, atol=1e-15)
    np.testing.assert_allclose(rn.t_end, rj.t_end, rtol=1e-9)
    for a, b in zip(rn.records, rj.records):
        assert len(a) == len(b)


@pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")
def test_jax_backend_deadlock_masks_and_raises():
    deadlocked = [[Allreduce()], [Allreduce(), Allreduce()]]
    healthy = [[Work("DDOT2", MB, tag="d")], [Work("DAXPY", MB, tag="x")]]
    res = run_batch([deadlocked, healthy], "CLX", t_max=1.0,
                    backend="jax")
    assert res.failed.tolist() == [True, False]
    assert len(res.records[1]) == 2
    with pytest.raises(RuntimeError, match="deadlock"):
        run_batch([deadlocked], "CLX", t_max=1.0, backend="jax",
                  on_deadlock="raise")


# ---------------------------------------------------------------------------
# Consumers: seed-ensemble straggler mode, result helpers
# ---------------------------------------------------------------------------


def _phases(f_followup):
    return [StepPhase("fwd", bytes_hbm=40e6, f=0.19, bs=800.0),
            StepPhase("probe", bytes_hbm=8e6, f=0.15, bs=800.0),
            StepPhase("grad_io", bytes_hbm=30e6, f=f_followup, bs=800.0)]


def test_seed_ensemble_is_deterministic():
    mon = StragglerMonitor(n_workers=16)
    a = mon.predict_amplification(_phases(0.9), probe=1, ensemble=16)
    b = mon.predict_amplification(_phases(0.9), probe=1, ensemble=16)
    assert a == b
    # a different seed gives a different (but same-sign) estimate
    c = mon.predict_amplification(_phases(0.9), probe=1, ensemble=16,
                                  seed=100)
    assert c != a and c > 0


def test_seed_ensemble_sign_agreement():
    """The ensemble estimate keeps the paper's amplification signs."""
    mon = StragglerMonitor(n_workers=16)
    assert mon.predict_amplification(_phases(0.9), probe=1,
                                     ensemble=16) > 0.2
    assert mon.predict_amplification(_phases(0.05), probe=1,
                                     ensemble=16) < -0.2


def test_single_draw_matches_scalar_engine():
    """ensemble=1 goes through the batch engine but must equal a scalar
    simulation of the same program (B=1 exactness, end to end).  Member
    0 of base seed 0 draws from the facade's splittable seed stream
    (api.derive_member_seed), so the scalar reference seeds the same
    way."""
    from repro.api import derive_member_seed
    from repro.core.table2 import KernelSpec
    mon = StragglerMonitor(n_workers=12)
    got = mon.predict_amplification(_phases(0.9), probe=1, ensemble=1)
    phases = _phases(0.9)
    specs = {ph.name: KernelSpec.synthetic(ph.name, ph.f, ph.bs)
             for ph in phases}
    rng = random.Random(derive_member_seed(0, 0))
    progs = []
    for _ in range(12):
        prog = [Idle(rng.expovariate(1 / 5e-5), tag="noise")]
        prog += [Work(ph.name, ph.bytes_hbm, tag=ph.name) for ph in phases]
        progs.append(prog)
    recs = DesyncSimulator(progs, "TPU", specs=specs).run(t_max=120.0)
    want = skewness(durations_by_tag(recs, "probe", n_ranks=12))
    # The batch engine's contention solves go through the jitted jax
    # solver, which keeps a few ULP of compiler latitude against the
    # scalar numpy engine (docs/known-issues.md); the skewness of the
    # durations carries that difference through.
    assert got == pytest.approx(want, rel=16 * np.finfo(np.float64).eps,
                                abs=0.0)


def test_pod_plan_candidates_evaluated_as_one_batch():
    """overlap_schedule evaluates B candidate chip-load plans in a single
    batched run; results match evaluating each candidate alone, and the
    balanced plan wins (a lagging chip delays the gradient allreduce)."""
    from repro.core.hlo import RooflineTerms
    from repro.runtime.overlap_schedule import (best_pod_plan,
                                                evaluate_pod_plans)

    terms = RooflineTerms(name="step", t_compute=1e-3, t_memory=2e-3,
                          t_collective=5e-4, flops=1e12, hbm_bytes=1.5e9,
                          wire_bytes=2e8)
    cands = [(1.0, 1.0, 1.0, 1.0),
             (1.6, 0.8, 0.8, 0.8),
             (1.2, 1.2, 0.8, 0.8)]
    evals = evaluate_pod_plans(terms, cands)
    assert len(evals) == 3
    solo = [evaluate_pod_plans(terms, [c])[0] for c in cands]
    for a, b in zip(evals, solo):
        assert a.t_step == b.t_step  # batching is layout, not semantics
    idx, best = best_pod_plan(terms, cands)
    assert idx == 0 and best.balanced
    assert evals[1].t_step > evals[0].t_step
    assert evals[1].bwd_spread > evals[0].bwd_spread
    with pytest.raises(ValueError, match="candidate"):
        evaluate_pod_plans(terms, [(1.0, 1.0)])


def test_result_helpers():
    progs = _programs(TAILS["daxpy"], seed=3, n=8)
    res = run_batch([progs, progs], "CLX", t_max=60)
    assert res.n_scenarios == 2
    assert res.n_ranks == 8
    assert res.n_events == sum(len(r) for r in res.records)
    sk = res.skew_by_tag("ddot2")
    assert sk.shape == (2,)
    assert sk[0] == sk[1]  # identical scenarios
    d = res.durations_by_tag(0, "ddot2")
    assert len(d) == 8 and all(x > 0 for x in d)
