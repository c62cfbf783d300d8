"""The plain references agree with the system's own numpy engines at small
sizes, on the CPU; their float32 runs do not."""

import numpy as np
import pytest

from bench.reference import desync as ref_desync
from bench.reference import sharing as ref_sharing


def _arrays(rng, rows, groups):
    n = rng.integers(0, 6, (rows, groups)).astype(float)
    f = rng.uniform(0.05, 1.0, (rows, groups))
    bs = rng.uniform(30.0, 110.0, (rows, groups))
    return n, f, bs


@pytest.mark.parametrize("groups", [1, 3, 4])
def test_sharing_matches_numpy_engine(groups):
    from repro.core import sharing
    n, f, bs = _arrays(np.random.default_rng(groups), 512, groups)
    want = sharing._solve_arrays_np(n, f, bs, utilization="recursion",
                                    p0_factor=0.5, saturated=None)[3]
    got = ref_sharing.solve(n, f, bs, p0_factor=0.5)
    assert ref_sharing.rel_gap(got, want) < 1e-14
    low = ref_sharing.solve(n, f, bs, p0_factor=0.5, dtype=np.float32)
    assert ref_sharing.rel_gap(low, want) > 1e-9


def test_sharing_empty_rows_are_zero():
    got = ref_sharing.solve(np.zeros((2, 3)), np.ones((2, 3)),
                            np.ones((2, 3)))
    assert np.array_equal(got, np.zeros((2, 3)))


def test_rel_gap_shape_mismatch_is_infinite():
    assert ref_sharing.rel_gap(np.zeros((1, 2)), np.zeros((1, 3))) == \
        float("inf")


def _hpcg(ranks, iterations, seed, members):
    from repro import api
    sc = (api.Scenario.on("ROME").using("ROME-2S-NPS4").ranks(ranks)
          .on_domains([f"ROME/s{r * 2 // ranks}/d{(r * 8 // ranks) % 4}"
                       for r in range(ranks)])
          .with_noise(6e-5, seed=seed, ensemble=members))
    for _ in range(iterations):
        sc = (sc.step("Schoenauer", 40e6, tag="symgs")
              .step("DDOT2", 8e6, tag="ddot2").barrier()
              .step("DAXPY", 30e6, tag="daxpy"))
    return sc


def test_member_noise_matches_with_noise():
    from repro.api.plan import _noise_items
    sc = _hpcg(8, 1, 2**31 + 17, 3)
    for m in range(3):
        want = [it.duration_s for it in _noise_items(sc, m, 8)]
        assert ref_desync.member_noise(2**31 + 17, m, 8, 6e-5) == want


def test_desync_matches_numpy_engine():
    from repro import api
    R, iters, seed = 16, 2, 5
    res = api.simulate(_hpcg(R, iters, seed, 2), t_max=60.0,
                       backend="numpy").raw
    names = ["DAXPY", "DDOT2", "Schoenauer"]
    f = {"Schoenauer": 0.859, "DDOT2": 0.79, "DAXPY": 0.82}
    bs = {"Schoenauer": 31.7, "DDOT2": 35.8, "DAXPY": 32.6}
    steps = [(ref_desync.WORK, 40e6, 2), (ref_desync.WORK, 8e6, 1),
             (ref_desync.ALLREDUCE, 5e-6, 0), (ref_desync.WORK, 30e6, 0)]
    domain = np.array([r * 8 // R for r in range(R)])
    for m in range(2):
        lead = ref_desync.member_noise(seed, m, R, 6e-5)
        kind = np.array([[ref_desync.IDLE] + [k for k, _, _ in steps] * iters
                         for _ in range(R)])
        qty = np.array([[lead[r]] + [q for _, q, _ in steps] * iters
                        for r in range(R)])
        kern = np.array([[0] + [k for _, _, k in steps] * iters
                         for _ in range(R)])
        args = (kind, qty, kern, domain, [f[k] for k in names],
                [bs[k] for k in names])
        s, e = ref_desync.simulate(*args, t_max=60.0)
        assert ref_desync.member_gap(res.start[m], res.end[m], s, e) < 1e-13
        s32, e32 = ref_desync.simulate(*args, t_max=60.0, dtype=np.float32)
        assert ref_desync.member_gap(s32, e32, s, e) > 1e-9


def test_member_gap_missing_record_is_infinite():
    s = np.array([[0.0, 1.0]])
    e = np.array([[1.0, 2.0]])
    e_missing = np.array([[1.0, np.nan]])
    assert ref_desync.member_gap(s, e_missing, s, e) == float("inf")
