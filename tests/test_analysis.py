"""Static traffic auditor: golden per-iteration counts vs Table II.

The walker's whole claim is that Table II falls out of the kernels'
own jaxprs.  These tests pin that: exact byte/stream/flop golden values
for the STREAM and Jacobi kernels, the full-suite count cross-check,
the in-place aliasing (RFO-suppression) path, control-flow recursion,
the no-pallas fallback, and the registry's ``"static"`` rung.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.analysis import audit, derive, features
from repro.analysis.report import cross_check, static_suite
from repro.core.table2 import TABLE2, KernelSpec
from repro.kernels.stream import LANES, map_stream, reduce_stream, row_block

jax.config.update("jax_enable_x64", False)

N = LANES * 64


def _map(name, n_arrays, n=N, **kw):
    s = jnp.float32(3.0)
    arrays = tuple(jnp.ones(n, jnp.float32) for _ in range(n_arrays))
    return (functools.partial(map_stream, name, interpret=True, **kw),
            (s, *arrays))


def _jacobi_v1():
    from repro.kernels.jacobi import jacobi_v1
    return functools.partial(jacobi_v1, interpret=True)


# ---------------------------------------------------------------------------
# Golden per-iteration byte counts (S3): STREAM copy/triad and Jacobi
# ---------------------------------------------------------------------------


def test_golden_dcopy():
    fn, args = _map("dcopy", 1)
    lf = features(fn, *args)
    assert (lf.reads, lf.writes, lf.rfo) == (1, 1, 1)
    assert lf.flops_per_iter == 0.0
    assert lf.iters == N
    assert lf.itemsize == 4
    assert lf.bytes_per_iter == 12.0          # load + store + RFO, f32
    assert lf.code_balance == float("inf")    # no flops at all


def test_golden_stream_triad():
    fn, args = _map("stream", 2)
    lf = features(fn, *args)
    assert (lf.reads, lf.writes, lf.rfo) == (2, 1, 1)
    assert lf.flops_per_iter == pytest.approx(2.0)
    assert lf.bytes_per_iter == 16.0          # 4 f32 streams
    assert lf.code_balance == pytest.approx(8.0)


def test_golden_jacobi_v1_layer_condition():
    jacobi_v1 = _jacobi_v1()
    a = jnp.ones((66, 128), jnp.float32)
    lc = features(jacobi_v1, a, jnp.float32(0.25), reuse=True)
    assert (lc.reads, lc.writes, lc.rfo) == (1, 1, 1)   # JacobiL2-v1
    assert lc.bytes_per_iter == 12.0
    assert lc.flops_per_iter == pytest.approx(4.0)
    no_lc = features(jacobi_v1, a, jnp.float32(0.25), reuse=False)
    assert (no_lc.reads, no_lc.writes, no_lc.rfo) == (3, 1, 1)  # L3-v1
    assert no_lc.bytes_per_iter == 20.0


def test_jacobi_views_share_one_base():
    jacobi_v1 = _jacobi_v1()
    a = jnp.ones((66, 128), jnp.float32)
    tr = audit(jacobi_v1, a, jnp.float32(0.25))
    bases = {s.base for s in tr.loads}
    assert bases == {"a"}           # up/mid/down recognized as one buffer
    assert len(tr.loads) == 3


# ---------------------------------------------------------------------------
# Full-suite count cross-check against Table II
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", static_suite(), ids=lambda c: c.label)
def test_suite_counts_match_table2(case):
    fn, args = case.build()
    lf = features(fn, *args, reuse=case.reuse)
    ref = TABLE2[case.table_name]
    if case.exact:
        assert (lf.reads, lf.writes, lf.rfo) == \
            (ref.reads, ref.writes, ref.rfo)
        assert lf.flops_per_iter == pytest.approx(ref.flops_per_iter,
                                                  abs=0.01)
    else:
        # functional DSCAL/DAXPY: one extra RFO vs the table's in-place
        # form — the documented write-allocate ambiguity.
        assert (lf.reads, lf.writes) == (ref.reads, ref.writes)
        assert lf.rfo == ref.rfo + 1


def test_cross_check_f_within_bounds():
    for row in cross_check("CLX"):
        assert row["ok"], row
        assert row["f_err"] <= row["bound"]


# ---------------------------------------------------------------------------
# In-place aliasing: input_output_aliases suppresses the RFO stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,n_arrays", [("dscal", 1), ("daxpy", 2)])
def test_in_place_suppresses_rfo(name, n_arrays):
    fn, args = _map(name, n_arrays, in_place=True)
    lf = features(fn, *args)
    ref = TABLE2[name.upper()]
    assert (lf.reads, lf.writes, lf.rfo) == \
        (ref.reads, ref.writes, ref.rfo)
    assert lf.rfo == 0
    tr = audit(fn, *args)
    assert any(s.aliased for s in tr.stores)


def test_in_place_numerics_unchanged():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal(N), jnp.float32)
    b = jnp.asarray(rng.standard_normal(N), jnp.float32)
    s = jnp.float32(1.7)
    np.testing.assert_allclose(
        map_stream("daxpy", s, a, b, in_place=True, interpret=True),
        map_stream("daxpy", s, a, b, interpret=True), rtol=1e-6)


def test_in_place_rejects_distinct_output_kernels():
    s = jnp.float32(1.0)
    a = jnp.ones(N, jnp.float32)
    with pytest.raises(ValueError, match="dscal"):
        map_stream("dcopy", s, a, in_place=True, interpret=True)


# ---------------------------------------------------------------------------
# Walker mechanics: grid fetches, control flow, fallback
# ---------------------------------------------------------------------------


def test_multi_step_grid_counts_all_fetches():
    rows = 2 * row_block(1 << 20, LANES * 4, 2, 4)   # grid (2,)
    fn, args = _map("dcopy", 1, n=LANES * rows)
    tr = audit(fn, *args)
    (load,) = tr.loads
    assert load.fetches == 2
    assert load.elements == LANES * rows
    lf = derive(tr)
    assert lf.iters == LANES * rows
    assert (lf.reads, lf.writes, lf.rfo) == (1, 1, 1)


def test_scan_multiplies_traffic():
    s = jnp.float32(0.5)
    a = jnp.ones(N, jnp.float32)

    def once(s, a):
        return map_stream("dscal", s, a, interpret=True)

    def repeated(s, a):
        def body(carry, _):
            return map_stream("dscal", s, carry, interpret=True), None
        out, _ = jax.lax.scan(body, a, None, length=3)
        return out

    single, tripled = audit(once, s, a), audit(repeated, s, a)
    assert tripled.flops == pytest.approx(3 * single.flops)
    assert tripled.total_bytes == pytest.approx(3 * single.total_bytes)


def test_fallback_pure_jnp_boundary_traffic():
    def dot(a, b):
        return jnp.sum(a * b)

    a = jnp.ones(N, jnp.float32)
    lf = features(dot, a, a + 1)
    assert (lf.reads, lf.writes, lf.rfo) == (2, 0, 0)
    assert lf.read_only
    assert lf.flops_per_iter == pytest.approx(2.0)


def test_reduction_accumulator_not_a_store_stream():
    fn, args = _map("dcopy", 1)  # placeholder to keep args style
    rfn = functools.partial(reduce_stream, "ddot2", interpret=True)
    arrays = (jnp.ones(N, jnp.float32), jnp.ones(N, jnp.float32))
    tr = audit(rfn, *arrays)
    assert not tr.stores            # (1,1) accumulator is grid-resident
    assert tr.reductions >= 1
    lf = derive(tr)
    assert (lf.reads, lf.writes, lf.rfo) == (2, 0, 0)
    assert any("accumulator" in n for n in lf.notes)


def test_audit_labels_from_signature():
    fn, args = _map("stream", 2)
    tr = audit(fn, *args)
    assert {s.base for s in tr.loads} == {"arrays[0]", "arrays[1]"}


# ---------------------------------------------------------------------------
# The "static" resolution rung
# ---------------------------------------------------------------------------


def test_from_static_analysis_provenance_and_archs():
    fn, args = _map("dcopy", 1)
    r = api.from_static_analysis(fn, args)
    assert r.provenance == "static"
    assert "static" in api.PROVENANCES
    assert set(r.spec.f) == {"BDW-1", "BDW-2", "CLX", "ROME"}
    assert set(r.spec.bs) == set(r.spec.f)
    single = api.from_static_analysis(fn, args, machine="CLX")
    assert set(single.spec.f) == {"CLX"}
    assert single.spec.f["CLX"] == pytest.approx(r.spec.f["CLX"])


def test_kernelspec_classmethod_matches_registry():
    fn, args = _map("stream", 2)
    spec = KernelSpec.from_static_analysis(fn, args, machine="ROME")
    via_api = api.from_static_analysis(fn, args, machine="ROME").spec
    assert spec.f == via_api.f
    assert spec.bs == via_api.bs


def test_static_provenance_travels_into_prediction():
    fn, args = _map("stream", 2)
    resolved = api.from_static_analysis(fn, args, machine="CLX")
    pred = api.predict(api.Scenario.on("CLX").run(resolved, 12))
    assert pred.total_bw > 0
    assert pred.groups[0].provenance == "static"
