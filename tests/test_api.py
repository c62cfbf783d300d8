"""The facade: Scenario builder, registry resolution chain, dispatch,
and the unified result schema.

Equivalence against the underlying engines is covered separately in
test_api_equivalence.py; this file covers the facade's own semantics —
build-time validation, provenance, error suggestions, engine selection,
and the dict/ndjson export surface.
"""

import io
import json

import numpy as np
import pytest

from repro import api
from repro.core import backend, machine, sharing, table2, topology
from repro.core.sharing import HAVE_JAX


# ---------------------------------------------------------------------------
# Scenario builder
# ---------------------------------------------------------------------------


def test_builder_is_immutable_and_composable():
    base = api.Scenario.on("CLX").run("DCOPY", 12)
    extended = base.run("DDOT2", 8)
    assert len(base.runs) == 1
    assert len(extended.runs) == 2
    # The shared prefix is untouched: templates are safe to reuse.
    assert api.predict(base.run("DAXPY", 4)).groups[1].name == "DAXPY"
    assert api.predict(extended).groups[1].name == "DDOT2"


def test_run_rejects_bad_counts_and_mixing():
    sc = api.Scenario.on("CLX")
    with pytest.raises(ValueError, match="non-negative int"):
        sc.run("DCOPY", -1)
    with pytest.raises(ValueError, match="non-negative int"):
        sc.run("DCOPY", 2.5)
    prog = api.Scenario.on("CLX").ranks(4).step("DCOPY", 1e6)
    with pytest.raises(ValueError, match="cannot mix"):
        prog.run("DDOT2", 4)
    with pytest.raises(ValueError, match="cannot mix"):
        sc.run("DCOPY", 4).ranks(4)


def test_unknown_kernel_suggests_nearest():
    with pytest.raises(KeyError, match="did you mean 'DCOPY'"):
        api.Scenario.on("CLX").run("DCPY", 4)
    with pytest.raises(KeyError, match="known kernels"):
        api.Scenario.on("CLX").run("nope", 4)


def test_unknown_arch_suggests_nearest():
    with pytest.raises(KeyError, match="did you mean 'CLX'"):
        api.Scenario.on("CLV").run("DCOPY", 4)
    # The same contract on the pre-facade entry points (satellite):
    with pytest.raises(KeyError, match="did you mean 'ROME'"):
        sharing.Group.of(table2.kernel("DCOPY"), "ROMA", 2)
    with pytest.raises(KeyError, match="did you mean 'DDOT2'"):
        table2.kernel("DDOT_2")
    with pytest.raises(KeyError, match="did you mean 'CLX-2S'"):
        topology.preset("CLX-2")


def test_options_whitelist():
    sc = api.Scenario.on("CLX").options(utilization="queue", t_max=5.0)
    assert sc.utilization == "queue"
    assert sc.t_max == 5.0
    with pytest.raises(TypeError, match="unknown scenario options"):
        sc.options(utlization="queue")


def test_program_steps_require_ranks():
    with pytest.raises(ValueError, match=r"\.ranks\(R\)"):
        api.Scenario.on("CLX").step("DCOPY", 1e6)
    with pytest.raises(ValueError, match=r"\.ranks\(R\)"):
        api.Scenario.on("CLX").barrier()


def test_per_rank_bytes_must_match_rank_count():
    sc = api.Scenario.on("CLX").ranks(4)
    with pytest.raises(ValueError, match="4 ranks"):
        sc.step("DCOPY", [1e6, 2e6])


def test_placed_requires_topology_and_full_placement():
    sc = api.Scenario.on("CLX").placed("DCOPY", 4, "CLX/d0")
    with pytest.raises(ValueError, match="no topology"):
        api.predict(sc)
    half = (api.Scenario.on("CLX").using("CLX")
            .placed("DCOPY", 4, "CLX/d0").run("DDOT2", 4))
    with pytest.raises(ValueError, match="place every group"):
        api.predict(half)


def test_using_accepts_preset_names():
    sc = (api.Scenario.on("CLX").using("CLX-2S")
          .placed("DCOPY", 4, "CLX/s0/d0"))
    assert api.predict(sc).engine == "topology"
    with pytest.raises(KeyError, match="topology preset"):
        api.Scenario.on("CLX").using("CLX-3S")


# ---------------------------------------------------------------------------
# Registry resolution chain
# ---------------------------------------------------------------------------


def test_resolve_table2_name():
    r = api.resolve("DCOPY", arch="CLX")
    assert r.provenance == "table2"
    assert r.spec is table2.TABLE2["DCOPY"]


def test_resolve_custom_specs_mapping():
    specs = {"phase": table2.KernelSpec.synthetic("phase", 0.5, 800.0)}
    r = api.resolve("phase", specs=specs)
    assert r.provenance == "custom"
    with pytest.raises(KeyError, match="known kernels: \\['phase'\\]"):
        api.resolve("phse", specs=specs)


def test_resolve_explicit_and_synthetic_specs():
    assert api.resolve(table2.kernel("DAXPY")).provenance == "explicit"
    syn = table2.KernelSpec.synthetic("mine", 0.4, 100.0)
    assert api.resolve(syn).provenance == "synthetic"


def test_resolve_f_bs_pair():
    r = api.resolve((0.5, 819.0), name="bwd")
    assert r.provenance == "synthetic"
    assert r.spec.f == {"TPU": 0.5}
    assert r.spec.bs == {"TPU": 819.0}


def test_resolve_calibration_mapping():
    r = api.resolve({"f": {"CLX": 0.2}, "bs": {"CLX": 100.0}},
                    name="cal", arch="CLX")
    assert r.provenance == "calibrated"
    assert r.spec.f["CLX"] == 0.2

    class FakeCalibratedValue:
        def __init__(self, value):
            self.value = value

    r2 = api.resolve({"f": FakeCalibratedValue(0.3),
                      "bs": FakeCalibratedValue(90.0)},
                     arch="ROME", name="cal2")
    assert r2.provenance == "calibrated"
    assert r2.spec.f == {"ROME": 0.3}
    # Scalar values without an arch cannot be keyed.
    with pytest.raises(ValueError, match="pass arch="):
        api.resolve({"f": 0.3, "bs": 90.0}, name="cal3")


def test_resolve_rejects_garbage():
    with pytest.raises(TypeError, match="cannot resolve"):
        api.resolve(42)


def test_from_loop_features_is_ecm_route():
    r = api.from_loop_features("mycopy", reads=1, writes=1, rfo=1,
                               flops_per_iter=0, machine=machine.CLX)
    assert r.provenance == "ecm"
    assert set(r.spec.f) == {"CLX"}
    assert 0 < r.spec.f["CLX"] <= 1
    # Matches the direct ECM prediction for the same stream mix.
    from repro.core import ecm
    direct = ecm.predict(table2.kernel("DCOPY"), machine.CLX)
    assert r.spec.f["CLX"] == pytest.approx(direct.f)


def test_from_loop_features_accepts_machine_names():
    by_name = api.from_loop_features("mycopy", reads=1, writes=1, rfo=1,
                                     flops_per_iter=0, machine="CLX")
    by_model = api.from_loop_features("mycopy", reads=1, writes=1, rfo=1,
                                      flops_per_iter=0,
                                      machine=machine.CLX)
    assert by_name.spec.f == by_model.spec.f
    assert by_name.spec.bs == by_model.spec.bs


def test_from_loop_features_unknown_machine_suggests():
    with pytest.raises(KeyError, match=r"did you mean 'CLX'"):
        api.from_loop_features("k", reads=1, writes=1, rfo=0,
                               flops_per_iter=1, machine="CLX2")
    with pytest.raises(TypeError, match="MachineModel"):
        api.from_loop_features("k", reads=1, writes=1, rfo=0,
                               flops_per_iter=1, machine=42)


def test_from_loop_features_unknown_bandwidth_class_suggests():
    with pytest.raises(KeyError, match=r"did you mean 'read_only'"):
        api.from_loop_features("k", reads=1, writes=0, rfo=0,
                               flops_per_iter=1, machine="CLX",
                               bandwidth_class="readonly")


def test_from_loop_features_bandwidth_class_override():
    forced = api.from_loop_features("k", reads=2, writes=1, rfo=1,
                                    flops_per_iter=1, machine="CLX",
                                    bandwidth_class="read_only")
    assert forced.spec.bs["CLX"] == \
        machine.CLX.saturated_bw_gbs["read_only"]


def test_from_static_analysis_unknown_machine_suggests():
    import functools

    import jax.numpy as jnp

    from repro.kernels.stream import map_stream
    fn = functools.partial(map_stream, "dcopy", interpret=True)
    args = (jnp.float32(1.0), jnp.ones(1024, jnp.float32))
    with pytest.raises(KeyError, match=r"did you mean 'ROME'"):
        api.from_static_analysis(fn, args, machine="ROME2")


def test_prelabelled_resolved_spec_passthrough():
    labelled = api.ResolvedSpec(spec=table2.kernel("DCOPY"),
                                provenance="calibrated")
    p = api.predict(api.Scenario.on("CLX").run(labelled, 4))
    assert p.groups[0].provenance == "calibrated"


# ---------------------------------------------------------------------------
# Engine dispatch
# ---------------------------------------------------------------------------


def test_single_scenario_uses_scalar_engine():
    p = api.predict(api.Scenario.on("CLX").run("DCOPY", 4))
    assert p.engine == "scalar"


def test_small_batch_uses_numpy():
    b = api.ScenarioBatch.split_sweep("CLX", "DCOPY", "DDOT2", 8)
    assert api.predict(b).engine == "numpy"


@pytest.mark.skipif(not HAVE_JAX, reason="jax not importable")
def test_large_batch_uses_jax():
    base = api.Scenario.on("CLX").run("DCOPY", 1).run("DDOT2", 1)
    na = 1 + np.arange(backend.DEFAULT_JAX_CUTOFF) % 19
    b = base.batch(np.stack([na, 20 - na], axis=-1))
    assert api.predict(b).engine == "jax"
    assert api.predict(b, backend="numpy").engine == "numpy"


def test_predict_rejects_program_scenarios():
    prog = api.Scenario.on("CLX").ranks(2).step("DCOPY", 1e6)
    with pytest.raises(ValueError, match="simulate"):
        api.predict(prog)


def test_simulate_rejects_nothing_to_run():
    with pytest.raises(ValueError, match="nothing to simulate"):
        api.simulate(api.Scenario.on("CLX"))


def test_batched_predict_rejects_placed_scenarios():
    placed = (api.Scenario.on("CLX").using("CLX")
              .placed("DCOPY", 4, "CLX/d0"))
    plain = api.Scenario.on("CLX").run("DCOPY", 4)
    with pytest.raises(ValueError, match="placed"):
        api.predict(api.ScenarioBatch.of([plain, placed]))


def test_batch_requires_uniform_options():
    a = api.Scenario.on("CLX").run("DCOPY", 4)
    b = api.Scenario.on("CLX").options(utilization="queue").run("DCOPY", 4)
    with pytest.raises(ValueError, match="solver options"):
        api.ScenarioBatch.of([a, b])


def test_ragged_batch_pads_with_neutral_groups():
    scens = [api.Scenario.on("CLX").run("DCOPY", 4),
             api.Scenario.on("CLX").run("DCOPY", 4).run("DDOT2", 4)
             .run("DAXPY", 2)]
    batch = api.predict(api.ScenarioBatch.of(scens), backend="numpy")
    n, f, bs, names = api.ScenarioBatch.of(scens).arrays
    assert n.shape == (2, 3)
    assert n[0].tolist() == [4, 0, 0]
    # Row 0 must equal the unpadded scalar solve.
    ref = api.predict(scens[0])
    assert batch[0].bw_group == ref.bw_group
    assert len(batch[0].groups) == 1
    assert len(batch[1].groups) == 3


def test_mixed_arch_batch_labels_rows_correctly():
    scens = [api.Scenario.on("CLX").run("DCOPY", 4),
             api.Scenario.on("ROME").run("DCOPY", 4)]
    batch = api.predict(api.ScenarioBatch.of(scens), backend="numpy")
    assert batch.archs == ("CLX", "ROME")
    assert batch.arch == "mixed"
    assert batch[0].arch == "CLX"
    assert batch[1].arch == "ROME"
    # Each row solved with its own arch's (f, bs).
    assert batch[1].bw_group == api.predict(scens[1]).bw_group
    assert [d["arch"] for d in batch.to_dicts()] == ["CLX", "ROME"]


def test_batch_rows_keep_genuine_zero_thread_groups():
    sc = api.Scenario.on("CLX").run("DCOPY", 0).run("DDOT2", 4)
    ref = api.predict(sc)
    assert len(ref.groups) == 2
    row = api.predict(api.ScenarioBatch.of(
        [sc, api.Scenario.on("CLX").run("DAXPY", 2)]), backend="numpy")[0]
    # The n = 0 group survives (distinguished from padding by its
    # provenance), and the row equals the scalar result exactly.
    assert len(row.groups) == 2
    assert row.bw_group == ref.bw_group
    assert row.groups[0].n == 0


def test_simulation_batch_requires_uniform_t_max_and_topology():
    a = api.Scenario.on("CLX").ranks(2).step("DCOPY", 1e6)
    b = a.options(t_max=1.0)
    with pytest.raises(ValueError, match="t_max"):
        api.simulate(api.ScenarioBatch.of([a, b]))
    # An explicit t_max overrides every scenario, so mixing is fine then.
    res = api.simulate(api.ScenarioBatch.of([a, b]), t_max=5.0)
    assert res.n_scenarios == 2


def test_scenario_batch_counts_shape_checked():
    base = api.Scenario.on("CLX").run("DCOPY", 1).run("DDOT2", 1)
    with pytest.raises(ValueError, match=r"\(B, 2\)"):
        base.batch(np.ones((4, 3)))


# ---------------------------------------------------------------------------
# Simulation facade
# ---------------------------------------------------------------------------


def test_group_mode_simulation_places_runs_on_domains():
    topo = topology.preset("CLX-2S")
    sc = (api.Scenario.on("CLX").using(topo)
          .run("DCOPY", 2, domain="CLX/s0/d0", bytes=1e6)
          .run("DDOT2", 2, domain="CLX/s1/d0", bytes=1e6))
    res = api.simulate(sc)
    assert res.n_ranks == 4
    # Separate domains: neither kernel contends with the other, so each
    # pair finishes as if alone (same finish for both ranks of a group).
    recs = res.records()
    ends = {}
    for r in recs:
        ends.setdefault(r.tag, set()).add(round(r.end, 12))
    assert len(ends["DCOPY"]) == 1
    assert len(ends["DDOT2"]) == 1


def test_noise_ensemble_expands_to_batch():
    sc = (api.Scenario.on("CLX").ranks(3)
          .step("DCOPY", 1e6)
          .with_noise(1e-5, seed=3, ensemble=5))
    res = api.simulate(sc)
    assert res.n_scenarios == 5
    assert res.engine == "desync-numpy"
    # Different seeds -> different noise draws -> different makespans.
    assert len({round(float(t), 15) for t in res.t_end}) > 1


def test_simulation_batch_fuses_inner_ensembles():
    # Batch × ensemble composition: each scenario's E members become
    # adjacent rows of one fused run, mapped by result.members.
    sc_a = (api.Scenario.on("CLX").ranks(2).step("DCOPY", 1e6)
            .with_noise(1e-5, seed=1, ensemble=2))
    sc_b = (api.Scenario.on("CLX").ranks(2).step("DCOPY", 2e6)
            .with_noise(1e-5, seed=2, ensemble=3))
    res = api.simulate(api.ScenarioBatch.of([sc_a, sc_b]))
    assert res.n_scenarios == 5
    assert res.members == ((0, 0), (0, 1), (1, 0), (1, 1), (1, 2))
    assert res.rows_for(0) == (0, 1)
    assert res.rows_for(1) == (2, 3, 4)
    # ensemble=1 batches map rows 1:1 to scenarios: no member map.
    one = api.simulate(api.ScenarioBatch.of(
        [sc_a.with_noise(1e-5, seed=1), sc_b.with_noise(1e-5, seed=2)]))
    assert one.n_scenarios == 2
    assert one.members is None


def test_simulation_result_analysis_helpers():
    sc = (api.Scenario.on("CLX").ranks(4)
          .with_noise(6e-5, seed=0, ensemble=2)
          .step("Schoenauer", 4e6, tag="symgs")
          .step("DDOT2", 1e6, tag="ddot2")
          .barrier())
    res = api.simulate(sc, t_max=60)
    assert res.skew("ddot2").shape == (2,)
    assert len(res.durations("ddot2", 1)) == 4
    assert res.end_spread("ddot2", 0) >= 0.0
    assert res.makespan(0) > 0.0
    d = res.to_dict(tags=["ddot2"])
    json.dumps(d)  # fully json-serializable
    assert d["n_scenarios"] == 2
    assert len(d["skew"]["ddot2"]) == 2


# ---------------------------------------------------------------------------
# Result schema + export
# ---------------------------------------------------------------------------


def test_prediction_schema_carries_provenance_and_domains():
    p = api.predict(api.Scenario.on("CLX").run("DCOPY", 12)
                    .run((0.5, 100.0), 8, name="mine"))
    assert [g.provenance for g in p.groups] == ["table2", "synthetic"]
    assert len(p.domains) == 1
    assert p.total_bw == pytest.approx(sum(p.bw_group))


def test_topology_prediction_domain_breakdown():
    sc = (api.Scenario.on("CLX").using("CLX-2S")
          .placed("DCOPY", 10, "CLX/s0/d0")
          .placed("DDOT2", 10, "CLX/s1/d0"))
    p = api.predict(sc)
    assert {d.domain for d in p.domains} == {"CLX/s0/d0", "CLX/s1/d0"}
    assert p.domain_bw("CLX/s0/d0") == pytest.approx(p.bw_group[0])
    with pytest.raises(KeyError, match="did you mean"):
        p.domain_bw("CLX/s0/d1")


def test_prediction_dict_round_trip():
    p = api.predict(api.Scenario.on("CLX").run("DCOPY", 12)
                    .run("DDOT2", 8))
    d = p.to_dict()
    json.dumps(d)
    assert api.Prediction.from_dict(d) == p


def test_ndjson_round_trip_flattens_batches():
    single = api.predict(api.Scenario.on("CLX").run("DAXPY", 4))
    batch = api.predict(
        api.ScenarioBatch.split_sweep("CLX", "DCOPY", "DDOT2", 6),
        backend="numpy")
    buf = io.StringIO()
    n = api.dump_ndjson([single, batch], buf)
    assert n == 1 + len(batch)
    buf.seek(0)
    loaded = api.load_ndjson(buf)
    assert loaded[0] == single
    for i in range(len(batch)):
        assert loaded[1 + i] == batch[i]


def test_load_ndjson_rejects_other_kinds():
    buf = io.StringIO(json.dumps({"kind": "simulation"}) + "\n")
    with pytest.raises(ValueError, match="not a prediction"):
        api.load_ndjson(buf)
