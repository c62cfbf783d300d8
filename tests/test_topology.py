"""Contention-domain topology engine (core/topology.py).

Covers the PR acceptance scenario: two groups pinned to different domains
are solved independently — each domain's prediction equals what that
group would attain alone on its own domain.
"""

import pytest

from repro.core import sharing, table2, topology
from repro.core.sharing import Group
from repro.core.topology import (ContentionDomain, Placed, Topology,
                                 TopologyNode, multi_socket, predict_placed,
                                 predict_single_domain, preset,
                                 single_domain, spread_counts, tpu_pod)


def _clx_groups():
    dcopy = table2.kernel("DCOPY")
    ddot2 = table2.kernel("DDOT2")
    return Group.of(dcopy, "CLX", 10), Group.of(ddot2, "CLX", 10)


# ---------------------------------------------------------------------------
# Tree structure
# ---------------------------------------------------------------------------


def test_presets_exist_and_leaf_counts():
    assert preset("CLX").domains[0].n_cores == 20
    assert len(preset("CLX-2S").domains) == 2
    assert len(preset("ROME-2S-NPS4").domains) == 8
    assert preset("ROME-2S-NPS4").total_cores == 64
    assert len(preset("TPUv5e-pod4").domains) == 4
    with pytest.raises(KeyError, match="unknown topology preset"):
        preset("KNL")


def test_domain_lookup():
    topo = multi_socket(topology.BDW1, 2)
    assert topo.domain("BDW-1/s1/d0").machine is topology.BDW1
    assert "BDW-1/s0/d0" in topo
    assert "BDW-1/s9/d0" not in topo
    with pytest.raises(KeyError, match="no contention domain"):
        topo.domain("nope")


def test_nested_tree_flattens_depth_first():
    inner = TopologyNode("pkg", (ContentionDomain("a", 4),
                                 ContentionDomain("b", 4)))
    root = TopologyNode("node", (inner, ContentionDomain("c", 8)))
    topo = Topology(root)
    assert topo.domain_names == ("a", "b", "c")
    assert topo.total_cores == 16


# ---------------------------------------------------------------------------
# Placement solves
# ---------------------------------------------------------------------------


def test_two_domains_predict_independently():
    """PR acceptance: groups pinned to different domains each see an
    uncontended domain — identical to running each alone."""
    g1, g2 = _clx_groups()
    topo = preset("CLX-2S")
    pred = predict_placed(topo, [Placed(g1, "CLX/s0/d0"),
                                 Placed(g2, "CLX/s1/d0")])
    solo1 = sharing.predict([g1])
    solo2 = sharing.predict([g2])
    assert pred.bw_group[0] == pytest.approx(solo1.bw_group[0], rel=1e-12)
    assert pred.bw_group[1] == pytest.approx(solo2.bw_group[0], rel=1e-12)
    assert pred.domain_bw("CLX/s0/d0") == pytest.approx(
        solo1.total_bw, rel=1e-12)
    assert pred.total_bw == pytest.approx(
        solo1.total_bw + solo2.total_bw, rel=1e-12)


def test_same_domain_reproduces_single_domain_model():
    """Both groups on one leaf == the paper's single-domain prediction."""
    g1, g2 = _clx_groups()
    topo = preset("CLX-2S")
    pred = predict_placed(topo, [Placed(g1, "CLX/s0/d0"),
                                 Placed(g2, "CLX/s0/d0")], strict=False)
    ref = sharing.predict([g1, g2])
    assert pred.bw_group == pytest.approx(ref.bw_group, rel=1e-12)
    assert pred.by_domain["CLX/s0/d0"].b_overlap == pytest.approx(
        ref.b_overlap, rel=1e-12)
    # The second socket is idle.
    assert pred.domain_bw("CLX/s1/d0") == 0.0


def test_cross_domain_no_interference():
    """Adding load on domain B never changes domain A's shares."""
    g1, g2 = _clx_groups()
    hog = Group(n=20, f=0.9, bs=50.0, name="hog")
    topo = preset("CLX-2S")
    alone = predict_placed(topo, [Placed(g1, "CLX/s0/d0"),
                                  Placed(g2, "CLX/s0/d0")], strict=False)
    loaded = predict_placed(topo, [Placed(g1, "CLX/s0/d0"),
                                   Placed(g2, "CLX/s0/d0"),
                                   Placed(hog, "CLX/s1/d0")], strict=False)
    assert loaded.bw_group[:2] == pytest.approx(alone.bw_group, rel=1e-12)


def test_input_order_preserved_across_domains():
    """bw_group follows placement order even when domains interleave."""
    gs = [Group(n=2, f=0.3, bs=100.0, name=f"g{i}") for i in range(4)]
    topo = multi_socket(topology.BDW1, 2)
    doms = ["BDW-1/s0/d0", "BDW-1/s1/d0", "BDW-1/s0/d0", "BDW-1/s1/d0"]
    pred = predict_placed(topo, [Placed(g, d) for g, d in zip(gs, doms)])
    for i, (g, d) in enumerate(zip(gs, doms)):
        dom_pred = pred.by_domain[d]
        assert any(pred.bw_group[i] == pytest.approx(b)
                   for b in dom_pred.bw_group)
        assert pred.bw_per_core[i] == pytest.approx(
            pred.bw_group[i] / g.n)


def test_strict_capacity_and_unknown_domain():
    topo = single_domain(topology.CLX)
    big = Group(n=25, f=0.2, bs=100.0)
    with pytest.raises(ValueError, match="overcommitted"):
        predict_placed(topo, [Placed(big, "CLX/d0")])
    # strict=False allows oversubscription (SMT-style experiments).
    pred = predict_placed(topo, [Placed(big, "CLX/d0")], strict=False)
    assert pred.total_bw > 0
    with pytest.raises(KeyError, match="unknown domain"):
        predict_placed(topo, [Placed(big, "CLX/d7")])


def test_check_placement_groups_by_domain_in_placement_order():
    topo = preset("ROME-2S-NPS4")
    names = topo.domain_names
    g1, g2, g3 = (Group(n=n, f=0.2, bs=100.0) for n in (3, 4, 1))
    placed = [Placed(g1, names[5]), Placed(g2, names[0]),
              Placed(g3, names[5])]
    assert topology.check_placement(topo, placed) == {
        5: [(0, g1), (2, g3)], 0: [(1, g2)]}
    assert topology.check_placement(topo, []) == {}
    over = placed + [Placed(g2, names[5]), Placed(g3, names[5])]  # 9 > 8
    with pytest.raises(ValueError, match=f"{names[5]}.*overcommitted: 9"):
        topology.check_placement(topo, over)
    assert len(topology.check_placement(topo, over, strict=False)[5]) == 4
    with pytest.raises(KeyError, match="placement 1 names unknown domain"):
        topology.check_placement(topo, [placed[0], Placed(g1, "ROME/s9")],
                                 strict=False)


def test_empty_placement_and_idle_domains():
    topo = preset("ROME-2S-NPS4")
    pred = predict_placed(topo, [])
    assert pred.total_bw == 0.0
    assert all(pred.by_domain[d].bw_group == () for d in topo.domain_names)


def test_single_domain_wrapper_equivalence():
    """predict_single_domain is a faithful wrapper of sharing.predict."""
    g1, g2 = _clx_groups()
    for kwargs in ({}, {"utilization": "queue"}, {"saturated": True}):
        ref = sharing.predict([g1, g2], **kwargs)
        wrap = predict_single_domain([g1, g2], **kwargs)
        assert wrap.bw_group == pytest.approx(ref.bw_group, rel=1e-12)
        assert wrap.alphas == pytest.approx(ref.alphas, rel=1e-12)
        assert wrap.b_overlap == pytest.approx(ref.b_overlap, rel=1e-12)


def test_solver_kwargs_forwarded():
    g1, g2 = _clx_groups()
    topo = single_domain(topology.CLX)
    placements = [Placed(g1, "CLX/d0"), Placed(g2, "CLX/d0")]
    sat = predict_placed(topo, placements, saturated=True)
    ref = sharing.predict([g1, g2], saturated=True)
    assert sat.bw_group == pytest.approx(ref.bw_group, rel=1e-12)


def test_spread_counts():
    assert spread_counts(10, 4) == (3, 3, 2, 2)
    assert spread_counts(8, 2) == (4, 4)
    assert sum(spread_counts(37, 8)) == 37


def test_tpu_pod_domains():
    topo = tpu_pod(n_chips=2, streams_per_chip=4)
    assert topo.domain_names == ("TPUv5e/chip0", "TPUv5e/chip1")
    d = topo.domain("TPUv5e/chip0")
    assert d.n_cores == 4
    assert d.saturated_bw_gbs == pytest.approx(819.0)


# ---------------------------------------------------------------------------
# Topology-aware consumers
# ---------------------------------------------------------------------------


def test_desync_two_domain_ranks_do_not_contend():
    """Two ranks running the same kernel finish in the same time whether
    they are alone on separate domains, and slower when sharing one."""
    from repro.core.desync import DesyncSimulator, Work

    prog = [Work("DCOPY", 64e6)]
    topo = preset("CLX-2S")
    sep = DesyncSimulator([list(prog), list(prog)], "CLX",
                          topology=topo,
                          placement=["CLX/s0/d0", "CLX/s1/d0"])
    recs_sep = sep.run()
    shared = DesyncSimulator([list(prog), list(prog)], "CLX",
                             topology=topo,
                             placement=["CLX/s0/d0", "CLX/s0/d0"])
    recs_shared = shared.run()
    t_sep = max(r.end for r in recs_sep)
    t_shared = max(r.end for r in recs_shared)
    # Separated ranks run at solo speed; sharing a domain costs bandwidth
    # only past the saturation knee — at 1+1 threads it merely must not be
    # faster.
    solo = DesyncSimulator([list(prog)], "CLX").run()
    assert t_sep == pytest.approx(max(r.end for r in solo), rel=1e-9)
    assert t_shared >= t_sep - 1e-12


def test_desync_placement_validation():
    from repro.core.desync import DesyncSimulator, Work

    topo = preset("CLX-2S")
    with pytest.raises(ValueError, match="together"):
        DesyncSimulator([[Work("DCOPY", 1e6)]], "CLX", topology=topo)
    with pytest.raises(ValueError, match="placement names"):
        DesyncSimulator([[Work("DCOPY", 1e6)]], "CLX", topology=topo,
                        placement=["CLX/s0/d0", "CLX/s1/d0"])
    with pytest.raises(KeyError):
        DesyncSimulator([[Work("DCOPY", 1e6)]], "CLX", topology=topo,
                        placement=["CLX/s9/d9"])


def test_pod_overlap_plan_straggler_chip():
    from repro.core.hlo import RooflineTerms
    from repro.runtime.overlap_schedule import plan_pod_overlap

    terms = RooflineTerms(name="step", t_compute=1e-3, t_memory=8e-4,
                          t_collective=5e-4, flops=2e11, hbm_bytes=6e8,
                          wire_bytes=2e8)
    plan = plan_pod_overlap(terms, chip_load=(1.0, 1.0, 1.3, 1.0))
    assert len(plan.by_chip) == 4
    assert plan.straggler_chip == "TPUv5e/chip2"
    assert plan.t_step == pytest.approx(
        plan.by_chip["TPUv5e/chip2"].t_planned)
    # Uniform load: all chips plan identically.
    uniform = plan_pod_overlap(terms)
    plans = list(uniform.by_chip.values())
    assert all(p.t_planned == pytest.approx(plans[0].t_planned)
               for p in plans)
