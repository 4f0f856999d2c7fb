"""TPU solver parity vs the CPU oracle (cost within 1.02x on BASELINE shapes)."""

import numpy as np
import pytest

from karpenter_tpu.models import labels as L
from karpenter_tpu.models.catalog import generate_catalog
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.solver import reference
from karpenter_tpu.solver.tpu import solve_tensors
from karpenter_tpu.solver.types import SimNode

PARITY = 1.02


def default_prov(**kw):
    return Provisioner(name=kw.pop("name", "default"), **kw).with_defaults()


def assert_parity(pods, provs, catalog, **tensorize_kw):
    oracle = reference.solve(pods, provs, catalog,
                             unavailable=tensorize_kw.get("unavailable"),
                             daemonsets=tensorize_kw.get("daemonsets", ()))
    st = tensorize(pods, provs, catalog, **tensorize_kw)
    out = solve_tensors(st)
    tpu = out.result
    assert len(tpu.infeasible) == len(oracle.infeasible), (
        f"infeasible mismatch: tpu={len(tpu.infeasible)} oracle={len(oracle.infeasible)}"
    )
    if oracle.new_node_cost > 0:
        ratio = tpu.new_node_cost / oracle.new_node_cost
        assert ratio <= PARITY + 1e-9, (
            f"cost parity violated: tpu=${tpu.new_node_cost:.3f} "
            f"oracle=${oracle.new_node_cost:.3f} ratio={ratio:.4f}\n"
            f"tpu: {tpu.summary()}\noracle: {oracle.summary()}"
        )
    assert tpu.n_scheduled == oracle.n_scheduled
    return oracle, tpu


class TestParityBasics:
    def test_single_group(self, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(50)]
        assert_parity(pods, [default_prov()], small_catalog)

    def test_two_resource_groups(self, small_catalog):
        pods = [PodSpec(name=f"a{i}", requests={"cpu": 1.0}, owner_key="a") for i in range(30)]
        pods += [PodSpec(name=f"b{i}", requests={"cpu": 0.5, "memory": 6 * GIB}, owner_key="b")
                 for i in range(30)]
        assert_parity(pods, [default_prov()], small_catalog)

    def test_backfill_small_into_big(self, small_catalog):
        pods = [PodSpec(name=f"big{i}", requests={"cpu": 14.0}) for i in range(2)]
        pods += [PodSpec(name=f"s{i}", requests={"cpu": 0.25}) for i in range(20)]
        assert_parity(pods, [default_prov()], small_catalog)

    def test_infeasible_pod_counted(self, small_catalog):
        pods = [PodSpec(name="giant", requests={"cpu": 1000.0}),
                PodSpec(name="ok", requests={"cpu": 1.0})]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert "giant" in tpu.infeasible

    def test_full_catalog(self, full_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 2.0, "memory": 4 * GIB})
                for i in range(100)]
        assert_parity(pods, [default_prov()], full_catalog)


class TestParityConstraints:
    def test_zone_selector(self, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0},
                        node_selector={L.ZONE: "zone-1b"}) for i in range(10)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert all(n.zone == "zone-1b" for n in tpu.nodes)

    def test_zone_spread(self, small_catalog):
        sel = LabelSelector.of({"app": "web"})
        pods = [PodSpec(name=f"w{i}", labels={"app": "web"}, requests={"cpu": 1.0},
                        topology_spread=[TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)])
                for i in range(30)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        zones = {}
        for n in tpu.nodes:
            zones[n.zone] = zones.get(n.zone, 0) + len(n.pods)
        counts = sorted(zones.values())
        assert max(counts) - min(counts) <= 1

    def test_hostname_anti_affinity(self, small_catalog):
        sel = LabelSelector.of({"app": "db"})
        pods = [PodSpec(name=f"db{i}", labels={"app": "db"}, requests={"cpu": 0.5},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)])
                for i in range(5)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert len(tpu.nodes) == 5
        for n in tpu.nodes:
            assert len(n.pods) == 1

    def test_taints_and_tolerations(self, small_catalog):
        tainted = Provisioner(
            name="team-a", taints=[Taint("team", L.EFFECT_NO_SCHEDULE, "a")]
        ).with_defaults()
        open_prov = default_prov(name="open")
        pods = [PodSpec(name=f"t{i}", requests={"cpu": 1.0},
                        tolerations=[Toleration(key="team", operator="Equal", value="a")])
                for i in range(5)]
        pods += [PodSpec(name=f"u{i}", requests={"cpu": 1.0}) for i in range(5)]
        assert_parity(pods, [tainted, open_prov], small_catalog)

    def test_spot_and_weights(self, small_catalog):
        spot = Provisioner(
            name="spot", weight=10,
            requirements=[Requirement(L.CAPACITY_TYPE, IN, [L.CAPACITY_TYPE_SPOT])],
        ).with_defaults()
        od = default_prov(name="od", weight=1)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(20)]
        oracle, tpu = assert_parity(pods, [spot, od], small_catalog)
        assert all(n.capacity_type == L.CAPACITY_TYPE_SPOT for n in tpu.nodes)

    def test_unavailable_offerings(self, small_catalog):
        base = reference.solve(
            [PodSpec(name="probe", requests={"cpu": 1.0})], [default_prov()], small_catalog
        )
        ice = {(base.nodes[0].instance_type, z, "on-demand")
               for z in ("zone-1a", "zone-1b", "zone-1c")}
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(10)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog, unavailable=ice)
        assert all((n.instance_type, n.zone, n.capacity_type) not in ice for n in tpu.nodes)

    def test_daemonset_overhead(self, small_catalog):
        ds = [PodSpec(name="agent", requests={"cpu": 0.5, "memory": 0.5 * GIB})]
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.5}) for i in range(10)]
        assert_parity(pods, [default_prov()], small_catalog, daemonsets=ds)

    def test_provisioner_limits(self, small_catalog):
        prov = Provisioner(name="capped", limits={"cpu": 8.0}).with_defaults()
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 3.0}) for i in range(10)]
        oracle = reference.solve(pods, [prov], small_catalog)
        st = tensorize(pods, [prov], small_catalog)
        tpu = solve_tensors(st).result
        total_cap = sum(
            next(t for t in small_catalog if t.name == n.instance_type).capacity["cpu"]
            for n in tpu.nodes
        )
        assert total_cap <= 8.0
        assert len(tpu.infeasible) > 0

    def test_limit_fallback_to_next_provisioner(self, small_catalog):
        """When the preferred provisioner's limit binds mid-group, the
        remainder must fall back to the next provisioner, not go infeasible."""
        capped = Provisioner(name="capped", weight=10, limits={"cpu": 8.0}).with_defaults()
        fallback = Provisioner(name="fallback", weight=5).with_defaults()
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 3.0}) for i in range(10)]
        oracle = reference.solve(pods, [capped, fallback], small_catalog)
        st = tensorize(pods, [capped, fallback], small_catalog)
        tpu = solve_tensors(st).result
        assert len(oracle.infeasible) == 0
        assert len(tpu.infeasible) == 0
        assert tpu.n_scheduled == 10
        # capped provisioner must not exceed its limit
        capped_cap = sum(
            next(t for t in small_catalog if t.name == n.instance_type).capacity["cpu"]
            for n in tpu.nodes if n.provisioner == "capped"
        )
        assert capped_cap <= 8.0
        assert tpu.new_node_cost / oracle.new_node_cost <= PARITY + 1e-9


class TestPositiveAffinity:
    """Positive pod-affinity on-device (solver/tpu.py modes A/B/C) vs oracle."""

    def test_zone_self_affinity_seeds_one_zone(self, small_catalog):
        sel = LabelSelector.of({"app": "web"})
        pods = [PodSpec(name=f"w{i}", labels={"app": "web"},
                        requests={"cpu": 1.0},
                        affinity_terms=[PodAffinityTerm(sel, L.ZONE)],
                        owner_key="web") for i in range(20)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        zones = {n.zone for n in tpu.nodes}
        assert len(zones) == 1  # the whole group seeded a single zone

    def test_zone_affinity_follows_other_service(self, small_catalog):
        sel_a = LabelSelector.of({"app": "a"})
        # service a is FFD-larger so it places first; b must join a's zone
        pods = [PodSpec(name=f"a{i}", labels={"app": "a"},
                        requests={"cpu": 4.0}, owner_key="a",
                        node_selector={L.ZONE: "zone-1b"}) for i in range(4)]
        pods += [PodSpec(name=f"b{i}", labels={"app": "b"},
                         requests={"cpu": 0.5}, owner_key="b",
                         affinity_terms=[PodAffinityTerm(sel_a, L.ZONE)])
                 for i in range(8)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        node_zone = {n.name: n.zone for n in tpu.nodes}
        for i in range(8):
            assert node_zone[tpu.assignments[f"b{i}"]] == "zone-1b"

    def test_unsupported_topology_keys_reject_with_reason(self, small_catalog):
        """Required constraints on topology keys outside the supported set
        must REJECT (infeasible + reason), never silently drop — a dropped
        anti-affinity term co-locates the replicas it exists to separate.
        Supported: zone/hostname/capacity-type for spread
        (scheduling.md:339-343), zone/hostname for (anti-)affinity."""
        from karpenter_tpu.solver.scheduler import BatchScheduler

        sel = LabelSelector.of({"app": "w"})
        prov = Provisioner(name="default").with_defaults()
        for bad in (
            dict(topology_spread=[TopologySpreadConstraint(
                1, "topology.example.com/rack", "DoNotSchedule", sel)]),
            dict(affinity_terms=[PodAffinityTerm(
                sel, "topology.example.com/rack", anti=True)]),
            dict(affinity_terms=[PodAffinityTerm(sel, L.CAPACITY_TYPE)]),
        ):
            pods = [PodSpec(name=f"w{i}", labels={"app": "w"},
                            requests={"cpu": 0.5}, owner_key="w", **bad)
                    for i in range(3)]
            res = BatchScheduler(backend="tpu").solve(pods, [prov], small_catalog)
            assert len(res.infeasible) == 3, bad
            assert all("unsupported topology key" in r
                       for r in res.infeasible.values()), res.infeasible

    def test_capacity_type_spread_balances_spot_od(self, small_catalog):
        """karpenter.sh/capacity-type is the reference's third supported
        spread topologyKey (scheduling.md:303-346): replicas spread across
        spot/on-demand to bound the interruption blast radius.  The device
        path serves these via the oracle carve-out (device_inexpressible),
        so the product boundary must land the exact balanced split."""
        from karpenter_tpu.solver.scheduler import BatchScheduler

        sel = LabelSelector.of({"app": "web"})
        prov = Provisioner(name="default", requirements=[
            Requirement(L.CAPACITY_TYPE, IN,
                        [L.CAPACITY_TYPE_SPOT, L.CAPACITY_TYPE_ON_DEMAND]),
        ]).with_defaults()
        pods = [PodSpec(name=f"w{i}", labels={"app": "web"},
                        requests={"cpu": 1.0},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, "DoNotSchedule", sel)],
                        owner_key="web") for i in range(10)]
        oracle = reference.solve(pods, [prov], small_catalog)
        got = BatchScheduler(backend="tpu").solve(pods, [prov], small_catalog)
        for res in (oracle, got):
            assert not res.infeasible
            by_ct = {}
            for n in res.nodes:
                by_ct[n.capacity_type] = by_ct.get(n.capacity_type, 0) + len(n.pods)
            assert set(by_ct) == {L.CAPACITY_TYPE_SPOT,
                                  L.CAPACITY_TYPE_ON_DEMAND}
            assert abs(by_ct[L.CAPACITY_TYPE_SPOT]
                       - by_ct[L.CAPACITY_TYPE_ON_DEMAND]) <= 1
        assert abs(got.new_node_cost - oracle.new_node_cost) < 1e-9

    def test_capacity_type_spread_single_eligible_domain(self, small_catalog):
        """A spot-only provisioner leaves ONE reachable ct domain; skew is
        judged over reachable domains (not a global {spot, od} constant), so
        every pod still places — on spot."""
        from karpenter_tpu.solver.scheduler import BatchScheduler

        sel = LabelSelector.of({"app": "w"})
        prov = Provisioner(name="spot-only", requirements=[
            Requirement(L.CAPACITY_TYPE, IN, [L.CAPACITY_TYPE_SPOT]),
        ]).with_defaults()
        pods = [PodSpec(name=f"w{i}", labels={"app": "w"},
                        requests={"cpu": 0.5},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, "DoNotSchedule", sel)],
                        owner_key="w") for i in range(8)]
        got = BatchScheduler(backend="tpu").solve(pods, [prov], small_catalog)
        assert not got.infeasible
        assert {n.capacity_type for n in got.nodes} == {L.CAPACITY_TYPE_SPOT}

    def test_capacity_type_spread_balances_against_existing(self, small_catalog):
        """Existing matching pods count toward the ct domains: a spot node
        already holding 3 web pods forces the next placements toward
        on-demand until the skew band re-levels."""
        from karpenter_tpu.solver.scheduler import BatchScheduler
        from karpenter_tpu.solver.types import SimNode

        sel = LabelSelector.of({"app": "web"})
        it = next(t for t in small_catalog if t.name == "m5.2xlarge")
        existing = SimNode(
            instance_type=it.name, provisioner="default", zone="zone-1a",
            capacity_type=L.CAPACITY_TYPE_SPOT, price=it.offerings[0].price,
            allocatable=dict(it.allocatable),
            labels={**it.labels(), L.ZONE: "zone-1a",
                    L.CAPACITY_TYPE: L.CAPACITY_TYPE_SPOT},
            existing=True,
        )
        for i in range(3):
            existing.pods.append(PodSpec(
                name=f"old{i}", labels={"app": "web"},
                requests={"cpu": 0.5}, owner_key="web"))
        # both cts reachable — otherwise the on-demand default would force
        # the balanced outcome trivially instead of via the skew band
        prov = Provisioner(name="default", requirements=[
            Requirement(L.CAPACITY_TYPE, IN,
                        [L.CAPACITY_TYPE_SPOT, L.CAPACITY_TYPE_ON_DEMAND]),
        ]).with_defaults()
        pods = [PodSpec(name=f"new{i}", labels={"app": "web"},
                        requests={"cpu": 0.5},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, "DoNotSchedule", sel)],
                        owner_key="web") for i in range(3)]
        got = BatchScheduler(backend="tpu").solve(
            pods, [prov], small_catalog, existing_nodes=[existing])
        assert not got.infeasible
        counts = {L.CAPACITY_TYPE_SPOT: 3}  # the existing node's web pods
        for n in list(got.existing_nodes) + list(got.nodes):
            for p in n.pods:
                if p.name.startswith("new"):
                    counts[n.capacity_type] = counts.get(n.capacity_type, 0) + 1
        # 3 existing spot + 3 new: balanced end state is 3/3
        assert counts.get(L.CAPACITY_TYPE_ON_DEMAND, 0) == 3

    def test_zone_affinity_seed_absorbs_into_fleet_zone(self, small_catalog):
        """The zone seed picks the cheapest-ABSORBING zone, not the earliest
        open slot's zone: a hostname-spread fleet pinned to zone-1b leaves
        one-pod-per-node slack there, and a zone-affine group with no pins
        of its own must ride that slack instead of buying dedicated nodes
        in whatever zone happens to hold the first open slot (kubelet fuzz
        seed 20's 1.1151 failure mode, fixed round 5)."""
        web_sel = LabelSelector.of({"app": "web"})
        pods = [PodSpec(name=f"web-{i}", labels={"app": "web"},
                        requests={"cpu": 0.5, "memory": 2 * GIB},
                        node_selector={L.ZONE: "zone-1b"},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.HOSTNAME, "DoNotSchedule", web_sel)],
                        owner_key="web") for i in range(12)]
        pods += [PodSpec(name=f"cache-{i}", labels={"app": "cache"},
                         requests={"cpu": 0.25, "memory": 1 * GIB},
                         affinity_terms=[PodAffinityTerm(
                             LabelSelector.of({"app": "cache"}), L.ZONE)],
                         owner_key="cache") for i in range(10)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert not tpu.infeasible
        # the fleet size is set by the hostname spread; cache rides its slack
        assert len(tpu.nodes) == 12
        cache_zones = {n.zone for n in tpu.nodes
                       for p in n.pods if p.owner_key == "cache"}
        assert cache_zones == {"zone-1b"}
        assert not [n for n in tpu.nodes
                    if n.pods and all(p.owner_key == "cache" for p in n.pods)]

    def test_hostname_self_affinity_one_node(self, small_catalog):
        sel = LabelSelector.of({"app": "pack"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "pack"},
                        requests={"cpu": 0.5},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME)],
                        owner_key="pack") for i in range(6)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        # everything that scheduled is on ONE node (both solvers may strand
        # overflow identically when the $/pod-greedy node pick is small)
        assert len(set(tpu.assignments.values())) <= 1
        assert len(tpu.assignments) >= 1

    def test_hostname_self_affinity_overflow_infeasible(self, small_catalog):
        # more pods than any single node can hold: remainder is infeasible
        sel = LabelSelector.of({"app": "big"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "big"},
                        requests={"cpu": 6.0},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME)],
                        owner_key="big") for i in range(10)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert len(tpu.infeasible) > 0
        assert len({tpu.assignments[p] for p in tpu.assignments}) == 1

    def test_hostname_affinity_to_other_service(self, small_catalog):
        sel_a = LabelSelector.of({"app": "a"})
        pods = [PodSpec(name=f"a{i}", labels={"app": "a"},
                        requests={"cpu": 4.0}, owner_key="a") for i in range(3)]
        pods += [PodSpec(name=f"b{i}", labels={"app": "b"},
                         requests={"cpu": 0.25}, owner_key="b",
                         affinity_terms=[PodAffinityTerm(sel_a, L.HOSTNAME)])
                 for i in range(6)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        a_nodes = {tpu.assignments[f"a{i}"] for i in range(3)}
        for i in range(6):
            assert tpu.assignments[f"b{i}"] in a_nodes

    def test_unmatchable_affinity_infeasible(self, small_catalog):
        sel = LabelSelector.of({"app": "ghost"})
        pods = [PodSpec(name="p", labels={"app": "solo"},
                        requests={"cpu": 0.5},
                        affinity_terms=[PodAffinityTerm(sel, L.ZONE)])]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert "p" in tpu.infeasible

    def test_inexpressible_shape_routes_to_oracle(self, small_catalog):
        from karpenter_tpu.models.tensorize import device_inexpressible
        from karpenter_tpu.solver.scheduler import BatchScheduler

        sel = LabelSelector.of({"app": "x"})
        pod = PodSpec(name="p", labels={"app": "x"}, requests={"cpu": 0.5},
                      affinity_terms=[PodAffinityTerm(sel, L.ZONE),
                                      PodAffinityTerm(sel, L.ZONE)])
        assert device_inexpressible(pod)
        res = BatchScheduler(backend="tpu").solve([pod], [default_prov()], small_catalog)
        assert res.n_scheduled == 1

    def test_host_seed_respects_zone_anti_affinity(self, small_catalog):
        """host_seed_flow must honor the zone anti-affinity cap: a group with
        self hostname-affinity AND self zone-anti-affinity places at most one
        matching pod per zone."""
        sel = LabelSelector.of({"app": "m"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "m"},
                        requests={"cpu": 0.5},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME),
                                        PodAffinityTerm(sel, L.ZONE, anti=True)])
                for i in range(5)]
        oracle = reference.solve(pods, [default_prov()], small_catalog)
        st = tensorize(pods, [default_prov()], small_catalog)
        tpu = solve_tensors(st).result
        assert tpu.n_scheduled == oracle.n_scheduled
        assert len(tpu.assignments) <= 1  # one pod on one node max

    def test_zone_seed_avoids_anti_blocked_zone(self, small_catalog):
        """_z_seed must not lock a seeding group into a zone its own
        anti-affinity forbids."""
        blk_sel = LabelSelector.of({"app": "blk"})
        pods = [PodSpec(name=f"b{i}", labels={"app": "blk"},
                        requests={"cpu": 4.0},
                        node_selector={L.ZONE: "zone-1a"}, owner_key="blk")
                for i in range(2)]
        self_sel = LabelSelector.of({"app": "w"})
        pods += [PodSpec(name=f"w{i}", labels={"app": "w"},
                         requests={"cpu": 0.5}, owner_key="w",
                         affinity_terms=[PodAffinityTerm(self_sel, L.ZONE),
                                         PodAffinityTerm(blk_sel, L.ZONE, anti=True)])
                 for i in range(4)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert len(tpu.infeasible) == 0
        w_zones = {n.zone for n in tpu.nodes
                   if any(p.name.startswith("w") for p in n.pods)}
        assert "zone-1a" not in w_zones

    def test_device_pods_with_affinity_to_carved_out_pods(self, small_catalog):
        """Expressible pods referencing carve-out (oracle-routed) pods must
        solve AFTER them so co-location counts exist."""
        from karpenter_tpu.solver.scheduler import BatchScheduler

        selx = LabelSelector.of({"app": "x"})
        pods = [PodSpec(name=f"x{i}", labels={"app": "x"}, requests={"cpu": 2.0},
                        affinity_terms=[PodAffinityTerm(selx, L.ZONE),
                                        PodAffinityTerm(selx, L.ZONE)],
                        owner_key="x")
                for i in range(3)]
        pods += [PodSpec(name=f"y{i}", labels={"app": "y"}, requests={"cpu": 0.5},
                         affinity_terms=[PodAffinityTerm(selx, L.ZONE)],
                         owner_key="y")
                 for i in range(4)]
        res = BatchScheduler(backend="tpu").solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}, res.infeasible
        zone_of = {n.name: n.zone for n in res.nodes}
        x_zones = {zone_of[res.assignments[f"x{i}"]] for i in range(3)}
        y_zones = {zone_of[res.assignments[f"y{i}"]] for i in range(4)}
        assert y_zones <= x_zones


class TestFeasibilityPaths:
    def test_matmul_equals_gather(self, small_catalog):
        """The MXU matmul label-feasibility path must bit-match the gather
        path (solver/tpu.py routes to matmul when G >= MATMUL_MIN_G)."""
        import jax
        import jax.numpy as jnp

        from karpenter_tpu.ops.feasibility import (
            candidate_selector,
            label_feasibility_matmul,
        )
        from karpenter_tpu.ops.masks import gather_pm_bits

        pods = []
        for i in range(40):
            kw = {}
            if i % 3 == 0:
                kw["node_selector"] = {L.ZONE: f"zone-1{'abc'[i % 3]}"}
            if i % 4 == 0:
                kw["node_selector"] = {L.ARCH: "amd64", "team": f"t{i % 5}"}
            pods.append(PodSpec(name=f"p{i}", requests={"cpu": 0.5 + (i % 4)}, **kw))
        provs = [default_prov(), Provisioner(name="gpu", labels={"team": "t0"}).with_defaults()]
        st = tensorize(pods, provs, small_catalog)

        pm = jnp.asarray(st.pm)
        cvw, cvb = jnp.asarray(st.cand_vw), jnp.asarray(st.cand_vb)
        kc = jnp.asarray(st.key_check)

        def one_group(pm_g):
            bits = gather_pm_bits(pm_g, cvw, cvb)
            return jnp.all(bits | ~kc[None, :], axis=1)

        lab_gather = np.asarray(jax.vmap(one_group)(pm))
        sel = candidate_selector(cvw, cvb, kc, st.pm.shape[2])
        lab_matmul = np.asarray(label_feasibility_matmul(pm, sel, kc))
        np.testing.assert_array_equal(lab_gather, lab_matmul)


class TestNodeBudget:
    def test_max_nodes_respected_despite_bucketing(self, small_catalog):
        """NR is bucketed up for jit-shape stability; the semantic max_nodes
        cap must survive (node_budget in the scan consts)."""
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 2.0}) for i in range(100)]
        st = tensorize(pods, [default_prov()], small_catalog)
        out = solve_tensors(st, max_nodes=2)
        assert len(out.result.nodes) <= 2
        assert len(out.result.infeasible) > 0
        assert out.result.n_scheduled + len(out.result.infeasible) == 100

    def test_budget_truncated_tail_fills_nodes(self, small_catalog):
        """When the node budget truncates a creation block, the written nodes
        must still be filled to per-node capacity (not take the partial
        last_extra meant for the untruncated block's final node)."""
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 3.0}) for i in range(10)]
        oracle = reference.solve(pods, [default_prov()], small_catalog,
                                 max_new_nodes=2)
        st = tensorize(pods, [default_prov()], small_catalog)
        out = solve_tensors(st, max_nodes=2)
        assert len(out.result.nodes) <= 2
        assert out.result.n_scheduled == oracle.n_scheduled, (
            f"tpu scheduled {out.result.n_scheduled} vs oracle "
            f"{oracle.n_scheduled} under the same 2-node budget"
        )

    def test_budget_below_existing_count_is_safe(self, small_catalog):
        """max_nodes < len(existing_nodes) must not walk the slot cursor
        backward (phantom prov_used deductions): no new nodes, existing
        capacity still usable."""
        it = next(t for t in small_catalog if t.name == "m5.4xlarge")
        existing = [
            SimNode(
                instance_type=it.name, provisioner="default", zone="zone-1a",
                capacity_type="on-demand", price=1.0,
                allocatable=dict(it.allocatable),
                labels={**it.labels(), L.ZONE: "zone-1a",
                        L.CAPACITY_TYPE: "on-demand",
                        L.PROVISIONER_NAME: "default"},
                existing=True,
            )
            for _ in range(3)
        ]
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(5)]
        st = tensorize(pods, [default_prov()], small_catalog)
        out = solve_tensors(st, existing_nodes=existing, max_nodes=1)
        assert out.result.nodes == []
        assert out.result.n_scheduled == 5  # existing capacity still served
        assert out.n_used == 3


class TestExistingNodes:
    def _existing(self, catalog, type_name="m5.4xlarge", zone="zone-1a", n=1):
        it = next(t for t in catalog if t.name == type_name)
        return [
            SimNode(
                instance_type=type_name, provisioner="default", zone=zone,
                capacity_type="on-demand",
                price=next(o.price for o in it.offerings
                           if o.zone == zone and o.capacity_type == "on-demand"),
                allocatable=dict(it.allocatable),
                labels={**it.labels(), L.ZONE: zone, L.CAPACITY_TYPE: "on-demand",
                        L.PROVISIONER_NAME: "default"},
                existing=True,
            )
            for _ in range(n)
        ]

    def test_existing_filled_first(self, small_catalog):
        existing = self._existing(small_catalog)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(5)]
        st = tensorize(pods, [default_prov()], small_catalog)
        out = solve_tensors(st, existing_nodes=existing)
        assert out.result.nodes == []  # everything fits on the existing node
        assert out.result.n_scheduled == 5

    def test_overflow_to_new_nodes(self, small_catalog):
        existing = self._existing(small_catalog)  # ~15.8 cpu allocatable
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 2.0}) for i in range(12)]
        st = tensorize(pods, [default_prov()], small_catalog)
        out = solve_tensors(st, existing_nodes=existing)
        oracle = reference.solve(pods, [default_prov()], small_catalog,
                                 existing_nodes=self._existing(small_catalog))
        assert out.result.n_scheduled == 12
        assert abs(out.result.new_node_cost - oracle.new_node_cost) < 1e-6


class TestScaleParity:
    def test_config1_1k_uniform(self, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(1000)]
        oracle, tpu = assert_parity(pods, [default_prov()], small_catalog)
        assert len(tpu.infeasible) == 0

    def test_config5_weighted_spot_od_mix(self, small_catalog):
        provs = []
        for i in range(10):
            ct = L.CAPACITY_TYPE_SPOT if i % 2 else L.CAPACITY_TYPE_ON_DEMAND
            provs.append(Provisioner(
                name=f"prov-{i}", weight=10 - i,
                requirements=[Requirement(L.CAPACITY_TYPE, IN, [ct])],
            ).with_defaults())
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0 + (i % 3) * 0.5, "memory": 2 * GIB},
                        owner_key=f"d{i % 3}") for i in range(300)]
        assert_parity(pods, provs, small_catalog)


class TestPreferenceRelaxation:
    def test_soft_ct_spread_relaxes_when_domain_unfundable(self, small_catalog):
        """ScheduleAnyway capacity-type spread composes with the relaxation
        ladder: hardened first (riding the oracle batch route), and when the
        on-demand domain is reachable but unfundable (a tiny provisioner cpu
        limit) the strand relaxes the soft spread away — everything lands on
        spot, nothing infeasible."""
        from karpenter_tpu.solver.scheduler import BatchScheduler

        sel = LabelSelector.of({"app": "w"})
        provs = [
            Provisioner(name="od", weight=10, limits={"cpu": 2.0},
                        requirements=[Requirement(
                            L.CAPACITY_TYPE, IN,
                            [L.CAPACITY_TYPE_ON_DEMAND])]).with_defaults(),
            Provisioner(name="spot", weight=1,
                        requirements=[Requirement(
                            L.CAPACITY_TYPE, IN,
                            [L.CAPACITY_TYPE_SPOT])]).with_defaults(),
        ]
        pods = [PodSpec(name=f"w{i}", labels={"app": "w"},
                        requests={"cpu": 2.0},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, "ScheduleAnyway", sel)],
                        owner_key="w") for i in range(9)]
        res = BatchScheduler(backend="tpu").solve(pods, provs, small_catalog)
        assert not res.infeasible
        assert res.n_scheduled == 9

    def test_preferred_zone_honored_when_feasible(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        pods = [PodSpec(
            name=f"p{i}", requests={"cpu": 1.0},
            preferred_affinity_terms=[[Requirement(L.ZONE, IN, ["zone-1b"])]],
        ) for i in range(5)]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}
        assert all(n.zone == "zone-1b" for n in res.nodes)

    def test_infeasible_preference_relaxed(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        # preference for a zone that doesn't exist: hardened solve fails,
        # relaxation retries without it and succeeds
        pods = [PodSpec(
            name="p", requests={"cpu": 1.0},
            preferred_affinity_terms=[[Requirement(L.ZONE, IN, ["mars-1a"])]],
        )]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}
        assert res.n_scheduled == 1

    def test_hard_requirement_never_relaxed(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        pods = [PodSpec(
            name="p", requests={"cpu": 1.0},
            node_selector={L.ZONE: "mars-1a"},  # hard: stays infeasible
        )]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert "p" in res.infeasible

    def test_mixed_preferences_relaxed_one_at_a_time(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        # term[0] satisfiable (zone-1b), term[1] not (mars): the ladder must
        # drop only term[1] and still honor term[0], not both.
        pods = [PodSpec(
            name=f"p{i}", requests={"cpu": 1.0},
            preferred_affinity_terms=[
                [Requirement(L.ZONE, IN, ["zone-1b"])],
                [Requirement(L.ZONE, IN, ["mars-1a"])],
            ],
        ) for i in range(3)]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}
        assert all(n.zone == "zone-1b" for n in res.nodes)

    def test_or_affinity_second_term_explored(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        # term[0] names a zone that doesn't exist; term[1] is satisfiable.
        # The OR ladder must schedule the pod under term[1].
        pods = [PodSpec(
            name=f"p{i}", requests={"cpu": 1.0},
            required_affinity_terms=[
                [Requirement(L.ZONE, IN, ["mars-1a"])],
                [Requirement(L.ZONE, IN, ["zone-1b"])],
            ],
        ) for i in range(4)]
        for backend in ("oracle", "tpu"):
            sched = BatchScheduler(backend=backend)
            res = sched.solve(pods, [default_prov()], small_catalog)
            assert res.infeasible == {}, backend
            assert all(n.zone == "zone-1b" for n in res.nodes), backend

    def test_or_term_keeps_preferences(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        # required term[0] infeasible; term[1] admits zone-1a|zone-1b; the
        # preference for zone-1b must still be honored under term[1].
        pods = [PodSpec(
            name="p", requests={"cpu": 1.0},
            required_affinity_terms=[
                [Requirement(L.ZONE, IN, ["mars-1a"])],
                [Requirement(L.ZONE, IN, ["zone-1a", "zone-1b"])],
            ],
            preferred_affinity_terms=[[Requirement(L.ZONE, IN, ["zone-1b"])]],
        )]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}
        assert all(n.zone == "zone-1b" for n in res.nodes)

    def test_or_affinity_all_terms_infeasible(self, small_catalog):
        from karpenter_tpu.solver.scheduler import BatchScheduler

        pods = [PodSpec(
            name="p", requests={"cpu": 1.0},
            required_affinity_terms=[
                [Requirement(L.ZONE, IN, ["mars-1a"])],
                [Requirement(L.ZONE, IN, ["mars-1b"])],
            ],
        )]
        sched = BatchScheduler(backend="oracle")
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert "p" in res.infeasible


class TestCoalescing:
    """Cost-neutral node coalescing (solver/coalesce.py): the scan buys each
    group's tail at that group's step, so cross-group fragments accumulate;
    the post-pass merges them into larger types at <= the same price
    (BASELINE config 5: 196 nodes -> 165, FEWER than FFD's 172, at lower $)."""

    def _c5_shaped(self, n=1000):
        from karpenter_tpu.models.instancetype import GIB
        from karpenter_tpu.models.requirements import IN, Requirement

        provs = [Provisioner(
            name=f"prov-{i}", weight=10 - i,
            requirements=[Requirement(L.CAPACITY_TYPE, IN,
                          [L.CAPACITY_TYPE_SPOT if i % 2
                           else L.CAPACITY_TYPE_ON_DEMAND])],
        ).with_defaults() for i in range(4)]
        pods = [PodSpec(name=f"p{i}",
                        requests={"cpu": 0.5 + (i % 5) * 0.5,
                                  "memory": (1 + i % 4) * GIB},
                        owner_key=f"d{i % 8}") for i in range(n)]
        return pods, provs

    def test_node_count_parity_on_weighted_od_shape(self, small_catalog):
        """The config-5 node-count gate under LINEAR (on-demand) pricing:
        mixed-size pods across weighted provisioners must not buy a multiple
        of FFD's node count at equal-or-lower cost — coalescing merges the
        cross-group tail fragments.  (The spot variant below gates cost
        only: zonal spot discounts are nonlinear in size, so a fleet of
        strictly-cheaper small nodes can be the genuinely better buy there.)"""
        from karpenter_tpu.models.requirements import IN, Requirement

        pods, _ = self._c5_shaped()
        provs = [Provisioner(
            name=f"prov-{i}", weight=4 - i,
            requirements=[Requirement(L.CAPACITY_TYPE, IN,
                          [L.CAPACITY_TYPE_ON_DEMAND])],
        ).with_defaults() for i in range(4)]
        oracle = reference.solve(pods, provs, small_catalog)
        st = tensorize(pods, provs, small_catalog)
        tpu = solve_tensors(st).result
        assert not tpu.infeasible and not oracle.infeasible
        assert tpu.new_node_cost <= oracle.new_node_cost * 1.02 + 1e-9
        assert len(tpu.nodes) <= 1.1 * len(oracle.nodes), (
            f"node count {len(tpu.nodes)} vs FFD {len(oracle.nodes)}"
        )

    def test_cost_parity_on_weighted_spot_shape(self, small_catalog):
        """Spot variant of the config-5 shape: the $ gate holds; node count
        is not gated here because nonlinear zonal spot pricing can make
        more, smaller, strictly-cheaper nodes the correct answer."""
        pods, provs = self._c5_shaped()
        oracle = reference.solve(pods, provs, small_catalog)
        st = tensorize(pods, provs, small_catalog)
        tpu = solve_tensors(st).result
        assert not tpu.infeasible and not oracle.infeasible
        assert tpu.new_node_cost <= oracle.new_node_cost * 1.02 + 1e-9

    def test_coalesce_never_spends_and_keeps_assignments(self, small_catalog):
        """Tracked path: every pod assignment survives coalescing (renamed to
        the replacement node), no node is overcommitted, and the cost is no
        higher than the uncoalesced creation total."""
        pods, provs = self._c5_shaped(400)
        st = tensorize(pods, provs, small_catalog)
        out = solve_tensors(st, track_assignments=True)
        res = out.result
        assert not res.infeasible
        node_names = {n.name for n in res.nodes} | {n.name for n in res.existing_nodes}
        assert set(res.assignments.values()) <= node_names
        for node in res.nodes:
            for k, v in node.used().items():
                assert v <= node.allocatable.get(k, 0.0) + 1e-6, (
                    f"{node.name} overcommitted on {k}"
                )
        # uncoalesced lower bound: every merge required price <= sum of parts,
        # so the coalesced total is <= the per-pod-equal FFD total too
        oracle = reference.solve(pods, provs, small_catalog)
        assert res.new_node_cost <= oracle.new_node_cost * 1.02 + 1e-9

    def test_hostname_anti_survives_coalescing(self, small_catalog):
        """Hostname anti-affinity caps are per-NODE: two nodes each holding a
        matching pod must never merge.  Capped solves still coalesce — the
        pair check just forbids combining nodes whose slot counts would
        exceed a cap."""
        from karpenter_tpu.solver.coalesce import hostname_constrained

        sel = LabelSelector.of({"app": "x"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "x"},
                        requests={"cpu": 0.25},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)])
                for i in range(6)]
        st = tensorize(pods, [default_prov()], small_catalog)
        assert hostname_constrained(st)  # untracked solves still skip the pass
        res = solve_tensors(st).result
        # anti-affinity still holds node-for-node after extraction+coalescing
        for node in res.nodes:
            assert sum(1 for p in node.pods if p.labels.get("app") == "x") <= 1

    def test_capped_cross_service_fragments_coalesce(self, small_catalog):
        """Bench config 3's shape in miniature: many single-pod-per-service
        hostname-anti fragments merge into shared nodes (one pod per service
        stays the invariant), instead of the whole solve skipping the pass
        (r4: config 3 shipped 1900 nodes where ~309 suffice)."""
        pods = []
        for s in range(8):
            sel = LabelSelector.of({"app": f"svc{s}"})
            for i in range(4):
                pods.append(PodSpec(
                    name=f"svc{s}-{i}", labels={"app": f"svc{s}"},
                    requests={"cpu": 0.5},
                    affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                    owner_key=f"svc{s}"))
        st = tensorize(pods, [default_prov()], small_catalog)
        res = solve_tensors(st).result
        assert not res.infeasible
        # per-node: at most one pod per service, always
        for node in res.nodes:
            per = {}
            for p in node.pods:
                per[p.labels["app"]] = per.get(p.labels["app"], 0) + 1
            assert all(v <= 1 for v in per.values()), (node.name, per)
        # and fragments DID merge: far fewer nodes than one per (svc, pod)
        assert len(res.nodes) <= 8, f"{len(res.nodes)} nodes for 32 capped pods"
        # assignments survived the merges
        node_names = {n.name for n in res.nodes}
        for p in pods:
            assert res.assignments[p.name] in node_names

    def test_nr_estimate_exhaustion_retries_at_full_budget(self, small_catalog):
        """The NR axis is sized by an optimistic resource-only estimate
        (the worst-case one-slot-per-pod axis dominated device time).  A shape the estimate undershoots — hostname
        anti-affinity forces ~1 pod/node where resources allow hundreds —
        must exhaust its slots and transparently re-solve at the full
        budget, placing every pod."""
        from karpenter_tpu.models.tensorize import tensorize as _tz
        from karpenter_tpu.solver.tpu import _node_budget, solve_dims

        sel = LabelSelector.of({"app": "x"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "x"},
                        requests={"cpu": 0.05},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                        owner_key="x")
                for i in range(3000)]
        st = _tz(pods, [default_prov()], small_catalog)
        nb = _node_budget(st, 0, None)
        est = solve_dims(st, NE=0, node_budget=nb)["NR"]
        full = solve_dims(st, NE=0, node_budget=nb, full_nr=True)["NR"]
        assert est < 3000 <= full, (est, full)  # the retry must be needed
        out = solve_tensors(st)
        assert out.result.infeasible == {}
        assert len(out.result.nodes) >= 3000 / 2  # anti caps at 1 matching/node
        for n in out.result.nodes:
            assert sum(1 for p in n.pods if p.labels.get("app") == "x") <= 1

    def test_coalesce_respects_type_pinned_selectors(self, small_catalog):
        """Coalescing must honor the same label feasibility the solve did:
        pods pinned by node_selector to one instance type must never come
        back assigned to a merged node of another type (review finding)."""
        pods = []
        for g in range(2):
            for i in range(2):
                pods.append(PodSpec(
                    name=f"g{g}-p{i}", requests={"cpu": 0.55},
                    node_selector={L.INSTANCE_TYPE: "r5.large"},
                    owner_key=f"g{g}",
                ))
        st = tensorize(pods, [default_prov()], small_catalog)
        res = solve_tensors(st).result
        assert not res.infeasible
        by_name = {n.name: n for n in res.nodes}
        for p in pods:
            node = by_name[res.assignments[p.name]]
            assert node.instance_type == "r5.large", (
                f"{p.name} pinned to r5.large but landed on {node.instance_type}"
            )


class TestWarmFailureBackoffClock:
    """ISSUE 2 satellite: the warm-failure backoff runs on the injectable
    clock (KT002), so tests advance a FakeClock past WARM_FAILURE_BACKOFF
    instead of sleeping it out."""

    def test_backoff_expires_on_the_injected_clock(self, small_catalog):
        from karpenter_tpu.solver.tpu import TpuSolver
        from karpenter_tpu.utils.clock import FakeClock

        clock = FakeClock(start=1_000.0)
        solver = TpuSolver(clock=clock)
        pods = [PodSpec(name=f"w-{i}", requests={"cpu": 0.5, "memory": GIB},
                        owner_key="w") for i in range(4)]
        st = tensorize(pods, [default_prov()], small_catalog)
        sig = solver.signature(st)
        spawned = []
        solver._spawn_warm = lambda sig, kwargs: spawned.append(sig)

        # a compile failure arms the backoff at now + WARM_FAILURE_BACKOFF
        solver._failed_until[sig] = clock.now() + TpuSolver.WARM_FAILURE_BACKOFF
        assert solver.warm_async(st) is False   # inside the backoff window
        assert spawned == []

        clock.advance(TpuSolver.WARM_FAILURE_BACKOFF - 1.0)
        assert solver.warm_async(st) is False   # still 1s short
        assert spawned == []

        clock.advance(2.0)                      # past the backoff
        assert solver.warm_async(st) is True
        assert spawned == [sig]
        # accepted warm is now in flight: immediate retry dedupes
        assert solver.warm_async(st) is False
