"""ScheduleAnyway topology spread: hardened first, relaxed on infeasibility.

Reference semantics: scheduling.md:303-346 (soft spread still influences
placement) on core's preference-relaxation ladder (one preference dropped per
failed attempt).  Parity requirement: a soft-spread workload
distributes across zones on BOTH backends.
"""

import pytest

from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import LabelSelector, PodSpec, TopologySpreadConstraint
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.solver.scheduler import BatchScheduler, _harden_preferences, _n_preferences


def soft_spread_pods(n, key=L.ZONE, skew=1):
    sel = LabelSelector.of({"app": "web"})
    return [
        PodSpec(name=f"p{i}", labels={"app": "web"}, requests={"cpu": 1.0},
                topology_spread=[TopologySpreadConstraint(skew, key, "ScheduleAnyway", sel)],
                owner_key="web")
        for i in range(n)
    ]


class TestHardening:
    def test_soft_spread_counts_as_preference(self):
        p = soft_spread_pods(1)[0]
        assert _n_preferences(p) == 1

    def test_hardened_copy_flips_to_do_not_schedule(self):
        p = soft_spread_pods(1)[0]
        h = _harden_preferences(p)
        assert len(h.topology_spread) == 1
        assert h.topology_spread[0].hard
        assert h.topology_spread[0].max_skew == 1
        # original untouched
        assert not p.topology_spread[0].hard

    def test_keep_zero_drops_soft_spread(self):
        p = soft_spread_pods(1)[0]
        h = _harden_preferences(p, keep=0)
        assert h.topology_spread == []


class TestSoftSpreadPlacement:
    @pytest.mark.parametrize("backend", ["oracle", "tpu"])
    def test_distributes_across_zones(self, small_catalog, backend):
        """Satisfiable soft zone spread must actually spread (not collapse
        into the cheapest single zone), on both backends."""
        sched = BatchScheduler(backend=backend)
        pods = soft_spread_pods(9)
        res = sched.solve(pods, [Provisioner(name="default").with_defaults()],
                          small_catalog)
        assert res.infeasible == {}
        zone_counts = {}
        node_zone = {n.name: n.zone for n in res.nodes}
        for p in pods:
            z = node_zone[res.assignments[p.name]]
            zone_counts[z] = zone_counts.get(z, 0) + 1
        assert len(zone_counts) == 3
        assert max(zone_counts.values()) - min(zone_counts.values()) <= 1

    @pytest.mark.parametrize("backend", ["oracle", "tpu"])
    def test_relaxes_when_unsatisfiable(self, small_catalog, backend):
        """Hostname soft spread (one pod per node) when new nodes are
        blocked entirely: hard semantics would leave pods pending;
        ScheduleAnyway must relax them onto the existing node's free
        capacity.  (New capacity blocked via an exhausted cpu limit makes
        the outcome scoring-independent on every backend.)"""
        from karpenter_tpu.solver.types import SimNode

        sel = LabelSelector.of({"app": "solo"})
        pods = [
            PodSpec(name=f"p{i}", labels={"app": "solo"}, requests={"cpu": 1.0},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.HOSTNAME, "ScheduleAnyway", sel)],
                    owner_key="solo")
            for i in range(3)
        ]
        node = SimNode(
            instance_type="c5.xlarge", provisioner="default", zone="zone-1a",
            capacity_type="on-demand", price=0.17,
            allocatable={"cpu": 3.82, "memory": 8e9, L.RESOURCE_PODS: 20.0},
            labels={L.ZONE: "zone-1a", L.CAPACITY_TYPE: "on-demand",
                    L.INSTANCE_TYPE: "c5.xlarge",
                    L.PROVISIONER_NAME: "default"},
            existing=True,
        )
        # limit already consumed by the existing node: no new capacity
        prov = Provisioner(name="default", limits={"cpu": 3.82}).with_defaults()
        sched = BatchScheduler(backend=backend)
        res = sched.solve(pods, [prov], small_catalog, existing_nodes=[node])
        assert res.infeasible == {}     # nobody left pending
        assert res.nodes == []          # no new capacity launched
        # all three doubled up on the one node (spread relaxed)
        assert all(res.assignments[p.name] == node.name for p in pods)

    @pytest.mark.parametrize("backend", ["oracle", "tpu"])
    def test_retry_wave_sees_prior_placements(self, small_catalog, backend):
        """Cross-wave capacity bookkeeping: wave 1 fills an existing node;
        the relaxation retry for a preference-carrying pod must see that
        placement and NOT double-book the node's capacity."""
        from karpenter_tpu.models.requirements import IN, Requirement
        from karpenter_tpu.solver.types import SimNode

        # existing node with room for exactly one 1-cpu pod
        node = SimNode(
            instance_type="c5.large", provisioner="default", zone="zone-1a",
            capacity_type=L.CAPACITY_TYPE_ON_DEMAND, price=0.085,
            allocatable={"cpu": 1.2, "memory": 8e9, L.RESOURCE_PODS: 10.0},
            labels={L.ZONE: "zone-1a", L.CAPACITY_TYPE: L.CAPACITY_TYPE_ON_DEMAND,
                    L.INSTANCE_TYPE: "c5.large", L.PROVISIONER_NAME: "default"},
            existing=True,
        )
        plain = PodSpec(name="plain", requests={"cpu": 1.0}, owner_key="a")
        picky = PodSpec(
            name="picky", requests={"cpu": 1.0}, owner_key="b",
            # unsatisfiable preference: hardened wave fails, retry drops it
            preferred_affinity_terms=[[Requirement("no-such-label", IN, ["x"])]],
        )
        prov = Provisioner(name="default").with_defaults()
        res = BatchScheduler(backend=backend).solve(
            [plain, picky], [prov], small_catalog, existing_nodes=[node],
        )
        assert res.infeasible == {}
        # the two pods cannot share the 1.2-cpu node
        assert {res.assignments["plain"], res.assignments["picky"]} != {node.name}
        on_existing = [p for p in (plain, picky) if res.assignments[p.name] == node.name]
        assert len(on_existing) <= 1
        assert len(res.nodes) == 1  # exactly one new node for the other pod
        # the caller's node object was never mutated by the simulation
        assert node.pods == []

    def test_relaxation_ladder_depth_capped(self, small_catalog):
        """A pod with more preferences than MAX_RELAXATION_WAVES still
        schedules (top rungs collapse) without one solve per preference."""
        from karpenter_tpu.models.requirements import IN, Requirement
        from karpenter_tpu.solver import scheduler as sched_mod

        pod = PodSpec(
            name="p", requests={"cpu": 1.0}, owner_key="a",
            preferred_affinity_terms=[
                [Requirement(f"pref-{i}", IN, ["x"])] for i in range(20)
            ],
        )
        prov = Provisioner(name="default").with_defaults()
        sched = BatchScheduler(backend="oracle")
        calls = {"n": 0}
        orig = sched._solve_once

        def counting(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        sched._solve_once = counting
        res = sched.solve([pod], [prov], small_catalog)
        assert res.infeasible == {}
        assert calls["n"] <= sched_mod.MAX_RELAXATION_WAVES + 1

    @pytest.mark.parametrize("backend", ["oracle", "tpu"])
    def test_relaxes_with_partial_new_capacity(self, small_catalog, backend):
        """Partial-capacity variant: the limit funds SOME per-pod spread
        nodes but not all; satisfied pods keep their spread nodes and only
        the still-infeasible pod doubles up (0.5-cpu pods make the doubling
        feasible on a c5.large's slack for any scoring policy)."""
        sel = LabelSelector.of({"app": "solo"})
        pods = [
            PodSpec(name=f"p{i}", labels={"app": "solo"}, requests={"cpu": 0.5},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.HOSTNAME, "ScheduleAnyway", sel)],
                    owner_key="solo")
            for i in range(3)
        ]
        # two c5.large fit (3.66 <= 4), a third does not (5.49 > 4)
        prov = Provisioner(name="default", limits={"cpu": 4.0}).with_defaults()
        res = BatchScheduler(backend=backend).solve(pods, [prov], small_catalog)
        assert res.infeasible == {}
        assert sum(n.allocatable.get("cpu", 0.0) for n in res.nodes) <= 4.0
        assert 1 <= len(res.nodes) < 3  # new nodes created, but not per-pod

    def test_hard_spread_still_hard(self, small_catalog):
        """DoNotSchedule must NOT be relaxed by the ladder."""
        sel = LabelSelector.of({"app": "solo"})
        pods = [
            PodSpec(name=f"p{i}", labels={"app": "solo"}, requests={"cpu": 1.0},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.HOSTNAME, "DoNotSchedule", sel)],
                    owner_key="solo")
            for i in range(3)
        ]
        prov = Provisioner(name="default", limits={"cpu": 8.0}).with_defaults()
        res = BatchScheduler(backend="oracle").solve(pods, [prov], small_catalog)
        assert len(res.infeasible) > 0
