"""Native C++ FFD tier: parity with the oracle + routing policy."""

import pytest

from karpenter_tpu.models import labels as L
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.pod import LabelSelector, PodSpec, TopologySpreadConstraint
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.solver import native, reference
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.types import SimNode


def default_prov(**kw):
    return Provisioner(name=kw.pop("name", "default"), **kw).with_defaults()


pytestmark = pytest.mark.skipif(not native.available(), reason="native lib unavailable")


class TestNativeParity:
    def _check(self, pods, provs, catalog, existing=()):
        oracle = reference.solve(pods, provs, catalog, existing_nodes=list(existing))
        st = tensorize(pods, provs, catalog)
        got = native.solve_tensors_native(st, existing_nodes=list(existing))
        assert len(got.infeasible) == len(oracle.infeasible)
        assert got.n_scheduled == oracle.n_scheduled
        if oracle.new_node_cost:
            assert got.new_node_cost / oracle.new_node_cost <= 1.02 + 1e-9, (
                f"native ${got.new_node_cost:.3f} vs oracle ${oracle.new_node_cost:.3f}"
            )
        return got

    def test_version(self):
        assert "karpenter-tpu-native" in native.version()

    def test_single_group(self, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(50)]
        got = self._check(pods, [default_prov()], small_catalog)
        assert got.infeasible == {}

    def test_mixed_groups(self, small_catalog):
        pods = [PodSpec(name=f"a{i}", requests={"cpu": 1.0}, owner_key="a") for i in range(30)]
        pods += [PodSpec(name=f"b{i}", requests={"cpu": 0.5, "memory": 6 * GIB}, owner_key="b")
                 for i in range(30)]
        pods += [PodSpec(name=f"c{i}", requests={"cpu": 14.0}, owner_key="c") for i in range(2)]
        self._check(pods, [default_prov()], small_catalog)

    def test_full_catalog(self, full_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 2.0, "memory": 4 * GIB})
                for i in range(100)]
        self._check(pods, [default_prov()], full_catalog)

    def test_weighted_provisioners(self, small_catalog):
        spot = Provisioner(
            name="spot", weight=10,
            requirements=[Requirement(L.CAPACITY_TYPE, IN, [L.CAPACITY_TYPE_SPOT])],
        ).with_defaults()
        od = default_prov(name="od", weight=1)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(20)]
        got = self._check(pods, [spot, od], small_catalog)
        assert all(n.capacity_type == L.CAPACITY_TYPE_SPOT for n in got.nodes)

    def test_existing_nodes_first(self, small_catalog):
        it = next(t for t in small_catalog if t.name == "m5.4xlarge")
        existing = [SimNode(
            instance_type="m5.4xlarge", provisioner="default", zone="zone-1a",
            capacity_type="on-demand", price=0.768, allocatable=dict(it.allocatable),
            labels={**it.labels(), L.ZONE: "zone-1a", L.CAPACITY_TYPE: "on-demand",
                    L.PROVISIONER_NAME: "default"},
            existing=True,
        )]
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(5)]
        got = self._check(pods, [default_prov()], small_catalog, existing=existing)
        assert got.nodes == []

    def test_infeasible(self, small_catalog):
        pods = [PodSpec(name="giant", requests={"cpu": 9999.0}),
                PodSpec(name="ok", requests={"cpu": 1.0})]
        got = self._check(pods, [default_prov()], small_catalog)
        assert "giant" in got.infeasible

    def test_zone_spread(self, small_catalog):
        sel = LabelSelector.of({"app": "web"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "web"}, requests={"cpu": 1.0},
                        topology_spread=[TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)],
                        owner_key="web")
                for i in range(12)]
        got = self._check(pods, [default_prov()], small_catalog)
        per_zone = {}
        for n in got.nodes:
            per_zone[n.zone] = per_zone.get(n.zone, 0) + len(n.pods)
        assert max(per_zone.values()) - min(per_zone.values()) <= 1

    def test_hostname_anti_affinity(self, small_catalog):
        from karpenter_tpu.models.pod import PodAffinityTerm

        sel = LabelSelector.of({"app": "solo"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "solo"}, requests={"cpu": 0.5},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                        owner_key="solo")
                for i in range(6)]
        got = self._check(pods, [default_prov()], small_catalog)
        assert len(got.nodes) == 6  # one matcher per node
        assert all(len(n.pods) == 1 for n in got.nodes)

    def test_existing_topology_state(self, small_catalog):
        """ex_selcnt/zc0 marshaling: spread counters must see pods already
        bound on existing nodes, so new placements balance against them."""
        sel = LabelSelector.of({"app": "web"})
        it = next(t for t in small_catalog if t.name == "m5.4xlarge")

        def node(zone):
            return SimNode(
                instance_type="m5.4xlarge", provisioner="default", zone=zone,
                capacity_type="on-demand", price=0.768, allocatable=dict(it.allocatable),
                labels={**it.labels(), L.ZONE: zone, L.CAPACITY_TYPE: "on-demand",
                        L.PROVISIONER_NAME: "default"},
                existing=True,
            )

        n1 = node("zone-1a")
        # two spread-matching pods already sit in zone-1a
        for i in range(2):
            n1.pods.append(PodSpec(name=f"old{i}", labels={"app": "web"},
                                   requests={"cpu": 1.0}, owner_key="web"))
        spread = [TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)]
        pods = [PodSpec(name=f"new{i}", labels={"app": "web"}, requests={"cpu": 1.0},
                        topology_spread=list(spread), owner_key="web")
                for i in range(2)]
        got = self._check(pods, [default_prov()], small_catalog, existing=[n1])
        # skew=1 with 2 already in zone-1a: both new pods must land elsewhere
        new_zones = [n.zone for n in got.nodes]
        assert all(z != "zone-1a" for z in new_zones)


class TestRouting:
    def test_auto_routes_small_to_oracle(self, small_catalog):
        """Steady-state sub-crossover batches are served by the oracle —
        exact FFD parity (r4 weak #3: the native tier permanently served
        19-20-node answers where the oracle packs 16)."""
        sched = BatchScheduler(backend="auto")
        assert sched._route_small(10)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(10)]
        st = tensorize(pods, [default_prov()], small_catalog)
        assert not sched._route_native(st, 10)

    def test_auto_small_batch_matches_oracle_exactly(self, small_catalog):
        from karpenter_tpu.metrics import SOLVER_BACKEND_DURATION, Registry
        from karpenter_tpu.solver import reference

        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="u")
                for i in range(60)]
        got = sched.solve(pods, [default_prov()], small_catalog)
        oracle = reference.solve(pods, [default_prov()], small_catalog)
        assert len(got.nodes) == len(oracle.nodes)
        assert abs(got.new_node_cost - oracle.new_node_cost) < 1e-9
        # and it really was the oracle that served it
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "oracle"}) >= 1
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "tpu"}) == 0

    def test_auto_routes_big_to_device(self, small_catalog):
        sched = BatchScheduler(backend="auto", native_batch_limit=64)
        assert not sched._route_small(100)
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(100)]
        st = tensorize(pods, [default_prov()], small_catalog)
        assert not sched._route_native(st, 100)

    def test_forced_native_backend_routes_native(self, small_catalog):
        sched = BatchScheduler(backend="native")
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(10)]
        st = tensorize(pods, [default_prov()], small_catalog)
        assert sched._route_native(st, 10)

    def test_native_backend_end_to_end(self, small_catalog):
        sched = BatchScheduler(backend="native")
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="d") for i in range(25)]
        res = sched.solve(pods, [default_prov()], small_catalog)
        assert res.infeasible == {}
        assert res.n_scheduled == 25

    def test_existing_compat_memo_matches_naive(self, small_catalog):
        """The [G, NE] compat memo (signature x node-class collapse) must
        answer exactly what the per-(group, node) requirement-algebra walk
        answers — across per-node hostname labels (which must NOT split
        classes), taints vs tolerations, node selectors, and the
        Exists+NotIn vs NotIn signature-collision case signature() exists
        to keep apart."""
        from karpenter_tpu.models.pod import Taint, Toleration
        from karpenter_tpu.models.requirements import EXISTS, NOT_IN

        it = next(t for t in small_catalog if t.name == "m5.4xlarge")

        def node(i, zone, taints=(), extra=None):
            n = SimNode(
                instance_type="m5.4xlarge", provisioner="default", zone=zone,
                capacity_type="on-demand", price=0.768,
                allocatable=dict(it.allocatable),
                labels={**it.labels(), L.ZONE: zone,
                        L.CAPACITY_TYPE: "on-demand",
                        L.PROVISIONER_NAME: "default", **(extra or {})},
                taints=list(taints), existing=True, name=f"ex-{i}",
            )
            n.labels[L.HOSTNAME] = n.name  # unique per node
            return n

        existing = (
            [node(i, "zone-1a") for i in range(3)]
            + [node(i + 3, "zone-1b",
                    taints=[Taint(key="dedicated", effect=L.EFFECT_NO_SCHEDULE,
                                  value="svc")]) for i in range(3)]
            + [node(7, "zone-1a", extra={"tier": "gold"})]
        )
        pods = (
            [PodSpec(name=f"plain{i}", requests={"cpu": 0.5},
                     owner_key=f"o{i}") for i in range(4)]
            + [PodSpec(name="tol", requests={"cpu": 0.5},
                       tolerations=[Toleration(key="dedicated",
                                               operator="Equal", value="svc",
                                               effect=L.EFFECT_NO_SCHEDULE)])]
            + [PodSpec(name="sel", requests={"cpu": 0.5},
                       node_selector={"tier": "gold"})]
            + [PodSpec(name="notin", requests={"cpu": 0.5},
                       required_affinity_terms=[[
                           Requirement("tier", NOT_IN, ["gold"])]])]
            + [PodSpec(name="exists-notin", requests={"cpu": 0.5},
                       required_affinity_terms=[[
                           Requirement("tier", EXISTS),
                           Requirement("tier", NOT_IN, ["gold"])]])]
        )
        st = tensorize(pods, [default_prov()], small_catalog)
        got = native.existing_compat(st, existing)
        for gi, g in enumerate(st.groups):
            rep = g.pods[0]
            for ni, n in enumerate(existing):
                want = (not any(t.blocks(rep.tolerations) for t in n.taints)
                        and g.requirements.compatible(n.labels) is None)
                assert bool(got[gi, ni]) == want, (g.pods[0].name, n.name)

    def test_native_latency_microseconds(self, small_catalog):
        """The point of the tier: sub-millisecond small solves (after warmup)."""
        sched = BatchScheduler(backend="native")
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(10)]
        sched.solve(pods, [default_prov()], small_catalog)  # warm caches
        import time

        prov = [default_prov()]
        t0 = time.perf_counter()
        res = sched.solve(pods, prov, small_catalog)
        dt = (time.perf_counter() - t0) * 1000
        assert res.n_scheduled == 10
        assert dt < 250  # whole pipeline incl. tensorize; C++ core itself is ~us


def test_unavailable_native_tier_says_why(monkeypatch, caplog):
    """ISSUE 21: a missing g++ or a failed build must not silently turn the
    cold tier into the Python oracle — ``available()`` logs the reason and
    keeps it for the sidecar's startup line (``load_error``)."""
    import logging
    import subprocess

    from karpenter_tpu.solver import native

    def no_compiler():
        raise subprocess.CalledProcessError(
            1, ["g++"], stderr=b"ffd.cpp:1: fatal error: boom")

    monkeypatch.setattr(native, "_load", no_compiler)
    monkeypatch.setattr(native, "_load_error", "")
    with caplog.at_level(logging.WARNING, logger=native.__name__):
        assert native.available() is False
        assert native.available() is False  # same reason: logged once
    assert "fatal error: boom" in native.load_error()
    assert [r for r in caplog.records
            if "native FFD tier unavailable" in r.getMessage()].__len__() == 1
