"""Periphery: interruption, pricing, settings, GC/link, templates, subnets."""

import pytest

from karpenter_tpu.cloud.fake import FakeCloudProvider
from karpenter_tpu.cloud.templates import (
    Image,
    LaunchTemplateProvider,
    NodeTemplate,
    get_family,
    image_for_instance_type,
    resolve_images,
)
from karpenter_tpu.controllers.garbagecollect import GarbageCollectController, LinkController
from karpenter_tpu.controllers.interruption import (
    REBALANCE_RECOMMENDATION,
    SPOT_INTERRUPTION,
    STATE_CHANGE,
    InterruptionController,
    InterruptionMessage,
    MessageQueue,
)
from karpenter_tpu.controllers.nodetemplate import NodeTemplateController
from karpenter_tpu.controllers.provisioning import ProvisioningController
from karpenter_tpu.controllers.state import ClusterState
from karpenter_tpu.controllers.termination import TerminationController
from karpenter_tpu.events import Recorder
from karpenter_tpu.metrics import Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.machine import Machine
from karpenter_tpu.models.pod import PodSpec, Taint
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement, Requirements
from karpenter_tpu.providers.pricing import PricingProvider
from karpenter_tpu.providers.securitygroup import SecurityGroup, SecurityGroupProvider
from karpenter_tpu.providers.subnet import Subnet, SubnetProvider
from karpenter_tpu.settings import Settings, SettingsStore
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.utils.clock import FakeClock


def make_env(catalog, provisioner=None):
    clock = FakeClock()
    state = ClusterState(clock=clock)
    cloud = FakeCloudProvider(catalog, clock=clock)
    rec, reg = Recorder(), Registry()
    prov = ProvisioningController(
        state, cloud, scheduler=BatchScheduler(backend="oracle", registry=reg),
        recorder=rec, registry=reg, clock=clock,
    )
    term = TerminationController(state, cloud, recorder=rec, registry=reg, clock=clock)
    state.apply_provisioner(provisioner or Provisioner(name="default"))
    return clock, state, cloud, prov, term, rec, reg


def pump(ctrl, clock):
    ctrl.reconcile()
    clock.advance(1.5)
    return ctrl.reconcile()


class TestInterruption:
    def _spot_env(self, small_catalog):
        prov = Provisioner(
            name="default",
            requirements=[Requirement(L.CAPACITY_TYPE, IN, [L.CAPACITY_TYPE_SPOT])],
        )
        clock, state, cloud, prov_ctrl, term, rec, reg = make_env(small_catalog, prov)
        state.add_pod(PodSpec(name="p", requests={"cpu": 0.5}))
        pump(prov_ctrl, clock)
        node_name = state.bindings["p"]
        ns = state.nodes[node_name]
        queue = MessageQueue()
        ic = InterruptionController(
            state, term, queue, unavailable=prov_ctrl.unavailable,
            recorder=rec, registry=reg, clock=clock,
        )
        return clock, state, cloud, term, rec, reg, queue, ic, ns

    def test_spot_interruption_drains_and_blacklists(self, small_catalog):
        clock, state, cloud, term, rec, reg, queue, ic, ns = self._spot_env(small_catalog)
        pid = ns.machine.provider_id
        queue.send(InterruptionMessage(SPOT_INTERRUPTION, pid, clock.now() - 2.0))
        handled = ic.reconcile()
        assert handled == 1
        assert ns.node.name not in state.nodes  # drained + deleted
        assert ic.unavailable.is_unavailable(
            ns.node.instance_type, ns.node.zone, L.CAPACITY_TYPE_SPOT
        )
        assert len(rec.of("SpotInterrupted")) == 1
        assert reg.counter("karpenter_interruption_received_messages_total").get(
            {"message_type": SPOT_INTERRUPTION}) == 1
        # latency histogram observed ~2s
        assert reg.histogram("karpenter_interruption_message_latency_seconds").count(
            {"message_type": SPOT_INTERRUPTION}) == 1

    def test_rebalance_is_advisory(self, small_catalog):
        clock, state, cloud, term, rec, reg, queue, ic, ns = self._spot_env(small_catalog)
        queue.send(InterruptionMessage(REBALANCE_RECOMMENDATION, ns.machine.provider_id, clock.now()))
        ic.reconcile()
        assert ns.node.name in state.nodes  # not drained
        assert len(rec.of("RebalanceRecommendation")) == 1

    def test_state_change_stopping_drains(self, small_catalog):
        clock, state, cloud, term, rec, reg, queue, ic, ns = self._spot_env(small_catalog)
        queue.send(InterruptionMessage(STATE_CHANGE, ns.machine.provider_id, clock.now(), state="stopping"))
        ic.reconcile()
        assert ns.node.name not in state.nodes

    def test_unknown_instance_ignored(self, small_catalog):
        clock, state, cloud, term, rec, reg, queue, ic, ns = self._spot_env(small_catalog)
        queue.send(InterruptionMessage(SPOT_INTERRUPTION, "fake://unknown/999", clock.now()))
        assert ic.reconcile() == 1
        assert ns.node.name in state.nodes


class TestPricing:
    def test_lookups_from_catalog(self, small_catalog):
        p = PricingProvider(small_catalog)
        od = p.on_demand_price("m5.xlarge")
        sp = p.spot_price("m5.xlarge", "zone-1a")
        assert od and sp and sp < od
        assert p.price("m5.xlarge", "zone-1a", "on-demand") == od

    def test_refresh_respects_period_and_change_monitor(self, small_catalog):
        clock = FakeClock()
        prices = {"val": 1.0}
        src = lambda: [("m5.xlarge", "zone-1a", "on-demand", prices["val"])]
        p = PricingProvider(small_catalog, source=src, clock=clock, refresh_period=100.0)
        assert p.maybe_refresh() is True  # first refresh applies change
        assert p.on_demand_price("m5.xlarge") == 1.0
        assert p.updates == 1
        assert p.maybe_refresh() is False  # within period
        clock.advance(101)
        assert p.maybe_refresh() is False  # no change -> not an update
        assert p.updates == 1
        prices["val"] = 2.0
        clock.advance(101)
        assert p.maybe_refresh() is True
        assert p.on_demand_price("m5.xlarge") == 2.0

    def test_isolated_vpc_stays_on_static_fallback(self, small_catalog):
        """Isolated VPCs can't reach the pricing API: never poll the source,
        keep the embedded fallback prices (pricing.go:121-123)."""
        clock = FakeClock()
        static = PricingProvider(small_catalog).on_demand_price("m5.xlarge")
        src = lambda: [("m5.xlarge", "zone-1a", "on-demand", 99.0)]
        p = PricingProvider(small_catalog, source=src, clock=clock,
                            refresh_period=1.0, isolated_vpc=True)
        clock.advance(100)
        assert p.maybe_refresh() is False
        assert p.on_demand_price("m5.xlarge") == static
        assert p.updates == 0


class TestSettings:
    def test_validation(self):
        store = SettingsStore()
        with pytest.raises(ValueError):
            store.update(vm_memory_overhead_percent=1.5)
        with pytest.raises(ValueError):
            store.update(batch_idle_duration=20.0)  # > max 10

    def test_hot_reload_subscribers(self):
        store = SettingsStore()
        seen = []
        store.subscribe(lambda s: seen.append(s.drift_enabled))
        store.update(drift_enabled=True)
        assert seen == [True]
        assert store.current.drift_enabled is True


class TestGCAndLink:
    def test_gc_reaps_leaked_instances(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, rec, reg = make_env(small_catalog)
        # leak: create an instance with no matching node in state
        m = cloud.create(Machine(
            provisioner="other",  # not a known provisioner -> link won't adopt
            requirements=Requirements([Requirement(L.INSTANCE_TYPE, IN, ["m5.large"])]),
        ))
        gc = GarbageCollectController(state, cloud, recorder=rec, clock=clock)
        assert gc.reconcile() == 0  # too young (grace)
        clock.advance(6 * 60)
        assert gc.reconcile() == 1
        assert len(cloud.list()) == 0
        assert len(rec.of("GarbageCollected")) == 1

    def test_link_adopts_owned_orphans(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, rec, reg = make_env(small_catalog)
        m = cloud.create(Machine(
            provisioner="default",
            requirements=Requirements([Requirement(L.INSTANCE_TYPE, IN, ["m5.large"])]),
        ))
        link = LinkController(state, cloud, recorder=rec, clock=clock)
        assert link.reconcile() == 1
        assert len(state.nodes) == 1
        ns = next(iter(state.nodes.values()))
        assert ns.machine.provider_id == m.provider_id
        # adopted nodes are protected from GC
        gc = GarbageCollectController(state, cloud, recorder=rec, clock=clock)
        clock.advance(10 * 60)
        assert gc.reconcile() == 0


class TestTemplates:
    def test_image_resolution_and_variant_pick(self, small_catalog):
        t = NodeTemplate(name="t", image_family="standard")
        images = resolve_images(t)
        assert len(images) == 3
        m5 = next(x for x in small_catalog if x.name == "m5.xlarge")
        img = image_for_instance_type(images, m5)
        assert img.image_id == "img-standard-amd64"

    def test_bootstrap_script_mime_merge(self):
        fam = get_family("standard")
        plain = fam.bootstrap_script("c1", {"a": "b"}, [Taint("t", "NoSchedule", "v")], {})
        assert plain.startswith("#!/bin/bash")
        assert "--node-labels=a=b" in plain and "t=v:NoSchedule" in plain
        merged = fam.bootstrap_script("c1", {}, [], {}, custom_userdata="echo hi")
        assert "multipart/mixed" in merged and "echo hi" in merged

    def test_toml_family(self):
        fam = get_family("toml")
        out = fam.bootstrap_script("c1", {"a": "b"}, [Taint("t", "NoSchedule", "v")], {})
        assert '[settings.kubernetes]' in out and 'cluster-name = "c1"' in out
        assert '"a" = "b"' in out and '"t" = "v:NoSchedule"' in out

    def test_custom_family_requires_selector(self):
        sel = {"discovery": "cluster"}
        bad = NodeTemplate(name="x", image_family="custom",
                           subnet_selector=sel, security_group_selector=sel)
        assert any("image selector" in e for e in bad.validate())
        ok = NodeTemplate(name="x", image_family="custom",
                          subnet_selector=sel, security_group_selector=sel,
                          image_selector={"id": "img-1"})
        assert ok.validate() == []

    def test_launch_template_cache(self):
        lt = LaunchTemplateProvider("c1")
        t = NodeTemplate(name="t", status_security_groups=["sg-1"])
        images = resolve_images(t)
        a = lt.ensure(t, images[0], {"x": "1"}, [])
        b = lt.ensure(t, images[0], {"x": "1"}, [])
        assert a is b and len(lt.created) == 1  # cache hit
        c = lt.ensure(t, images[0], {"x": "2"}, [])
        assert c.name != a.name and len(lt.created) == 2  # different hash
        lt.invalidate(a.name)
        d = lt.ensure(t, images[0], {"x": "1"}, [])
        assert len(lt.created) == 3  # recreated after invalidation

    def test_nodetemplate_controller_status(self):
        clock = FakeClock()
        subnets = SubnetProvider([
            Subnet("sn-1", "zone-1a", 100, tags={"env": "prod"}),
            Subnet("sn-2", "zone-1b", 50, tags={"env": "dev"}),
        ])
        sgs = SecurityGroupProvider([
            SecurityGroup("sg-1", tags={"env": "prod"}),
            SecurityGroup("sg-2", tags={"env": "dev"}),
        ], clock=clock)
        ctrl = NodeTemplateController(subnets, sgs, clock=clock)
        ctrl.apply(NodeTemplate(name="t", subnet_selector={"env": "prod"},
                                security_group_selector={"env": "prod"}))
        t = ctrl.get("t")
        assert t.status_subnets == ["sn-1"]
        assert t.status_security_groups == ["sg-1"]
        assert t.status_images


class TestSubnets:
    def test_zonal_pick_most_free_and_inflight(self):
        p = SubnetProvider([
            Subnet("sn-a1", "zone-1a", 10),
            Subnet("sn-a2", "zone-1a", 100),
            Subnet("sn-b1", "zone-1b", 5),
        ])
        best = p.zonal_subnets_for_launch({})
        assert best["zone-1a"].subnet_id == "sn-a2"
        # in-flight accounting flips the choice
        p.reserve("sn-a2", 95)
        best = p.zonal_subnets_for_launch({})
        assert best["zone-1a"].subnet_id == "sn-a1"
        # sync clears in-flight
        p.sync("sn-a2", 100)
        best = p.zonal_subnets_for_launch({})
        assert best["zone-1a"].subnet_id == "sn-a2"

    def test_exhausted_subnet_excluded(self):
        p = SubnetProvider([Subnet("sn-b1", "zone-1b", 1)])
        p.reserve("sn-b1", 1)
        assert "zone-1b" not in p.zonal_subnets_for_launch({})


class TestSettingsWiring:
    """Every settings key must be consumed somewhere (an early review found
    node_name_convention was defined-but-dead; settings.go:40-65 wires all
    of these into the launch path in the reference)."""

    def test_node_name_convention(self, small_catalog):
        from karpenter_tpu.cloud.fake import FakeCloudProvider
        from karpenter_tpu.models.machine import Machine
        from karpenter_tpu.models.requirements import Requirements
        from karpenter_tpu.settings import Settings

        cloud = FakeCloudProvider(small_catalog)
        m = cloud.create(Machine(provisioner="default", requirements=Requirements()))
        # "ip-10-" (not "ip-10-0-"): the octets encode a process-global
        # sequence, so the assertion must not depend on test order
        assert m.node_name.startswith("ip-10-")  # default ip-name

        cloud.configure_settings(Settings(node_name_convention="resource-name"))
        m2 = cloud.create(Machine(provisioner="default", requirements=Requirements()))
        assert m2.node_name.startswith("i-")

    def test_cluster_name_and_default_tags_on_instances(self, small_catalog):
        from karpenter_tpu.cloud.fake import FakeCloudProvider
        from karpenter_tpu.models.machine import Machine
        from karpenter_tpu.models.requirements import Requirements
        from karpenter_tpu.settings import Settings

        cloud = FakeCloudProvider(small_catalog)
        cloud.configure_settings(Settings(
            cluster_name="prod", tags={"team": "infra", "env": "prod"}
        ))
        m = cloud.create(Machine(provisioner="default", requirements=Requirements()))
        tags = cloud.instances[m.provider_id].tags
        assert tags["kubernetes.io/cluster/prod"] == "owned"
        assert tags["team"] == "infra" and tags["env"] == "prod"
        assert tags["karpenter.sh/provisioner-name"] == "default"

    def test_cluster_endpoint_and_default_profile_in_launch_template(self):
        from karpenter_tpu.cloud.templates import (
            Image, LaunchTemplateProvider, NodeTemplate,
        )
        import base64

        ltp = LaunchTemplateProvider(
            "c1", cluster_endpoint="https://api.example:6443",
            default_instance_profile="KarpenterNodeRole",
        )
        t = NodeTemplate(name="t", subnet_selector={"a": "b"},
                         security_group_selector={"a": "b"})
        lt = ltp.ensure(t, Image("img-standard-amd64", L.ARCH_AMD64), {}, [])
        userdata = base64.b64decode(lt.user_data_b64).decode()
        assert "--apiserver-endpoint 'https://api.example:6443'" in userdata
        assert lt.instance_profile == "KarpenterNodeRole"  # settings default
        # a template-level profile overrides the settings default
        t2 = NodeTemplate(name="t2", subnet_selector={"a": "b"},
                          security_group_selector={"a": "b"},
                          instance_profile="Custom")
        lt2 = ltp.ensure(t2, Image("img-standard-amd64", L.ARCH_AMD64), {}, [])
        assert lt2.instance_profile == "Custom"

    def test_endpoint_and_profile_flow_through_launch(self, small_catalog):
        """clusterEndpoint + defaultInstanceProfile reach the LIVE launch
        path: create() ensures a launch template whose userdata/profile
        carry them (launchtemplate.go EnsureAll before CreateFleet)."""
        import base64

        from karpenter_tpu.cloud.fake import FakeCloudProvider
        from karpenter_tpu.models.machine import Machine
        from karpenter_tpu.models.requirements import Requirements
        from karpenter_tpu.settings import Settings

        cloud = FakeCloudProvider(small_catalog)
        cloud.configure_settings(Settings(
            cluster_endpoint="https://api.example:6443",
            default_instance_profile="KarpenterNodeRole",
        ))
        m = cloud.create(Machine(provisioner="default", requirements=Requirements()))
        assert m.launch_template
        lt = next(t for t in cloud.launch_template_provider._cache.values()
                  if t.name == m.launch_template)
        userdata = base64.b64decode(lt.user_data_b64).decode()
        assert "--apiserver-endpoint 'https://api.example:6443'" in userdata
        assert lt.instance_profile == "KarpenterNodeRole"

    def test_restricted_tag_prefixes_rejected(self):
        from karpenter_tpu.settings import Settings

        assert Settings(tags={"karpenter.sh/provisioner-name": "x"}).validate()
        assert Settings(tags={"kubernetes.io/cluster/prod": "shared"}).validate()
        assert not Settings(tags={"team": "infra"}).validate()

    def test_operator_pushes_settings_into_cloud(self, small_catalog):
        from karpenter_tpu.cloud.fake import FakeCloudProvider
        from karpenter_tpu.metrics import Registry
        from karpenter_tpu.operator import Operator
        from karpenter_tpu.utils.clock import FakeClock

        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        op = Operator(cloud, clock=clock, scheduler_backend="oracle",
                      registry=Registry())
        op.settings.update(cluster_name="blue",
                           node_name_convention="resource-name",
                           tags={"owner": "sre"})
        assert cloud.cluster_name == "blue"
        assert cloud.node_name_convention == "resource-name"
        assert cloud.default_tags == {"owner": "sre"}

    def test_no_dead_settings_keys(self):
        """Every Settings field is read somewhere outside settings.py —
        config keys that nothing consumes are drift seeds."""
        import pathlib
        from dataclasses import fields

        from karpenter_tpu.settings import Settings

        root = pathlib.Path(__file__).resolve().parents[1] / "karpenter_tpu"
        corpus = "\n".join(
            p.read_text() for p in root.rglob("*.py")
            if p.name != "settings.py"
        )
        for f in fields(Settings):
            assert f.name in corpus, f"settings key {f.name!r} is consumed nowhere"
