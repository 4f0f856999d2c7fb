"""Deprovisioning ladder: emptiness, expiration, drift, consolidation."""

import pytest

from karpenter_tpu.cloud.fake import FakeCloudProvider
from karpenter_tpu.controllers.deprovisioning import (
    MIN_NODE_LIFETIME,
    DeprovisioningController,
)
from karpenter_tpu.controllers.provisioning import ProvisioningController
from karpenter_tpu.controllers.state import ClusterState
from karpenter_tpu.controllers.termination import TerminationController
from karpenter_tpu.events import Recorder
from karpenter_tpu.metrics import Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pdb import PodDisruptionBudget
from karpenter_tpu.models.pod import LabelSelector, PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.utils.clock import FakeClock


def make_env(small_catalog, provisioner=None, drift_enabled=False):
    clock = FakeClock()
    state = ClusterState(clock=clock)
    cloud = FakeCloudProvider(small_catalog, clock=clock)
    recorder = Recorder()
    registry = Registry()
    sched = BatchScheduler(backend="oracle", registry=registry)
    prov_ctrl = ProvisioningController(
        state, cloud, scheduler=sched, recorder=recorder, registry=registry, clock=clock
    )
    term = TerminationController(state, cloud, recorder=recorder, registry=registry, clock=clock)
    deprov = DeprovisioningController(
        state, cloud, term, provisioning=prov_ctrl, scheduler=sched,
        recorder=recorder, registry=registry, clock=clock, drift_enabled=drift_enabled,
        deprovisioning_ttl=0.0,  # unit tests exercise mechanisms directly;
                                 # TestDeprovisioningTTL covers the 15s wait
    )
    state.apply_provisioner(provisioner or Provisioner(name="default", consolidation_enabled=True))
    return clock, state, cloud, prov_ctrl, term, deprov, recorder


def pump(ctrl, clock, idle=1.5):
    ctrl.reconcile()
    clock.advance(idle)
    return ctrl.reconcile()


def schedule(state, prov_ctrl, clock, pods):
    for p in pods:
        state.add_pod(p)
    return pump(prov_ctrl, clock)


C2X = Requirement(L.INSTANCE_TYPE, IN, ["c5.2xlarge"])


class TestEmptiness:
    def test_ttl_after_empty_deletes(self, small_catalog):
        prov = Provisioner(name="default", ttl_seconds_after_empty=30.0)
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog, prov)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        node_name = state.bindings["p"]
        state.delete_pod("p")
        state.empty_nodes()  # observe emptiness start
        clock.advance(31)
        action = deprov.reconcile()
        assert action is not None and action.mechanism == "emptiness"
        assert node_name not in state.nodes
        assert cloud.delete_calls  # instance terminated

    def test_consolidation_owns_empty_nodes_when_enabled(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        node_name = state.bindings["p"]
        state.delete_pod("p")
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None
        assert action.mechanism == "consolidation" and action.kind == "delete"
        assert node_name not in state.nodes

    def test_daemon_only_node_reclaimed_under_pending_pods(self, small_catalog):
        """The anti-starvation empties path must count daemon-only nodes as
        empty (matching state.empty_nodes()): clusters running daemonsets —
        the common case — still get the unbounded-growth guard while a pod is
        perpetually pending."""
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        node_name = state.bindings["p"]
        # a daemon pod lands on the node; the workload pod then goes away
        state.add_pod(PodSpec(name="ds-p", requests={"cpu": 0.1}, is_daemon=True))
        state.bind("ds-p", node_name)
        state.delete_pod("p")
        # a pending pod that can never use this node keeps the cluster in the
        # stabilization path
        state.add_pod(PodSpec(name="stuck", requests={"cpu": 1.0},
                              node_selector={L.INSTANCE_TYPE: "no-such-type"}))
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None and action.kind == "delete"
        assert node_name not in state.nodes
        # the daemon pod died with its node — it must not linger as a pending
        # pod or trigger provisioning (create/delete churn loop)
        assert "ds-p" not in state.pods
        nodes_before = len(state.nodes)
        creates_before = len(cloud.create_calls)
        pump(prov_ctrl, clock)
        assert len(cloud.create_calls) == creates_before
        assert len(state.nodes) == nodes_before

    def test_young_nodes_not_consolidated(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        state.delete_pod("p")
        clock.advance(60)  # < 5 min lifetime
        assert deprov.reconcile() is None


class TestConsolidationDelete:
    def test_underutilized_node_drained_onto_peer(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog,
            Provisioner(name="default", consolidation_enabled=True, requirements=[C2X]),
        )
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d") for i in range(20)]
        schedule(state, prov_ctrl, clock, pods)
        assert len(state.nodes) == 2
        # free up most of the fuller node
        node_pods = {}
        for p, n in state.bindings.items():
            node_pods.setdefault(n, []).append(p)
        big_node = max(node_pods, key=lambda n: len(node_pods[n]))
        for p in node_pods[big_node][:10]:
            state.delete_pod(p)
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        # either a single-node delete or a multi-node replace-with-one is
        # acceptable; both converge to one node with everything placed
        assert action is not None and action.mechanism == "consolidation"
        pump(prov_ctrl, clock)
        assert len(state.nodes) == 1
        assert not state.pending_pods()

    def test_spot_is_delete_only(self, small_catalog):
        prov = Provisioner(
            name="default", consolidation_enabled=True,
            requirements=[
                Requirement(L.CAPACITY_TYPE, IN, [L.CAPACITY_TYPE_SPOT]),
                Requirement(L.INSTANCE_TYPE, IN, ["c5.2xlarge"]),
            ],
        )
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog, prov)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        clock.advance(MIN_NODE_LIFETIME + 1)
        # pod can't fit elsewhere (single node) -> only a replace would help,
        # but spot is delete-only -> no action
        assert deprov.reconcile() is None
        assert len(state.nodes) == 1


class TestConsolidationReplace:
    def test_replace_with_cheaper_node(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog,
            Provisioner(name="default", consolidation_enabled=True, requirements=[C2X]),
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        old_node = state.bindings["p"]
        old_price = state.nodes[old_node].node.price
        # widen the provisioner so cheaper types become available
        state.apply_provisioner(Provisioner(name="default", consolidation_enabled=True))
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None and action.kind == "replace"
        assert action.savings > 0
        assert old_node not in state.nodes
        # replacement exists and is cheaper
        assert len(state.nodes) == 1
        new_ns = next(iter(state.nodes.values()))
        assert new_ns.node.price < old_price
        # evicted pod reschedules onto the replacement
        pump(prov_ctrl, clock)
        assert state.bindings["p"] == new_ns.node.name
        assert len(state.nodes) == 1


class TestReplacementWaitReady:
    """Replace actions launch the replacement, then wait for readiness before
    terminating the old node (designs/deprovisioning.md:32-33)."""

    def _trigger_replace(self, small_catalog, ready_delay):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog,
            Provisioner(name="default", consolidation_enabled=True, requirements=[C2X]),
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        old_node = state.bindings["p"]
        state.apply_provisioner(Provisioner(name="default", consolidation_enabled=True))
        cloud.node_ready_delay = ready_delay
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None and action.kind == "replace"
        return clock, state, cloud, deprov, recorder, old_node

    def test_old_node_survives_until_replacement_ready(self, small_catalog):
        clock, state, cloud, deprov, recorder, old_node = self._trigger_replace(
            small_catalog, ready_delay=30.0
        )
        # replacement launched, old node still serving
        assert old_node in state.nodes
        assert len(state.nodes) == 2
        repl = next(n for n in state.nodes if n != old_node)
        assert not state.nodes[repl].initialized
        # nomination shields the empty replacement from consolidation
        assert state.nodes[repl].nominated_until > clock.now()

        # not ready yet: nothing happens, and no new action starts
        clock.advance(10)
        assert deprov.reconcile() is None
        assert old_node in state.nodes

        # readiness reached: old node terminated, pod reschedules
        clock.advance(25)
        deprov.reconcile()
        assert old_node not in state.nodes
        assert state.nodes[repl].initialized

    def test_interrupted_replacement_abandons_action(self, small_catalog):
        """A spot interruption that kills the replacement mid-wait abandons
        the consolidation action; the old node keeps serving."""
        clock, state, cloud, deprov, recorder, old_node = self._trigger_replace(
            small_catalog, ready_delay=60.0
        )
        repl = next(n for n in state.nodes if n != old_node)
        # the interruption controller's effect: the replacement node vanishes
        state.remove_node(repl)
        clock.advance(10)
        assert deprov.reconcile() is None
        assert old_node in state.nodes  # action abandoned, no termination
        # the wait-ready state machine is cleared, not wedged
        assert deprov._pending is None

    def test_timeout_abandons_and_reaps_replacement(self, small_catalog):
        clock, state, cloud, deprov, recorder, old_node = self._trigger_replace(
            small_catalog, ready_delay=1e12  # never becomes ready
        )
        repl = next(n for n in state.nodes if n != old_node)
        from karpenter_tpu.controllers.deprovisioning import REPLACEMENT_READY_TIMEOUT

        clock.advance(REPLACEMENT_READY_TIMEOUT + 1)
        deprov.reconcile()
        # the doomed replacement is reaped; the old node keeps serving
        assert repl not in state.nodes
        assert old_node in state.nodes
        assert any(e.reason == "ReplacementTimedOut" for e in recorder.events)


class TestDeprovisioningTTL:
    """Proposed actions wait DEPROVISIONING_TTL, get re-validated against
    fresh state, then execute (designs/deprovisioning.md 'DeprovisioningTTL
    of 15 seconds')."""

    def _env(self, small_catalog):
        clock = FakeClock()
        state = ClusterState(clock=clock)
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        recorder = Recorder()
        registry = Registry()
        sched = BatchScheduler(backend="oracle", registry=registry)
        prov_ctrl = ProvisioningController(
            state, cloud, scheduler=sched, recorder=recorder, registry=registry, clock=clock
        )
        term = TerminationController(state, cloud, recorder=recorder, registry=registry, clock=clock)
        deprov = DeprovisioningController(
            state, cloud, term, provisioning=prov_ctrl, scheduler=sched,
            recorder=recorder, registry=registry, clock=clock,
        )  # default 15s TTL
        state.apply_provisioner(Provisioner(name="default", consolidation_enabled=True))
        return clock, state, cloud, prov_ctrl, deprov

    def test_action_deferred_then_executed(self, small_catalog):
        clock, state, cloud, prov_ctrl, deprov = self._env(small_catalog)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        node = state.bindings["p"]
        state.delete_pod("p")
        clock.advance(MIN_NODE_LIFETIME + 1)
        # first reconcile proposes but does not act
        assert deprov.reconcile() is None
        assert node in state.nodes
        # still inside the TTL: nothing happens
        clock.advance(5)
        assert deprov.reconcile() is None
        assert node in state.nodes
        # TTL passed: re-validated and executed
        clock.advance(11)
        action = deprov.reconcile()
        assert action is not None and action.kind == "delete"
        assert node not in state.nodes

    def test_grown_delete_set_does_not_starve_proposal(self, small_catalog):
        """If MORE nodes become delete-eligible during the TTL wait, the
        proposed subset still executes instead of restarting the clock."""
        clock, state, cloud, prov_ctrl, deprov = self._env(small_catalog)
        schedule(state, prov_ctrl, clock, [
            PodSpec(name="p1", requests={"cpu": 1.0}),
            PodSpec(name="p2", requests={"cpu": 7.0}),  # forces a 2nd node
        ])
        n1, n2 = state.bindings["p1"], state.bindings["p2"]
        state.delete_pod("p1")
        clock.advance(MIN_NODE_LIFETIME + 1)
        assert deprov.reconcile() is None  # proposes delete of n1's node
        # during the wait the second node empties too -> eligible set grows
        state.delete_pod("p2")
        clock.advance(16)
        action = deprov.reconcile()
        assert action is not None and action.kind == "delete"
        assert set(action.nodes) <= {n1, n2} and len(action.nodes) >= 1

    def test_invalidated_proposal_dropped(self, small_catalog):
        clock, state, cloud, prov_ctrl, deprov = self._env(small_catalog)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 1.0})])
        node = state.bindings["p"]
        state.delete_pod("p")
        clock.advance(MIN_NODE_LIFETIME + 1)
        assert deprov.reconcile() is None  # proposal armed
        # conditions change inside the TTL: a pod lands on the node again
        state.add_pod(PodSpec(name="q", requests={"cpu": 1.0}))
        state.bind("q", node)
        clock.advance(16)
        assert deprov.reconcile() is None  # re-validation fails; no action
        assert node in state.nodes


class TestMultiNode:
    def test_multi_node_delete(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog,
            Provisioner(name="default", consolidation_enabled=True, requirements=[C2X]),
        )
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d") for i in range(30)]
        schedule(state, prov_ctrl, clock, pods)
        n0 = len(state.nodes)
        assert n0 >= 2
        # empty out all but ~4 pods across the cluster
        for p in list(state.pods)[: len(state.pods) - 4]:
            state.delete_pod(p)
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None and action.kind == "delete"
        pump(prov_ctrl, clock)
        assert len(state.nodes) < n0
        assert not state.pending_pods()


class TestMultiSubsetScreen:
    def test_subset_screen_finds_pairwise_delete(self, small_catalog):
        """With >= SUBSET_SCREEN_MIN candidates, the batched subset screen
        runs first and confirms a multi-node delete exactly."""
        from karpenter_tpu.controllers.deprovisioning import SUBSET_SCREEN_MIN

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog,
            Provisioner(name="default", consolidation_enabled=True, requirements=[C2X]),
        )
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d")
                for i in range(60)]
        schedule(state, prov_ctrl, clock, pods)
        n0 = len(state.nodes)
        assert n0 >= SUBSET_SCREEN_MIN
        # shrink to a handful of pods so several nodes can empty out together
        for p in list(state.pods)[: len(state.pods) - 5]:
            state.delete_pod(p)
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is not None and action.kind == "delete"
        assert len(action.nodes) >= 2  # a genuine multi-node action
        pump(prov_ctrl, clock)
        assert not state.pending_pods()


class TestBlockers:
    def test_do_not_evict_blocks(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog)
        schedule(state, prov_ctrl, clock,
                 [PodSpec(name="p", requests={"cpu": 0.5}, do_not_evict=True)])
        state.add_pod(PodSpec(name="q", requests={"cpu": 0.5}))
        pump(prov_ctrl, clock)
        clock.advance(MIN_NODE_LIFETIME + 1)
        action = deprov.reconcile()
        assert action is None

    def test_pdb_blocks_drain(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog)
        schedule(state, prov_ctrl, clock,
                 [PodSpec(name="p", labels={"app": "db"}, requests={"cpu": 0.5})])
        term.pdbs.append(PodDisruptionBudget(
            name="db-pdb", selector=LabelSelector.of({"app": "db"}), min_available=1,
        ))
        node = state.bindings["p"]
        term.begin(node)
        term.reconcile()
        # pod not evictable -> node still present with pod
        assert node in state.nodes
        assert state.bindings.get("p") == node
        assert term.blocked(node) == ["p"]


class TestExpirationAndDrift:
    def test_expiration_replaces(self, small_catalog):
        prov = Provisioner(name="default", ttl_seconds_until_expired=3600.0)
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog, prov)
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        node = state.bindings["p"]
        # reconcile before expiry: no action, and this must NOT suppress the
        # later time-driven expiration (regression: seqnum backoff starved
        # clock-driven mechanisms)
        assert deprov.reconcile() is None
        clock.advance(3601)
        action = deprov.reconcile()
        assert action is not None and action.mechanism == "expiration"
        assert node not in state.nodes
        # pod pending again; provisioning replaces the node
        pump(prov_ctrl, clock)
        assert "p" in state.bindings

    def test_drift_gated_and_replaces(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        node = state.bindings["p"]
        pid = state.nodes[node].machine.provider_id
        cloud.mark_drifted(pid)
        clock.advance(10)
        action = deprov.reconcile()
        assert action is not None and action.mechanism == "drift"
        assert node not in state.nodes

    def test_drift_disabled_no_action(self, small_catalog):
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=False,
            provisioner=Provisioner(name="default"),
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        node = state.bindings["p"]
        cloud.mark_drifted(state.nodes[node].machine.provider_id)
        clock.advance(10)
        assert deprov.reconcile() is None

    def test_image_drift_detected_when_newer_image_published(self, small_catalog):
        """Real drift (cloudprovider.go:258-287): machines launch with the
        currently-resolved image; publishing a newer image per alias makes the
        old image unresolved -> drifted -> replace."""
        from karpenter_tpu.cloud.templates import Image

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        node = state.bindings["p"]
        machine = state.nodes[node].machine
        assert machine.image_id == "img-standard-amd64"
        assert not cloud.is_machine_drifted(machine)

        cloud.publish_image(
            Image("img-standard-amd64-v2", L.ARCH_AMD64, created_at=99.0, family="standard")
        )
        assert cloud.is_machine_drifted(machine)
        clock.advance(10)
        action = deprov.reconcile()
        assert action is not None and action.mechanism == "drift"
        assert node not in state.nodes

    def test_launch_template_override_drift(self, small_catalog):
        """launch_template_name templates launch with the named LT's image;
        repointing the LT at a new image drifts existing machines."""
        from karpenter_tpu.cloud.templates import NodeTemplate

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        cloud.templates["default"] = NodeTemplate(
            name="default", subnet_selector={"discovery": "c"},
            launch_template_name="my-lt",
        )
        cloud.register_launch_template("my-lt", "img-custom-v1")
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        machine = state.nodes[state.bindings["p"]].machine
        assert machine.image_id == "img-custom-v1"
        assert not cloud.is_machine_drifted(machine)
        cloud.register_launch_template("my-lt", "img-custom-v2")
        assert cloud.is_machine_drifted(machine)

    def test_drift_replace_waits_for_replacement_readiness(self, small_catalog):
        """Drift replaces share the launch-then-wait path: the drifted node
        keeps serving until its pre-launched replacement initializes."""
        from karpenter_tpu.cloud.templates import Image

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        old = state.bindings["p"]
        cloud.node_ready_delay = 40.0
        cloud.publish_image(
            Image("img-standard-amd64-v2", L.ARCH_AMD64, created_at=99.0, family="standard")
        )
        clock.advance(10)
        action = deprov.reconcile()
        assert action is not None and action.mechanism == "drift"
        # old node alive; replacement launched, not yet initialized
        assert old in state.nodes
        repl = next(n for n in state.nodes if n != old)
        assert not state.nodes[repl].initialized
        clock.advance(5)
        assert deprov.reconcile() is None and old in state.nodes
        # readiness: old node drains, pod reschedules onto the replacement
        clock.advance(36)
        deprov.reconcile()
        assert old not in state.nodes
        pump(prov_ctrl, clock)
        assert state.bindings["p"] == repl

    def test_failed_replace_backs_off_instead_of_hot_looping(self, small_catalog):
        """A replace whose machine create persistently fails retries on the
        REPLACE_RETRY_BACKOFF cadence, not every tick."""
        from karpenter_tpu.cloud.base import InsufficientCapacityError
        from karpenter_tpu.cloud.templates import Image
        from karpenter_tpu.controllers.deprovisioning import REPLACE_RETRY_BACKOFF

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        old = state.bindings["p"]
        cloud.publish_image(
            Image("img-standard-amd64-v2", L.ARCH_AMD64, created_at=99.0, family="standard")
        )
        creates_before = len(cloud.create_calls)
        cloud.next_error = InsufficientCapacityError("c5.large", "zone-1a", "on-demand")
        clock.advance(10)
        action = deprov.reconcile()   # create fails -> action aborted
        assert old in state.nodes
        first_attempt = len(cloud.create_calls)
        assert first_attempt == creates_before + 1
        # inside the backoff window: drift does NOT re-attempt the create
        for _ in range(5):
            clock.advance(10)
            deprov.reconcile()
        assert len(cloud.create_calls) == first_attempt
        # after the cool-off the replace retries (and now succeeds)
        clock.advance(REPLACE_RETRY_BACKOFF + 1)
        deprov.reconcile()
        assert len(cloud.create_calls) == first_attempt + 1
        assert old not in state.nodes  # replacement launched, old drained

    def test_infeasible_replace_defers_instead_of_evicting(self, small_catalog):
        """When the replacement what-if is INFEASIBLE — the node's pods cannot
        be rescheduled onto the remaining cluster plus one new node — the
        replace must abort and arm the per-node backoff, NOT fall through to
        terminate (launch-before-delete invariant, consolidation.md:15)."""
        from karpenter_tpu.controllers.deprovisioning import REPLACE_RETRY_BACKOFF

        prov = Provisioner(
            name="default", ttl_seconds_until_expired=3600.0, requirements=[C2X]
        )
        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(small_catalog, prov)
        schedule(state, prov_ctrl, clock, [
            PodSpec(name="p", requests={"cpu": 1.0},
                    node_selector={L.INSTANCE_TYPE: "c5.2xlarge"}),
        ])
        node = state.bindings["p"]
        # narrow the pool so no replacement can ever host the pinned pod
        state.apply_provisioner(Provisioner(
            name="default", ttl_seconds_until_expired=3600.0,
            requirements=[Requirement(L.INSTANCE_TYPE, IN, ["m5.large"])],
        ))
        deletes_before = len(cloud.delete_calls)
        clock.advance(3601)
        deprov.reconcile()
        # node survives, pod stays bound, nothing launched or terminated
        assert node in state.nodes
        assert state.bindings["p"] == node
        assert len(cloud.delete_calls) == deletes_before
        assert not cloud.create_calls[1:]  # only the original provisioning create
        assert any(e.reason == "ReplacementInfeasible" for e in recorder.events)
        # backoff: the doomed replace isn't re-planned every tick
        for _ in range(3):
            clock.advance(10)
            deprov.reconcile()
        assert node in state.nodes
        # after the cool-off it is re-examined (still infeasible, still alive)
        clock.advance(REPLACE_RETRY_BACKOFF + 1)
        deprov.reconcile()
        assert node in state.nodes and state.bindings["p"] == node

    def test_selector_images_do_not_drift_while_still_matching(self, small_catalog):
        """Selector-pinned images (ami.go:158-230) keep matching even when
        other images appear, so no drift is reported."""
        from karpenter_tpu.cloud.templates import Image, NodeTemplate

        clock, state, cloud, prov_ctrl, term, deprov, recorder = make_env(
            small_catalog, drift_enabled=True
        )
        cloud.templates["default"] = NodeTemplate(
            image_selector={"id": "img-pinned"}
        )
        cloud.publish_image(Image("img-pinned", L.ARCH_AMD64, created_at=1.0))
        schedule(state, prov_ctrl, clock, [PodSpec(name="p", requests={"cpu": 0.5})])
        machine = state.nodes[state.bindings["p"]].machine
        assert machine.image_id == "img-pinned"
        cloud.publish_image(Image("img-other", L.ARCH_AMD64, created_at=99.0))
        assert not cloud.is_machine_drifted(machine)
        clock.advance(10)
        assert deprov.reconcile() is None


class TestRepackConvergence:
    def test_device_loop_matches_oracle_loop_savings(self, small_catalog):
        """The end-to-end repack (BASELINE config 4 at test scale): driving
        the full ladder to convergence with the device-screened loop must
        achieve >= 0.98x the savings of the oracle-driven loop, with every
        evicted pod rebound.  Nothing runs it at full scale (5k nodes)."""
        from repack_fleet import repack_to_convergence

        dev = repack_to_convergence(small_catalog, 80, "auto", False)
        orc = repack_to_convergence(small_catalog, 80, "oracle", True)
        assert dev["pending_end"] == 0 and orc["pending_end"] == 0
        assert orc["saved"] > 0
        assert dev["saved"] >= 0.98 * orc["saved"], (dev, orc)
        assert dev["nodes_end"] <= 1.1 * orc["nodes_end"]


class TestCapacityTypeSpreadConsolidation:
    def test_delete_refused_when_it_would_unbalance_ct_spread(self, small_catalog):
        """Consolidation what-ifs ride the scheduler, so a delete whose
        displaced pods cannot re-place without breaking their hard
        capacity-type spread must NOT execute; the identical fleet without
        the spread consolidates (control)."""
        from karpenter_tpu.models.pod import LabelSelector, TopologySpreadConstraint
        from karpenter_tpu.models.requirements import IN, Requirement

        def run(hard: bool):
            prov = Provisioner(
                name="default", consolidation_enabled=True,
                requirements=[Requirement(
                    L.CAPACITY_TYPE, IN,
                    [L.CAPACITY_TYPE_SPOT, L.CAPACITY_TYPE_ON_DEMAND])],
            )
            clock, state, cloud, prov_ctrl, term, deprov, _ = make_env(
                small_catalog, provisioner=prov)
            sel = LabelSelector.of({"app": "web"})
            when = "DoNotSchedule" if hard else "ScheduleAnyway"
            # a balanced 2-node fleet (1 spot + 1 on-demand), lightly used:
            # a delete is cost-attractive, but the hard spread makes it
            # push all web pods onto one capacity type (skew 4 > 1)
            schedule(state, prov_ctrl, clock, [
                PodSpec(name=f"web-{i}", labels={"app": "web"},
                        requests={"cpu": 0.25},
                        topology_spread=[TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, when, sel)],
                        owner_key="web")
                for i in range(4)
            ])
            cts = {state.node_of(f"web-{i}").capacity_type for i in range(4)}
            clock.advance(MIN_NODE_LIFETIME + 1)
            action = deprov.reconcile()
            return cts, action

        # DoNotSchedule: the balanced 2-ct fleet must NOT merge — the
        # what-if can only satisfy the spread by opening a replacement node
        # in the vacated capacity type, which erases the savings, so no
        # delete is economically proposable (plain-fleet consolidation is
        # covered by the tests above)
        cts, action = run(hard=True)
        assert cts == {L.CAPACITY_TYPE_SPOT, L.CAPACITY_TYPE_ON_DEMAND}
        assert action is None or action.kind != "delete", action
        # the soft variant places identically and is refused for the same
        # economic reason (the hardened what-if is feasible with the one
        # replacement node, so the relaxation ladder never drops it)
        cts2, action2 = run(hard=False)
        assert cts2 == {L.CAPACITY_TYPE_SPOT, L.CAPACITY_TYPE_ON_DEMAND}
        assert action2 is None or action2.kind != "delete", action2


class TestVolumePinnedConsolidation:
    def test_delete_refused_when_pod_is_volume_pinned_off_zone(self, small_catalog):
        """The what-if injects CURRENT volume pins before simulating a move
        (deprovisioning._solve_what_if), so a delete whose displaced pod
        could only land off the volume's zone must not execute; unbinding
        the claim (control) lets the same consolidation through."""
        from karpenter_tpu.models.volume import (
            PersistentVolume, PersistentVolumeClaim, StorageClass,
        )

        def run(bind_volume: bool):
            clock, state, cloud, prov_ctrl, term, deprov, _ = make_env(small_catalog)
            state.apply_storage(StorageClass(name="ebs"))
            state.apply_storage(PersistentVolumeClaim(
                name="data", storage_class="ebs"))
            if bind_volume:
                state.bind_volume("default", "data", PersistentVolume(
                    name="pv", zones=("zone-1b",)))
            # an anchor fleet in zone-1a with slack the displaced pod could
            # ride — but only if the volume allows leaving zone-1b
            schedule(state, prov_ctrl, clock, [
                PodSpec(name=f"web-{i}", requests={"cpu": 1.0},
                        node_selector={L.ZONE: "zone-1a"}, owner_key="web")
                for i in range(3)
            ])
            # control places in zone-1b via a SOFT preference: honored at
            # schedule time, relaxable in the what-if — so only the volume
            # pin (hard, persistent) blocks the move
            from karpenter_tpu.models.requirements import IN, Requirement
            db = PodSpec(name="db", requests={"cpu": 0.5},
                         volume_claims=["data"] if bind_volume else [],
                         preferred_affinity_terms=(
                             [] if bind_volume
                             else [[Requirement(L.ZONE, IN, ["zone-1b"])]]),
                         owner_key="db")
            schedule(state, prov_ctrl, clock, [db])
            db_node = state.node_of("db")
            assert db_node.zone == "zone-1b"
            clock.advance(MIN_NODE_LIFETIME + 1)
            action = deprov.reconcile()
            return db_node.name, action, state

        name, action, state = run(bind_volume=True)
        # the db node must survive: the pin forbids riding zone-1a slack
        assert name in state.nodes, action

        # control: no volume (zone preference only at schedule time via
        # selector-free re-placement) — the pod may move and the node goes
        name2, action2, state2 = run(bind_volume=False)
        # the pin-free fleet consolidates (a delete, or a replace merging
        # the nodes into one cheaper machine)
        assert action2 is not None and action2.mechanism == "consolidation"
        assert name2 in action2.nodes or name2 not in state2.nodes


class TestKubeletDensityConsolidation:
    def test_delete_refused_when_density_cap_blocks_merge(self, small_catalog):
        """A delete whose displaced pods would overflow the survivors'
        kubeletConfiguration pod-density cap must not execute: the what-if
        prices the specialized (maxPods-capped) catalog, so tiny pods that
        FIT by cpu/memory still can't merge past the density ceiling.  The
        same fleet without the override consolidates (control)."""
        from karpenter_tpu.models.provisioner import KubeletConfiguration

        def run(shrink_to):
            prov = Provisioner(
                name="default", consolidation_enabled=True,
                kubelet=KubeletConfiguration(max_pods=4),
            )
            clock, state, cloud, prov_ctrl, term, deprov, _ = make_env(
                small_catalog, provisioner=prov)
            # 8 tiny pods: with maxPods=4 they need two nodes even though
            # one node's cpu/memory could hold all of them
            schedule(state, prov_ctrl, clock, [
                PodSpec(name=f"p-{i}", requests={"cpu": 0.1}, owner_key="d")
                for i in range(8)
            ])
            assert len(state.nodes) == 2  # density forced the split
            if shrink_to is not None:
                # shrink each node to ``shrink_to`` pods
                per: dict = {}
                for name in sorted(state.bindings):
                    node = state.node_of(name).name
                    per[node] = per.get(node, 0) + 1
                    if per[node] > shrink_to:
                        state.delete_pod(name)
            clock.advance(MIN_NODE_LIFETIME + 1)
            action = deprov.reconcile()
            return action, state

        # full 4+4 fleet: every survivor is at its density cap — no merge
        action, state = run(shrink_to=None)
        assert action is None, action
        assert len(state.nodes) == 2

        # control: 2+2 after pod churn — a merge to exactly 4 sits AT the
        # cap and must go through
        action2, state2 = run(shrink_to=2)
        assert action2 is not None and action2.mechanism == "consolidation"
