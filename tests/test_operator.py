"""Operator runtime: wiring, leadership gating, HTTP endpoints, settings."""

import urllib.request

import pytest

from karpenter_tpu.cloud.fake import FakeCloudProvider
from karpenter_tpu.metrics import Registry
from karpenter_tpu.models.machine import Machine
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement, Requirements
from karpenter_tpu.operator import InMemoryLeaseStore, LeaderElector, Operator
from karpenter_tpu.settings import SettingsStore
from karpenter_tpu.utils.clock import FakeClock


@pytest.fixture
def op(small_catalog):
    clock = FakeClock()
    cloud = FakeCloudProvider(small_catalog, clock=clock)
    op = Operator(cloud, clock=clock, scheduler_backend="oracle", registry=Registry())
    op.state.apply_provisioner(Provisioner(name="default", consolidation_enabled=True))
    return op


class TestOperator:
    def test_scale_up_via_ticks(self, op):
        for i in range(20):
            op.state.add_pod(PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d"))
        for _ in range(3):
            op.tick()
            op.clock.advance(1.5)
        assert len(op.state.pending_pods()) == 0
        assert len(op.state.nodes) >= 1

    def test_leadership_gates_reconciles(self, small_catalog):
        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        op = Operator(cloud, clock=clock, scheduler_backend="oracle", registry=Registry())
        op.elector = LeaderElector(elect=lambda: False)
        op.state.apply_provisioner(Provisioner(name="default"))
        op.state.add_pod(PodSpec(name="p", requests={"cpu": 0.5}))
        for _ in range(3):
            op.tick()
            clock.advance(2.0)
        assert len(op.state.nodes) == 0  # never elected -> no reconciles

    def test_hydration_on_election_adopts_orphans(self, small_catalog):
        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        # pre-existing instance from a previous leader
        cloud.create(Machine(
            provisioner="default",
            requirements=Requirements([Requirement(L.INSTANCE_TYPE, IN, ["m5.large"])]),
        ))
        op = Operator(cloud, clock=clock, scheduler_backend="oracle", registry=Registry())
        op.state.apply_provisioner(Provisioner(name="default"))
        op.tick()  # elects + hydrates
        assert len(op.state.nodes) == 1  # adopted by link controller

    def test_restart_resumes_from_cloud_state(self, small_catalog):
        """SURVEY §5 checkpoint/resume posture end to end: the controller is
        stateless — after a crash, a fresh operator re-adopts the previous
        leader's instances via the link controller and re-binds the durable
        pod objects onto them, launching NOTHING new."""
        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)

        def durable_objects(op):
            op.state.apply_provisioner(
                Provisioner(name="default", consolidation_enabled=True)
            )
            for i in range(6):
                op.state.add_pod(
                    PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="d")
                )

        op1 = Operator(cloud, clock=clock, scheduler_backend="oracle", registry=Registry())
        durable_objects(op1)
        for _ in range(3):
            op1.tick()
            clock.advance(1.5)
        assert not op1.state.pending_pods()
        n_nodes = len(op1.state.nodes)
        launches_before = len(cloud.create_calls)
        op1.shutdown()

        # crash: in-memory state lost; cloud instances + API objects survive
        op2 = Operator(cloud, clock=clock, scheduler_backend="oracle", registry=Registry())
        durable_objects(op2)
        for _ in range(3):
            op2.tick()
            clock.advance(1.5)
        assert len(op2.state.nodes) == n_nodes          # re-adopted, not re-built
        assert len(cloud.create_calls) == launches_before  # zero new launches
        assert not op2.state.pending_pods()             # pods re-bound
        live = [i for i in cloud.instances.values() if not i.terminated]
        assert len(live) == n_nodes                     # nothing leaked or reaped

    def test_settings_hot_reload_rewires_batch_window(self, op):
        op.settings.update(batch_idle_duration=0.1, batch_max_duration=5.0)
        assert op.provisioning.window.idle == 0.1
        op.settings.update(drift_enabled=True)
        assert op.deprovisioning.drift_enabled is True
        op.settings.update(deprovisioning_ttl=30.0)
        assert op.deprovisioning.deprovisioning_ttl == 30.0
        op.settings.update(isolated_vpc=True)
        assert op.pricing.isolated_vpc is True
        with pytest.raises(ValueError):
            op.settings.update(deprovisioning_ttl=-1.0)

    def test_interruption_gated_on_queue_name(self, op):
        """Interruption reconciles only when a queue name is configured."""
        from karpenter_tpu.controllers.interruption import (
            SPOT_INTERRUPTION,
            InterruptionMessage,
        )

        op.state.add_pod(PodSpec(name="p", requests={"cpu": 0.5}))
        for _ in range(3):
            op.tick()
            op.clock.advance(1.5)
        node = op.state.bindings["p"]
        pid = op.state.nodes[node].machine.provider_id
        op.queue.send(InterruptionMessage(SPOT_INTERRUPTION, pid, op.clock.now()))
        op.tick()
        assert node in op.state.nodes           # no queue name -> ignored
        assert len(op.queue) == 1               # message not consumed
        op.settings.update(interruption_queue_name="q")
        op.tick()
        assert node not in op.state.nodes       # drained + deleted

    def test_http_metrics_and_healthz(self, small_catalog):
        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        op = Operator(cloud, clock=clock, scheduler_backend="oracle",
                      registry=Registry(), metrics_port=18765)
        port = op.start_http()
        try:
            op.state.apply_provisioner(Provisioner(name="default"))
            op.state.add_pod(PodSpec(name="p", requests={"cpu": 0.5}))
            op.tick(); clock.advance(1.5); op.tick()
            body = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
            assert "karpenter_nodes_created_total" in body
            health = urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz")
            assert health.status == 200
            # the observability surface rides the same server (ISSUE 3):
            # the provisioning pass above cut a trace with the window/
            # dispatch spans, and /statusz reports the flight-recorder ring
            import json as _json

            tz = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tracez").read())
            assert tz["count"] >= 1
            names = {c["name"] for t in tz["traces"]
                     for c in t.get("spans", ())}
            assert {"window", "dispatch"} <= names
            st = _json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz").read())
            assert st["flight_recorder"]["ring"] == tz["count"]
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
        finally:
            op.shutdown()


class TestLeaderElection:
    """Lease-based leader election (settings.md:23 LEADER_ELECT): two
    operator replicas contend on a shared lease store; only the holder
    reconciles; the standby takes over when the lease expires."""

    def _pair(self, small_catalog):
        clock = FakeClock()
        store = InMemoryLeaseStore()
        cloud = FakeCloudProvider(small_catalog, clock=clock)

        def mk(ident):
            op = Operator(cloud, clock=clock, scheduler_backend="oracle",
                          registry=Registry(), lease_store=store, identity=ident)
            op.state.apply_provisioner(Provisioner(name="default"))
            return op

        return clock, store, cloud, mk("op-1"), mk("op-2")

    def test_holder_renews_and_standby_never_steals(self, small_catalog):
        clock, store, cloud, op1, op2 = self._pair(small_catalog)
        op1.tick()
        assert op1.elector.elected
        for _ in range(10):
            clock.advance(5.0)  # < TTL between renewals
            op1.tick()
            op2.tick()
            assert op1.elector.elected
            assert not op2.elector.elected
        lease = store.get("karpenter-tpu-leader")
        assert lease.holder == "op-1"

    def test_standby_does_not_reconcile(self, small_catalog):
        clock, store, cloud, op1, op2 = self._pair(small_catalog)
        op1.tick()
        op2.state.add_pod(PodSpec(name="p", requests={"cpu": 0.5}))
        for _ in range(3):
            op2.tick()
            clock.advance(1.5)
            op1.tick()  # keep the lease renewed
        # the standby enqueued nothing and launched nothing
        assert not cloud.create_calls
        assert "p" not in op2.state.bindings

    def test_failover_mid_reconcile_resumes_within_ttl(self, small_catalog):
        """Kill the leader mid-reconcile: the standby acquires on lease
        expiry, hydration re-runs (election-gated), and it resumes from
        cloud state — adopting the dead leader's instances, launching
        nothing new, and finishing the in-flight work exactly once."""
        clock, store, cloud, op1, op2 = self._pair(small_catalog)

        def durable(op):
            for i in range(4):
                op.state.add_pod(PodSpec(name=f"p{i}", requests={"cpu": 1.0},
                                         owner_key="d"))

        durable(op1)
        durable(op2)
        op1.tick()
        clock.advance(1.5)
        op1.tick()  # batch window fired: nodes launched
        assert cloud.create_calls
        launches = len(cloud.create_calls)
        n_nodes = len(op1.state.nodes)
        # op1 dies here (no shutdown — the lease is NOT released)

        # within the TTL the standby stays standby
        clock.advance(5.0)
        op2.tick()
        assert not op2.elector.elected

        # past the TTL it takes over and resumes from cloud state
        clock.advance(LeaderElector.DEFAULT_TTL + 1.0)
        for _ in range(3):
            op2.tick()
            clock.advance(1.5)
        assert op2.elector.elected
        assert len(op2.state.nodes) == n_nodes       # adopted, not re-launched
        assert len(cloud.create_calls) == launches   # no duplicated work
        assert not op2.state.pending_pods()          # pods re-bound

    def test_deposed_leader_steps_down(self, small_catalog):
        clock, store, cloud, op1, op2 = self._pair(small_catalog)
        op1.tick()
        assert op1.elector.elected
        # op1 stalls (GC pause / partition) past the TTL; op2 takes over
        clock.advance(LeaderElector.DEFAULT_TTL + 1.0)
        op2.tick()
        assert op2.elector.elected
        # the old leader wakes up and must step down, not split-brain
        op1.tick()
        assert not op1.elector.elected
        assert store.get("karpenter-tpu-leader").holder == "op-2"

    def test_clean_shutdown_hands_over_without_waiting_ttl(self, small_catalog):
        clock, store, cloud, op1, op2 = self._pair(small_catalog)
        op1.tick()
        assert op1.elector.elected
        op1.shutdown()  # resigns the lease
        op2.tick()      # same instant: no TTL wait
        assert op2.elector.elected


def test_forced_exit_on_a_hung_compile_is_nonzero():
    """ISSUE 21: when a background compile is still running after the
    grace, ``drain_warm_threads`` forces the exit with FORCED_EXIT_RC —
    never with the command's own 0."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, threading, time\n"
        "from karpenter_tpu.operator import drain_warm_threads\n"
        "threading.Thread(target=time.sleep, args=(60,),\n"
        "                 name='tpu-solver-warm').start()\n"
        "drain_warm_threads(grace_s=0.2)\n"
        "sys.exit(0)\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=repo, timeout=120,
                       capture_output=True, text=True)
    from karpenter_tpu.operator import FORCED_EXIT_RC

    assert FORCED_EXIT_RC != 0 and p.returncode == FORCED_EXIT_RC
    assert "forcing process exit" in p.stderr
