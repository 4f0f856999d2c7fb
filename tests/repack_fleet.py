"""The BASELINE config-4 repack at test scale: an under-utilized fleet and
the loop that drives the whole deprovisioning ladder over it until nothing
fires.  Helper of ``test_deprovisioning.py::TestRepackConvergence``."""

import numpy as np

from karpenter_tpu.cloud.fake import FakeCloudProvider
from karpenter_tpu.controllers import deprovisioning as deprov_mod
from karpenter_tpu.controllers.deprovisioning import DeprovisioningController
from karpenter_tpu.controllers.provisioning import ProvisioningController
from karpenter_tpu.controllers.state import ClusterState
from karpenter_tpu.controllers.termination import TerminationController
from karpenter_tpu.events import Recorder
from karpenter_tpu.metrics import Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.machine import Machine
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.types import SimNode
from karpenter_tpu.utils.clock import FakeClock


def repack_fleet(catalog, n_nodes, rng):
    """The config-4 fleet: ~30%-utilized nodes of one 16-cpu type."""
    it = next(t for t in catalog if t.allocatable.get("cpu", 0) >= 15)
    specs = []
    for i in range(n_nodes):
        zone = f"zone-1{'abc'[i % 3]}"
        pods = [
            PodSpec(
                name=f"n{i}-p{k}",
                requests={"cpu": float(rng.uniform(0.25, 1.5)),
                          "memory": float(rng.uniform(0.5, 2.0)) * GIB},
                owner_key=f"n{i}",
            )
            for k in range(int(rng.integers(2, 6)))
        ]
        node = SimNode(
            instance_type=it.name, provisioner="default", zone=zone,
            capacity_type="on-demand", price=it.offerings[0].price,
            allocatable=dict(it.allocatable),
            labels={**it.labels(), L.ZONE: zone,
                    L.CAPACITY_TYPE: "on-demand",
                    L.PROVISIONER_NAME: "default"},
            existing=True, name=f"bench-n{i}",
        )
        node.labels[L.HOSTNAME] = node.name
        specs.append((node, pods))
    return specs


def repack_env(catalog, n_nodes, backend):
    """Controllers + the fleet loaded into state, clock already advanced
    past the minimum node lifetime.  Returns (clock, state, deprov, term,
    prov_ctrl)."""
    rng = np.random.default_rng(42)
    clock = FakeClock()
    state = ClusterState(clock=clock)
    cloud = FakeCloudProvider(catalog, clock=clock)
    reg = Registry()
    rec = Recorder()
    sched = BatchScheduler(backend=backend, registry=reg)
    # deterministic tiering: no background XLA compiles — the what-if
    # confirms ride the cold native tier (the cold-operator path)
    sched.stop_warms()
    prov_ctrl = ProvisioningController(
        state, cloud, scheduler=sched, recorder=rec, registry=reg, clock=clock,
    )
    term = TerminationController(state, cloud, recorder=rec, registry=reg,
                                 clock=clock)
    deprov = DeprovisioningController(
        state, cloud, term, provisioning=prov_ctrl, scheduler=sched,
        recorder=rec, registry=reg, clock=clock,
    )
    state.apply_provisioner(
        Provisioner(name="default", consolidation_enabled=True).with_defaults()
    )
    for i, (node, pods) in enumerate(repack_fleet(catalog, n_nodes, rng)):
        for p in pods:
            state.add_pod(p)
        node.pods = list(pods)
        ns = state.add_node(node, machine=Machine(name=f"m{i}",
                                                  provider_id=f"i-r{i:08d}"))
        ns.initialized = True
    clock.advance(deprov_mod.MIN_NODE_LIFETIME + 1)
    return clock, state, deprov, term, prov_ctrl


def repack_to_convergence(catalog, n_nodes, backend, disable_screen,
                          max_ticks=800):
    """Drive the FULL deprovisioning ladder (propose -> 15 s TTL revalidate ->
    execute -> drain -> rebind) on the fleet until no action fires.  Returns
    the savings achieved and what is left: the product metric BASELINE
    config 4 names (min-cost repack), not just the deletability screen."""
    clock, state, deprov, term, prov_ctrl = repack_env(
        catalog, n_nodes, backend)
    cost0 = sum(ns.node.price for ns in state.nodes.values())
    saved_screen = (deprov_mod.SCREEN_THRESHOLD, deprov_mod.SUBSET_SCREEN_MIN)
    if disable_screen:
        # the pure-CPU baseline: sequential prefix binary search + singles,
        # no device screen (the reference's own heuristic shape)
        deprov_mod.SCREEN_THRESHOLD = 10**9
        deprov_mod.SUBSET_SCREEN_MIN = 10**9
    actions = idle_ticks = ticks = 0
    try:
        while idle_ticks < 12 and ticks < max_ticks:
            act = deprov.reconcile()
            term.reconcile()
            prov_ctrl.reconcile()
            clock.advance(5.0)
            ticks += 1
            if act is not None:
                actions += 1
                idle_ticks = 0
            else:
                idle_ticks += 1
    finally:
        deprov_mod.SCREEN_THRESHOLD, deprov_mod.SUBSET_SCREEN_MIN = saved_screen
    cost1 = sum(ns.node.price for ns in state.nodes.values())
    return {
        "saved": round(cost0 - cost1, 2),
        "nodes_end": len(state.nodes),
        "actions": actions,
        "ticks": ticks,
        "pending_end": len(state.pending_pods()),
    }
