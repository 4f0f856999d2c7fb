#!/usr/bin/env python
"""All five BASELINE.json benchmark configs — TPU solver vs the in-repo CPU
FFD oracle (BASELINE.md "Targets for this repo").

Prints ONE JSON line PER config:

  {"config": N, "metric": ..., "value": <device ms>, "unit": "ms",
   "vs_baseline": <cpu_ms / device_ms>, "cost_ratio_vs_ffd": ..., ...}

``bench.py`` stays the single-line headline (config #2); this is the full
sweep the parity story rests on.  Run: ``python bench_all.py [--configs 1,3]``.
"""

import argparse
import json
import time

import numpy as np


def _ffd_and_tpu(pods, provs, catalog, label):
    """Shared harness: CPU oracle once, TPU solve (compile excluded), report."""
    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver import reference
    from karpenter_tpu.solver.tpu import solve_tensors

    t0 = time.perf_counter()
    oracle = reference.solve(pods, provs, catalog)
    cpu_ms = (time.perf_counter() - t0) * 1000.0

    # track_assignments=True is the PRODUCTION configuration (the scheduler
    # always materializes assignments, and per-node group tracking is what
    # lets hostname-capped solves coalesce — config 3 is 1900 nodes without
    # it, ~342 with).  Tracking work is host-side; solve_ms stays the fenced
    # device measurement either way.
    st = tensorize(pods, provs, catalog)
    out = solve_tensors(st, track_assignments=True, measure=True)
    tpu = out.result
    cost_ratio = (
        tpu.new_node_cost / oracle.new_node_cost if oracle.new_node_cost > 0 else 1.0
    )
    # which tier the auto policy serves this batch size from in steady state
    # (r4 weak #3: the table must be the SERVING tier's numbers) — small
    # batches are oracle-served (exact parity), larger ones device-served
    from karpenter_tpu.solver.scheduler import NATIVE_BATCH_LIMIT

    serving = "oracle" if len(pods) <= NATIVE_BATCH_LIMIT else "tpu"
    return {
        "metric": label,
        "value": round(out.solve_ms, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / max(out.solve_ms, 1e-9), 3),
        "cpu_ffd_ms": round(cpu_ms, 1),
        "compile_ms": round(out.compile_ms, 1),
        "cost_ratio_vs_ffd": round(cost_ratio, 4),
        "tpu_nodes": len(tpu.nodes),
        "ffd_nodes": len(oracle.nodes),
        "infeasible": len(tpu.infeasible),
        "infeasible_ffd": len(oracle.infeasible),
        "serving_tier": serving,
        "serving_nodes": len(oracle.nodes) if serving == "oracle" else len(tpu.nodes),
        "serving_cost_ratio": 1.0 if serving == "oracle" else round(cost_ratio, 4),
    }


def config1():
    """1k uniform-CPU pods, 1 Provisioner, 20 instance types."""
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.pod import PodSpec
    from karpenter_tpu.models.provisioner import Provisioner

    catalog = generate_catalog(full=False)
    pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="u")
            for i in range(1000)]
    provs = [Provisioner(name="default").with_defaults()]
    rec = _ffd_and_tpu(pods, provs, catalog, "c1_1k_uniform_20types")

    # cold-tier diagnostic: the native C++ FFD serves this shape only while
    # the device program compiles behind (steady state is device at 1k pods,
    # oracle below NATIVE_BATCH_LIMIT — see serving_tier)
    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver import native as native_mod

    if native_mod.available():
        st = tensorize(pods, provs, catalog)
        t0 = time.perf_counter()
        nres = native_mod.solve_tensors_native(st, existing_nodes=[], max_nodes=1000)
        rec["cold_native_ms"] = round((time.perf_counter() - t0) * 1000.0, 3)
        rec["cold_native_nodes"] = len(nres.nodes)
    return rec


def config2():
    """50k mixed CPU/mem pods, full catalog, 3-AZ spread (bench.py headline)."""
    from bench import build_scenario

    pods, provs, catalog = build_scenario()
    return _ffd_and_tpu(pods, provs, catalog, "c2_50k_mixed_full_catalog_3az")


def config3():
    """10k pods with pod anti-affinity + taints/tolerations (hostname spread)."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import (
        LabelSelector, PodAffinityTerm, PodSpec, Taint, Toleration,
    )
    from karpenter_tpu.models.provisioner import Provisioner

    catalog = generate_catalog(full=True)
    pods = []
    for s in range(100):
        sel = LabelSelector.of({"app": f"svc{s}"})
        tol = ([Toleration(key="dedicated", operator="Equal", value="svc",
                           effect=L.EFFECT_NO_SCHEDULE)] if s % 2 else [])
        for i in range(100):
            pods.append(PodSpec(
                name=f"svc{s}-{i}", labels={"app": f"svc{s}"},
                requests={"cpu": 0.5 + (s % 4) * 0.25, "memory": (1 + s % 3) * GIB},
                affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                tolerations=tol, owner_key=f"svc{s}",
            ))
    provs = [
        Provisioner(name="dedicated", weight=10,
                    taints=[Taint(key="dedicated", effect=L.EFFECT_NO_SCHEDULE,
                                  value="svc")]).with_defaults(),
        Provisioner(name="default", weight=5).with_defaults(),
    ]
    return _ffd_and_tpu(pods, provs, catalog, "c3_10k_antiaffinity_taints_hostname")


def _repack_fleet(catalog, n_nodes, rng):
    """The config-4 fleet: ~30%-utilized nodes of one 16-cpu type."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import PodSpec
    from karpenter_tpu.solver.types import SimNode

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



def _repack_env(catalog, n_nodes, backend, deprovisioning_ttl=None):
    """Shared control-plane wiring for the repack benchmarks: controllers +
    the ~30%-utilized fleet loaded into state, clock already advanced past
    the minimum node lifetime.  Returns (clock, state, deprov, term,
    prov_ctrl, reg)."""
    import numpy as _np

    from karpenter_tpu.cloud.fake import FakeCloudProvider
    from karpenter_tpu.controllers import deprovisioning as deprov_mod
    from karpenter_tpu.controllers.deprovisioning import DeprovisioningController
    from karpenter_tpu.controllers.provisioning import ProvisioningController
    from karpenter_tpu.controllers.state import ClusterState
    from karpenter_tpu.controllers.termination import TerminationController
    from karpenter_tpu.events import Recorder
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.models.machine import Machine
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.solver.scheduler import BatchScheduler
    from karpenter_tpu.utils.clock import FakeClock

    rng = _np.random.default_rng(42)
    clock = FakeClock()
    state = ClusterState(clock=clock)
    cloud = FakeCloudProvider(catalog, clock=clock)
    reg = Registry()
    rec = Recorder()
    sched = BatchScheduler(backend=backend, registry=reg)
    # deterministic tiering for the benchmark: no background XLA compiles —
    # the ~17k-pod what-if confirms ride the cold native tier (the realistic
    # cold-operator path; a long-lived operator would migrate them on-device
    # once the background compile lands).  Without this, compile-behind
    # spawns NE~5000-rung batch-solver compiles that eat the host's CPU for
    # the whole loop and the wall-clock measures XLA, not the control plane.
    sched.stop_warms()
    prov_ctrl = ProvisioningController(
        state, cloud, scheduler=sched, recorder=rec, registry=reg, clock=clock,
    )
    term = TerminationController(state, cloud, recorder=rec, registry=reg,
                                 clock=clock)
    kw = {}
    if deprovisioning_ttl is not None:
        kw["deprovisioning_ttl"] = deprovisioning_ttl
    deprov = DeprovisioningController(
        state, cloud, term, provisioning=prov_ctrl, scheduler=sched,
        recorder=rec, registry=reg, clock=clock, **kw,
    )
    state.apply_provisioner(
        Provisioner(name="default", consolidation_enabled=True).with_defaults()
    )
    for i, (node, pods) in enumerate(_repack_fleet(catalog, n_nodes, rng)):
        for p in pods:
            state.add_pod(p)
        node.pods = list(pods)
        ns = state.add_node(node, machine=Machine(name=f"m{i}",
                                                  provider_id=f"i-r{i:08d}"))
        ns.initialized = True
    clock.advance(deprov_mod.MIN_NODE_LIFETIME + 1)
    return clock, state, deprov, term, prov_ctrl, reg


def _repack_to_convergence(catalog, n_nodes, backend, disable_screen,
                           max_ticks=800):
    """Drive the FULL deprovisioning ladder (propose -> 15 s TTL revalidate ->
    execute -> drain -> rebind) on an under-utilized fleet until no action
    fires.  Returns achieved savings, actions, wall time, and per-reconcile
    latency — the product metric BASELINE config 4 names (min-cost repack),
    not just the deletability screen."""
    import time as _time

    from karpenter_tpu.controllers import deprovisioning as deprov_mod
    from karpenter_tpu.metrics import DEPROVISIONING_DURATION

    clock, state, deprov, term, prov_ctrl, reg = _repack_env(
        catalog, n_nodes, backend,
    )

    cost0 = sum(ns.node.price for ns in state.nodes.values())
    saved_screen = (deprov_mod.SCREEN_THRESHOLD, deprov_mod.SUBSET_SCREEN_MIN)
    if disable_screen:
        # the pure-CPU baseline: sequential prefix binary search + singles,
        # no device screen (the reference's own heuristic shape)
        deprov_mod.SCREEN_THRESHOLD = 10**9
        deprov_mod.SUBSET_SCREEN_MIN = 10**9
    t0 = _time.perf_counter()
    actions = 0
    action_nodes = []
    idle_ticks = 0
    ticks = 0
    other_s = 0.0  # termination + provisioning (drain/rebind) per tick
    try:
        while idle_ticks < 12 and ticks < max_ticks:
            act = deprov.reconcile()
            t1 = _time.perf_counter()
            term.reconcile()
            prov_ctrl.reconcile()
            other_s += _time.perf_counter() - t1
            clock.advance(5.0)
            ticks += 1
            if act is not None:
                actions += 1
                action_nodes.append(len(act.nodes))
                idle_ticks = 0
            else:
                idle_ticks += 1
    finally:
        deprov_mod.SCREEN_THRESHOLD, deprov_mod.SUBSET_SCREEN_MIN = saved_screen
    wall_s = _time.perf_counter() - t0
    cost1 = sum(ns.node.price for ns in state.nodes.values())
    hist = reg.histogram(DEPROVISIONING_DURATION)
    n_obs = sum(hist.totals.values())
    mean_ms = (sum(hist.sums.values()) / n_obs * 1000.0) if n_obs else 0.0
    phases = {k: round(v, 1) for k, v in
              sorted(deprov.phase_s.items(), key=lambda kv: -kv[1])}
    phases["drain_rebind"] = round(other_s, 1)
    return {
        "initial_cost": round(cost0, 2),
        "final_cost": round(cost1, 2),
        "saved": round(cost0 - cost1, 2),
        "nodes_start": n_nodes,
        "nodes_end": len(state.nodes),
        "actions": actions,
        "action_nodes": action_nodes[:40],
        "ticks": ticks,
        "pending_end": len(state.pending_pods()),
        "wall_s": round(wall_s, 1),
        "reconcile_mean_ms": round(mean_ms, 1),
        "phase_s": phases,
        "phase_calls": dict(deprov.phase_n),
    }


def _scratch_pack_ffd(catalog, n_nodes):
    """From-scratch FFD pack of the repack fleet's pods — the reference
    heuristic's answer when allowed to re-bin every pod freely onto fresh
    nodes.  NOT a lower bound (FFD is a heuristic): measured r4, the
    converged repack's final cost BEATS it ($272 vs $288 at 2k nodes) while
    keeping whole existing nodes."""
    import time as _time

    import numpy as _np

    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.solver import reference

    rng = _np.random.default_rng(42)
    pods = [p for _node, plist in _repack_fleet(catalog, n_nodes, rng)
            for p in plist]
    provs = [Provisioner(name="default", consolidation_enabled=True).with_defaults()]
    t0 = _time.perf_counter()
    res = reference.solve(pods, provs, catalog)
    return {
        "cost": round(res.new_node_cost, 2),
        "nodes": len(res.nodes),
        "infeasible": len(res.infeasible),
        "solve_s": round(_time.perf_counter() - t0, 1),
    }


def _one_reconcile_at(catalog, n_nodes):
    """One full consolidation evaluation (screen + subset confirm + propose)
    at ``n_nodes`` — the per-reconcile latency of the deprovisioning loop at
    fleet scale, without driving the fleet to convergence."""
    import time as _time

    # ttl=0: measure the evaluation, not the TTL wait
    clock, state, deprov, _term, _prov_ctrl, _reg = _repack_env(
        catalog, n_nodes, "auto", deprovisioning_ttl=0.0,
    )
    t0 = _time.perf_counter()
    action = deprov.reconcile()
    dt = _time.perf_counter() - t0
    # settle: drain the executed delete and rebind evicted pods, so the
    # second evaluation is a FULL pass (a pending pod would early-out on
    # the stabilization path and fake a ~0s reconcile)
    for _ in range(10):
        _term.reconcile()
        _prov_ctrl.reconcile()
        clock.advance(5.0)
        if not state.pending_pods():
            break
    # second evaluation: the screen kernels now hit the jit cache — the
    # steady-state reconcile cost a long-lived operator actually pays
    clock.advance(20.0)
    settled = not state.pending_pods()
    t1 = _time.perf_counter()
    deprov.reconcile()
    dt_warm = _time.perf_counter() - t1
    return {
        "n_nodes": n_nodes,
        "reconcile_s": round(dt, 1),
        # None when pods didn't drain: an unsettled fleet early-outs on the
        # stabilization path and would fake a ~0s steady-state number
        "reconcile_warm_s": round(dt_warm, 1) if settled else None,
        "proposed": action.kind if action is not None else None,
        "proposed_nodes": len(action.nodes) if action is not None else 0,
    }


def config4():
    """Multi-node consolidation screen: 5k under-utilized nodes."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import PodSpec
    from karpenter_tpu.solver.consolidation import screen_delete_candidates
    from karpenter_tpu.solver.types import SimNode

    catalog = generate_catalog(full=False)
    it = next(t for t in catalog if t.allocatable.get("cpu", 0) >= 15)
    rng = np.random.default_rng(42)
    nodes = []
    for i in range(5000):
        node = SimNode(
            instance_type=it.name, provisioner="default", zone=f"zone-1{'abc'[i % 3]}",
            capacity_type="on-demand", price=it.offerings[0].price,
            allocatable=dict(it.allocatable),
        )
        # ~30% utilization: under-utilized fleet, the consolidation target
        for k in range(int(rng.integers(2, 6))):
            node.pods.append(PodSpec(
                name=f"n{i}-p{k}",
                requests={"cpu": float(rng.uniform(0.25, 1.5)),
                          "memory": float(rng.uniform(0.5, 2.0)) * GIB},
            ))
        nodes.append(node)

    # CPU baseline: the same first-fit screen, sequentially per candidate
    resources = [L.RESOURCE_CPU, L.RESOURCE_MEMORY, L.RESOURCE_PODS]
    residual = np.zeros((len(nodes), 3), dtype=np.float64)
    for i, n in enumerate(nodes):
        rem = n.remaining()
        residual[i] = [max(0.0, rem.get(r, 0.0)) for r in resources]
    t0 = time.perf_counter()
    cpu_deletable = np.zeros(len(nodes), dtype=bool)
    for i, n in enumerate(nodes):
        res = residual.copy()
        res[i] = 0.0
        ok = True
        for p in sorted(n.pods, key=lambda p: -p.requests.get("cpu", 0)):
            row = np.array([p.requests.get(L.RESOURCE_CPU, 0.0),
                            p.requests.get(L.RESOURCE_MEMORY, 0.0), 1.0])
            fits = (res >= row - 1e-9).all(axis=1)
            j = int(np.argmax(fits))
            if not fits[j]:
                ok = False
                break
            res[j] -= row
        cpu_deletable[i] = ok
    cpu_ms = (time.perf_counter() - t0) * 1000.0

    pmax = max(8, max(len(n.pods) for n in nodes))
    out = screen_delete_candidates(nodes, pmax=pmax, measure=True)
    agree = float((out.deletable == cpu_deletable).mean())

    # ---- end-to-end min-cost REPACK (the BASELINE config-4 product metric):
    # run the deprovisioning ladder to convergence, device-screened loop vs
    # the oracle-driven pure-CPU loop, at KT_C4_REPACK_NODES (default 2k —
    # the largest scale where BOTH loops converge inside a bench deadline on
    # this 1-core host: the oracle's prefix binary search pays ~12
    # full-fleet re-solves per reconcile, and at 5k even the device loop's
    # per-reconcile host work — the O(cands x nodes) compat matrix — puts
    # convergence past the budget).  The 5k story is still covered: the
    # device screen above runs at 5k, repack_reconcile_5k measures one full
    # consolidation evaluation at 5k, and the from-scratch oracle pack
    # bounds the achievable $.
    # Partial results stream to stderr so a deadline kill keeps what landed.
    import os
    import sys

    n_repack = int(os.environ.get("KT_C4_REPACK_NODES", "2000"))
    n_oracle = min(int(os.environ.get("KT_C4_ORACLE_NODES", str(n_repack))),
                   n_repack)
    rec = {
        "metric": "c4_consolidation_screen_5k_nodes",
        "value": round(out.eval_ms, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / max(out.eval_ms, 1e-9), 3),
        "cpu_screen_ms": round(cpu_ms, 1),
        "compile_ms": round(out.compile_ms, 1),
        "deletable": int(out.deletable.sum()),
        "agreement_with_cpu": round(agree, 4),
    }
    if n_repack:
        dev = _repack_to_convergence(catalog, n_repack, "auto", False)
        print(f"# c4 repack device@{n_repack}: {json.dumps(dev)}",
              file=sys.stderr, flush=True)
        rec["repack_device"] = dev
        rec["repack_scratch_ffd"] = _scratch_pack_ffd(catalog, n_repack)
        orc = _repack_to_convergence(catalog, n_oracle, "oracle", True)
        print(f"# c4 repack oracle@{n_oracle}: {json.dumps(orc)}",
              file=sys.stderr, flush=True)
        rec["repack_oracle"] = orc
        if n_oracle != n_repack:
            # parity compares like with like: re-run the device loop at the
            # oracle's scale
            dev_cmp = _repack_to_convergence(catalog, n_oracle, "auto", False)
            rec["repack_device_at_oracle_scale"] = dev_cmp
        else:
            dev_cmp = dev
        if orc.get("saved"):
            rec["repack_savings_parity"] = round(
                dev_cmp["saved"] / orc["saved"], 4)
        rec["repack_speedup"] = round(
            orc["wall_s"] / max(dev_cmp["wall_s"], 1e-9), 2)
        rec["repack_reconcile_5k"] = _one_reconcile_at(catalog, 5000)
    return rec


def config5():
    """Spot+on-demand price-aware pack, 10 weighted Provisioners, 5k pods."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import PodSpec
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.models.requirements import IN, Requirement

    catalog = generate_catalog(full=True)
    provs = []
    for i in range(10):
        ct = L.CAPACITY_TYPE_SPOT if i % 2 else L.CAPACITY_TYPE_ON_DEMAND
        provs.append(Provisioner(
            name=f"prov-{i}", weight=10 - i,
            requirements=[Requirement(L.CAPACITY_TYPE, IN, [ct])],
        ).with_defaults())
    pods = [PodSpec(name=f"p{i}", requests={"cpu": 0.5 + (i % 5) * 0.5,
                                            "memory": (1 + i % 4) * GIB},
                    owner_key=f"d{i % 8}")
            for i in range(5000)]
    return _ffd_and_tpu(pods, provs, catalog, "c5_spot_od_10weighted_provs_5k")


def config6():
    """Interruption-controller throughput at 15k queued messages — the
    reference's own benchmark shape (interruption_benchmark_test.go runs
    100/1k/5k/15k SQS messages; no numbers published, so measured here)."""
    from karpenter_tpu.cloud.fake import FakeCloudProvider
    from karpenter_tpu.controllers.interruption import (
        SPOT_INTERRUPTION, STATE_CHANGE, InterruptionController,
        InterruptionMessage, MessageQueue,
    )
    from karpenter_tpu.controllers.state import ClusterState
    from karpenter_tpu.controllers.termination import TerminationController
    from karpenter_tpu.events import Recorder
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.machine import Machine
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.solver.types import SimNode
    from karpenter_tpu.utils.clock import FakeClock

    catalog = generate_catalog(full=False)
    it = catalog[0]
    rates = {}
    for n_msgs in (100, 1_000, 5_000, 15_000):
        clock = FakeClock()
        state = ClusterState(clock=clock)
        cloud = FakeCloudProvider(catalog, clock=clock)
        reg = Registry()
        term = TerminationController(state, cloud, recorder=Recorder(),
                                     registry=reg, clock=clock)
        state.apply_provisioner(Provisioner(name="default"))
        queue = MessageQueue()
        ic = InterruptionController(state, term, queue, recorder=Recorder(),
                                    registry=reg, clock=clock)
        # 2k-node cluster; messages target real + unknown instances (~50/50)
        for i in range(2000):
            node = SimNode(instance_type=it.name, provisioner="default",
                           zone="zone-1a", capacity_type="spot", price=0.1,
                           allocatable=dict(it.allocatable), name=f"n{i}")
            machine = Machine(name=f"m{i}", provider_id=f"i-{i:08d}")
            state.add_node(node, machine=machine)
        for i in range(n_msgs):
            kind = SPOT_INTERRUPTION if i % 2 else STATE_CHANGE
            iid = f"i-{i % 4000:08d}"  # half miss the cluster
            queue.send(InterruptionMessage(kind, iid, clock.now(),
                                           state="stopping"))
        t0 = time.perf_counter()
        handled = ic.reconcile()
        dt = time.perf_counter() - t0
        assert handled == n_msgs
        rates[n_msgs] = n_msgs / dt
    return {
        "metric": "c6_interruption_controller_msgs_per_sec",
        "value": round(rates[15_000], 1),
        "unit": "msgs/s",
        "vs_baseline": 1.0,  # reference publishes no numbers (BASELINE.md)
        "rate_100": round(rates[100], 1),
        "rate_1k": round(rates[1_000], 1),
        "rate_5k": round(rates[5_000], 1),
        "rate_15k": round(rates[15_000], 1),
    }


CONFIGS = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5,
           6: config6}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5,6",
                    help="comma-separated config numbers to run")
    args = ap.parse_args()
    picked = [int(x) for x in args.configs.split(",") if x.strip()]
    import os

    from bench import arm_watchdog, require_tpu

    arm_watchdog(float(os.environ.get("BENCH_DEADLINE_S", "3000")),
                 metric="bench_all_sweep")
    # no TPU -> non-zero exit before any config runs (bench.require_tpu);
    # every line names the device it was measured on
    device = require_tpu()
    for n in picked:
        # a config that raises ends the sweep with its traceback and a
        # non-zero exit: the lines already printed stand, nothing is
        # papered over with a value-less record
        print(json.dumps({"config": n, **CONFIGS[n](), **device}), flush=True)


if __name__ == "__main__":
    main()
