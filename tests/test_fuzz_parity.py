"""Randomized differential fuzzing: TPU solver vs the CPU oracle.

The reference's hardening tier is ``make battletest`` — race-detector runs
with randomized spec order and injected delays (reference Makefile:69-76).
The analog for a numeric solver is *differential fuzzing*: seeded random
scenarios over the whole constraint surface (requests, selectors, spreads,
anti-affinity, taints/tolerations, weighted/limited provisioners, ICE'd
offerings, existing nodes), each gated on the same invariants the curated
parity suites use:

- identical scheduled/infeasible pod counts,
- new-node cost within the 1.02x parity budget,
- determinism: re-solving the same tensors yields identical packing.

Scenario axes are kept bucket-stable (pod counts < 512, the 20-type catalog)
so the persistent jit cache makes the sweep cheap after the first seed.
"""

import dataclasses
import os

import numpy as np
import pytest

from karpenter_tpu.models import labels as L
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
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.tpu import solve_tensors
from karpenter_tpu.solver.validate import validate_solution

PARITY = 1.02
#: random-adversarial-shape quality bounds.  The curated BASELINE configs
#: are held to PARITY (BENCHMARK.json's cost ceiling on the chip and the
#: tpu-solver suites here); random fuzz shapes
#: get a hard per-seed ceiling plus a tight MEAN gate (test_zz_fuzz_cost_mean)
#: so a systematic regression fails even when each seed stays under the
#: ceiling.
#: observed worst case 1.0157 (seed 28) over the 40-seed sweep: cross-group
#: tail interleaving — the oracle seats a 2-pod d0 tail and a 1-pod d4 tail
#: on SHARED nodes mid-interleave, where the group-at-a-time scan strands
#: each on its own right-sized node; both nodes pass the reseat screen
#: honestly (no absorption room anywhere, already the cheapest types), so
#: closing it needs a whole-batch re-solve — the structural FFD-interleave
#: edge the batched design trades for its 17x latency win.  History of
#: closed worsts: seed 14's 1.104 zone-tail type split (r4 per-zone suffix
#: projection — now BEATS the oracle), seed 23's 1.0203 limit-capped
#: purchase mix (drew a capacity-type spread when that axis landed, so the
#: whole batch now oracle-routes at exact parity; the pure limit-mix shape
#: remains covered by the other capped seeds under this ceiling)
FUZZ_PARITY = 1.02           # per-seed, plain scenarios — the parity budget
#: observed worst case 1.0 — every seed at or below oracle cost since the
#: generalized nearly-empty reseat (seed 5's 1.0334 hostname-anti residue
#: closed by the capped reseat at 1.0133, 1.0068 by the absorption-aware
#: zone seed, <=1.0 by the generalized reseat; seed 23's 1.0265
#: oracle-routes since the ct-spread axis)
FUZZ_PARITY_EXISTING = 1.02  # per-seed, adversarial existing-node scenarios
#: per-suite mean gate.  Observed means sit at 0.75-0.77 (the device is
#: usually far cheaper than sequential FFD); 0.90 leaves population-shift
#: headroom while still failing a systematic drift toward the per-seed
#: ceilings long before every seed individually trips — at 1.02 (== the
#: per-seed ceiling) this gate would be vacuous for plain/existing
FUZZ_MEAN = 0.90             # mean per suite
_RATIOS: dict = {}           # suite -> [per-pod cost ratios], gated at the end


def _gate_cost(seed, suite, oracle, tpu, ceiling):
    """Per-pod cost-ratio gate — comparable even when the two backends
    schedule different pod counts, so a cost regression cannot hide behind
    a count difference."""
    if oracle.new_node_cost <= 0:
        if tpu.n_scheduled <= oracle.n_scheduled:
            # oracle needed no new capacity for at least as many pods:
            # launching any node is a pure regression
            assert tpu.new_node_cost == 0, (
                f"seed {seed}: device launched {len(tpu.nodes)} unnecessary nodes"
            )
        return
    if tpu.n_scheduled == 0 or oracle.n_scheduled == 0:
        return
    ratio = (tpu.new_node_cost / tpu.n_scheduled) / (
        oracle.new_node_cost / oracle.n_scheduled
    )
    _RATIOS.setdefault(suite, []).append(ratio)
    assert ratio <= ceiling + 1e-9, (
        f"seed {seed}: per-pod cost ratio {ratio:.4f} "
        f"(tpu ${tpu.new_node_cost:.3f}/{tpu.n_scheduled} vs "
        f"oracle ${oracle.new_node_cost:.3f}/{oracle.n_scheduled})"
    )


#: widened by `make battletest` (KT_FUZZ_SEEDS=40)
SEEDS = range(int(os.environ.get("KT_FUZZ_SEEDS", "10")))


def random_scenario(seed: int, catalog):
    rng = np.random.default_rng(seed)
    zones = ["zone-1a", "zone-1b", "zone-1c"]

    # -- provisioners: 1-3, weighted; maybe a taint, maybe a cpu limit -----
    provs = []
    n_prov = int(rng.integers(1, 4))
    for i in range(n_prov):
        kw = {}
        if rng.random() < 0.3:
            kw["taints"] = [Taint(key="team", effect=L.EFFECT_NO_SCHEDULE, value="a")]
        if rng.random() < 0.3:
            kw["limits"] = {"cpu": float(rng.integers(16, 128))}
        if rng.random() < 0.4:
            ct = L.CAPACITY_TYPE_SPOT if rng.random() < 0.5 else L.CAPACITY_TYPE_ON_DEMAND
            kw["requirements"] = [Requirement(L.CAPACITY_TYPE, IN, [ct])]
        provs.append(Provisioner(name=f"prov{i}", weight=int(rng.integers(1, 11)), **kw).with_defaults())

    # -- pods: up to 8 deployment-like groups, constraint mix -------------
    pods = []
    n_dep = int(rng.integers(1, 9))
    for d in range(n_dep):
        n = int(rng.integers(3, 40))
        cpu = float(rng.choice([0.25, 0.5, 1.0, 2.0, 3.5]))
        mem = float(rng.choice([0.5, 1.0, 2.0, 6.0])) * GIB
        labels = {"app": f"d{d}"}
        sel = LabelSelector.of(labels)
        kw = {}
        r = rng.random()
        if r < 0.25:
            kw["topology_spread"] = [TopologySpreadConstraint(
                int(rng.integers(1, 4)), L.ZONE, "DoNotSchedule", sel)]
        elif r < 0.45:
            kw["affinity_terms"] = [PodAffinityTerm(sel, L.HOSTNAME, anti=True)]
        elif r < 0.55:
            kw["topology_spread"] = [TopologySpreadConstraint(
                int(rng.integers(1, 3)), L.HOSTNAME, "DoNotSchedule", sel)]
        elif r < 0.63:
            kw["affinity_terms"] = [PodAffinityTerm(sel, L.ZONE)]  # self zone paff
        elif r < 0.70 and d > 0:
            kw["affinity_terms"] = [PodAffinityTerm(
                LabelSelector.of({"app": f"d{int(rng.integers(0, d))}"}),
                L.ZONE if rng.random() < 0.5 else L.HOSTNAME)]
        if rng.random() < 0.25:
            kw["node_selector"] = {L.ZONE: str(rng.choice(zones))}
        if rng.random() < 0.2:
            kw["tolerations"] = [Toleration(key="team", operator="Equal", value="a",
                                            effect=L.EFFECT_NO_SCHEDULE)]
        for i in range(n):
            pods.append(PodSpec(name=f"d{d}-{i}", labels=dict(labels),
                                requests={"cpu": cpu, "memory": mem},
                                owner_key=f"d{d}", **kw))

    # -- ICE'd offerings ----------------------------------------------------
    unavailable = set()
    if rng.random() < 0.4:
        for _ in range(int(rng.integers(1, 6))):
            it = catalog[int(rng.integers(0, len(catalog)))]
            o = it.offerings[int(rng.integers(0, len(it.offerings)))]
            unavailable.add((it.name, o.zone, o.capacity_type))

    # -- volume topology pins (scheduling.md:378-433): some deployments
    # mount zonal storage — a bound PV (1 zone) or a WaitForFirstConsumer
    # class (2 zones).  Separate rng stream so pre-existing seeds keep their
    # exact scenarios (the observed-worst ceilings stay comparable).
    vrng = np.random.default_rng(seed + 55_000)
    for d in range(n_dep):
        if vrng.random() < 0.15:
            nz = 1 if vrng.random() < 0.6 else 2
            vz = sorted(vrng.choice(zones, size=nz, replace=False).tolist())
            req = Requirement(L.ZONE, IN, vz)
            for pod in pods:
                if pod.owner_key == f"d{d}":
                    pod.volume_zone_requirements = [req]

    # -- capacity-type spread (scheduling.md:303-346's third topologyKey):
    # some deployments spread replicas across spot/on-demand.  Separate rng
    # stream so pre-existing seeds keep their exact scenarios; layers on top
    # of whatever constraints the deployment already drew (the oracle's
    # ct path composes with zone rules and hostname caps).
    crng = np.random.default_rng(seed + 99_000)
    for d in range(n_dep):
        if crng.random() < 0.12:
            sel = LabelSelector.of({"app": f"d{d}"})
            for pod in pods:
                if pod.owner_key == f"d{d}":
                    pod.topology_spread = list(pod.topology_spread) + [
                        TopologySpreadConstraint(
                            1, L.CAPACITY_TYPE, "DoNotSchedule", sel)
                    ]

    return pods, provs, unavailable


def with_random_kubelet(seed: int, provs):
    """Layer kubeletConfiguration overrides onto ``provs``
    (karpenter.sh_provisioners.yaml:56-135): density caps (maxPods /
    podsPerCore) and reservation overrides both change solver-visible
    allocatable, so every tier must price them identically.  A separate
    scenario axis (like random_existing_nodes) rather than a mutation of
    random_scenario — the plain/existing suites' observed-worst ceilings
    stay comparable across rounds."""
    from karpenter_tpu.models.provisioner import KubeletConfiguration

    krng = np.random.default_rng(seed + 77_000)
    out = list(provs)
    for i, p in enumerate(out):
        if krng.random() < 0.35:
            kc = {}
            r = krng.random()
            if r < 0.4:
                kc["max_pods"] = int(krng.integers(8, 40))
            elif r < 0.7:
                kc["pods_per_core"] = int(krng.integers(1, 6))
            else:
                kc["kube_reserved"] = {"cpu": float(krng.choice([0.5, 1.0, 2.0]))}
            out[i] = dataclasses.replace(p, kubelet=KubeletConfiguration(**kc))
    return out


def random_existing_nodes(seed: int, catalog, provs):
    """Existing cluster state: partially-filled nodes of random types, some
    pre-placed filler pods consuming capacity."""
    from karpenter_tpu.solver.types import SimNode

    rng = np.random.default_rng(seed + 10_000)
    zones = ["zone-1a", "zone-1b", "zone-1c"]
    nodes = []
    for i in range(int(rng.integers(1, 8))):
        it = catalog[int(rng.integers(0, len(catalog)))]
        zone = str(rng.choice(zones))
        prov = provs[int(rng.integers(0, len(provs)))]
        node = SimNode(
            instance_type=it.name,
            provisioner=prov.name,
            zone=zone,
            capacity_type=L.CAPACITY_TYPE_ON_DEMAND,
            price=it.offerings[0].price,
            allocatable=dict(it.allocatable),
            labels={**it.labels(), L.ZONE: zone,
                    L.CAPACITY_TYPE: L.CAPACITY_TYPE_ON_DEMAND,
                    L.PROVISIONER_NAME: prov.name},
            existing=True,
        )
        node.labels[L.HOSTNAME] = node.name
        # fill 0-70% of cpu with filler pods (never past cpu OR pod-density
        # capacity)
        cpu_cap = node.allocatable.get("cpu", 0.0)
        pods_cap = node.allocatable.get(L.RESOURCE_PODS, 110.0)
        target = cpu_cap * float(rng.random() * 0.7)
        used, j, size = 0.0, 0, 0.25
        while used < target and used + size <= cpu_cap and j + 1 <= pods_cap:
            node.pods.append(PodSpec(name=f"filler-{i}-{j}",
                                     requests={"cpu": size},
                                     owner_key=f"filler-{i}"))
            used += size
            j += 1
        nodes.append(node)
    return nodes


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_existing_node_parity_and_no_overcommit(seed, small_catalog):
    """Solves against pre-populated cluster state: device vs oracle parity,
    plus the placed snapshots never overcommit any node and the CALLER's
    node objects are never mutated (the snapshot-isolation invariant)."""
    pods, provs, unavailable = random_scenario(seed, small_catalog)
    existing = random_existing_nodes(seed, small_catalog, provs)
    before = {n.name: len(n.pods) for n in existing}

    oracle = reference.solve(pods, provs, small_catalog,
                             existing_nodes=existing, unavailable=unavailable)
    # the product boundary (scheduling.Solve = BatchScheduler): includes the
    # relaxation ladder, OR-term ladder, and the residue-convergence waves
    # that close the in-step limit-cascade bound (seed 31)
    tpu = BatchScheduler(backend="tpu").solve(
        pods, provs, small_catalog,
        existing_nodes=existing, unavailable=unavailable,
    )

    # caller's nodes untouched by BOTH backends
    assert {n.name: len(n.pods) for n in existing} == before

    # the batched solver may legitimately schedule MORE than the sequential
    # oracle under capacity pressure, and on adversarial limit+spread mixes
    # its closed-form limit-funding estimate may fall a bounded few pods
    # short of the oracle's mixed-type packing (exact funding is a knapsack)
    floor = oracle.n_scheduled - max(2, oracle.n_scheduled // 4)
    assert tpu.n_scheduled >= floor, (
        f"seed {seed}: scheduled tpu={tpu.n_scheduled} oracle={oracle.n_scheduled}"
    )
    errs = validate_solution(pods, provs, tpu, small_catalog,
                             unavailable=unavailable)
    assert not errs, f"seed {seed}: invalid solution: {errs[:4]}"
    _gate_cost(seed, "existing", oracle, tpu, FUZZ_PARITY_EXISTING)

    # no node (existing snapshot or new) is overcommitted — used() includes
    # the per-node pod-density (RESOURCE_PODS) term
    for res in (oracle, tpu):
        for node in list(res.existing_nodes) + list(res.nodes):
            for k, v in node.used().items():
                assert v <= node.allocatable.get(k, 0.0) + 1e-6, (
                    f"seed {seed}: {node.name} overcommitted on {k}: "
                    f"{v} > {node.allocatable.get(k)}"
                )


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_cost_and_feasibility_parity(seed, small_catalog):
    pods, provs, unavailable = random_scenario(seed, small_catalog)
    oracle = reference.solve(pods, provs, small_catalog, unavailable=unavailable)
    # product boundary (see the existing-node test's comment)
    tpu = BatchScheduler(backend="tpu").solve(
        pods, provs, small_catalog, unavailable=unavailable
    )

    floor = oracle.n_scheduled - max(2, oracle.n_scheduled // 10)
    assert tpu.n_scheduled >= floor, (
        f"seed {seed}: scheduled tpu={tpu.n_scheduled} oracle={oracle.n_scheduled} "
        f"(tpu infeasible={len(tpu.infeasible)}, oracle={len(oracle.infeasible)})"
    )
    errs = validate_solution(pods, provs, tpu, small_catalog,
                             unavailable=unavailable)
    assert not errs, f"seed {seed}: invalid solution: {errs[:4]}"
    _gate_cost(seed, "plain", oracle, tpu, FUZZ_PARITY)


#: kubeletConfiguration fuzz: per-seed ceiling for scenarios whose
#: provisioners carry density caps / reservation overrides.  40-seed sweep:
#: mean 0.740, observed worst 1.0157 (seed 28) with seed 20 at 1.0105 —
#: inside the same 1.02 parity budget as the plain suites.  History:
#: seed 20 was 1.1151 (zone-affinity seed chasing the earliest open slot
#: into a zone needing 4 dedicated nodes; absorption-aware seed -> 1.0555),
#: then 1.0105 (the generalized nearly-empty reseat re-solves the
#: band-top orphan onto another zone's slack and downsizes its node);
#: seed 3's 1.0500 double-paid-reservation shape drew a ct spread when
#: that axis landed and now oracle-routes at exact parity.
FUZZ_PARITY_KUBELET = 1.02


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_kubelet_overrides_parity(seed, small_catalog):
    """random_scenario with per-provisioner kubeletConfiguration layered on
    (karpenter.sh_provisioners.yaml:56-135): maxPods/podsPerCore density
    caps and kube-reserved overrides change solver-visible allocatable per
    provisioner, so the device's specialized candidate rows must price them
    the way the oracle's specialized instance types do."""
    pods, provs, unavailable = random_scenario(seed, small_catalog)
    provs = with_random_kubelet(seed, provs)
    if all(p.kubelet is None for p in provs):
        pytest.skip("no kubelet override drawn for this seed")
    oracle = reference.solve(pods, provs, small_catalog, unavailable=unavailable)
    tpu = BatchScheduler(backend="tpu").solve(
        pods, provs, small_catalog, unavailable=unavailable
    )
    floor = oracle.n_scheduled - max(2, oracle.n_scheduled // 10)
    assert tpu.n_scheduled >= floor, (
        f"seed {seed}: scheduled tpu={tpu.n_scheduled} oracle={oracle.n_scheduled} "
        f"(tpu infeasible={len(tpu.infeasible)}, oracle={len(oracle.infeasible)})"
    )
    errs = validate_solution(pods, provs, tpu, small_catalog,
                             unavailable=unavailable)
    assert not errs, f"seed {seed}: invalid solution: {errs[:4]}"
    # Independent density check — validate_solution's pod-density row reads
    # the node's SELF-reported allocatable, so a solver that ignored maxPods
    # (and built default-density nodes) would sail through it while packing
    # 30 pods onto an 11-pod node.  Re-derive the cap from the raw catalog
    # + the provisioner's kubeletConfiguration (the instancetype.go:326-340
    # formula) and check the actual per-node pod counts in every tier.
    from karpenter_tpu.models.instancetype import kubelet_pod_density

    by_prov = {p.name: p for p in provs}
    by_type = {it.name: it for it in small_catalog}
    for res in (oracle, tpu):
        for node in res.nodes:
            kc = by_prov[node.provisioner].kubelet
            if kc is None or not (kc.max_pods or kc.pods_per_core):
                continue
            it = by_type[node.instance_type]
            cap = kubelet_pod_density(
                it.capacity.get(L.RESOURCE_PODS, 110.0),
                it.capacity.get("cpu", 0.0), kc)
            assert len(node.pods) <= cap + 1e-9, (
                f"seed {seed}: {node.name} ({node.instance_type}) packs "
                f"{len(node.pods)} pods over kubelet density cap {cap}"
            )
    _gate_cost(seed, "kubelet", oracle, tpu, FUZZ_PARITY_KUBELET)


def test_zz_fuzz_cost_mean():
    """Aggregate cost-parity gate: individual adversarial seeds get bounded
    per-seed ceilings, but the MEAN per suite must stay inside the tight
    band — a systematic cost regression fails here even if each seed ducks
    under its ceiling.  (zz-named to run after the parametrized sweeps in
    file order; per-suite so -k selections can't mix bands.)"""
    gated = False
    for suite, ratios in _RATIOS.items():
        if len(ratios) < 5:
            continue
        gated = True
        mean = sum(ratios) / len(ratios)
        assert mean <= FUZZ_MEAN + 1e-9, (
            f"{suite}: mean per-pod cost ratio {mean:.4f} over "
            f"{len(ratios)} seeds (max {max(ratios):.4f})"
        )
    if not gated:
        pytest.skip("not enough ratio samples in this selection")


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_native_parity(seed, small_catalog):
    """Native C++ tier vs oracle over the same scenario sweep.  Positive
    pod-affinity scenarios are skipped — the scheduler's has_topology gate
    routes those to the device/oracle, never to the native tier."""
    from karpenter_tpu.solver import native

    if not native.available():
        pytest.skip("native lib unavailable")
    pods, provs, unavailable = random_scenario(seed, small_catalog)
    st = tensorize(pods, provs, small_catalog, unavailable=unavailable)
    if native.has_topology(st):
        pytest.skip("positive pod-affinity routes away from the native tier")
    oracle = reference.solve(pods, provs, small_catalog, unavailable=unavailable)
    got = native.solve_tensors_native(st)

    # the size tie-break can legitimately schedule MORE than the oracle
    # under limit pressure (a larger type spends the same headroom on more
    # pods — seed 27); never fewer
    assert got.n_scheduled >= oracle.n_scheduled, (
        f"seed {seed}: scheduled native={got.n_scheduled} oracle={oracle.n_scheduled} "
        f"(native infeasible={len(got.infeasible)}, oracle={len(oracle.infeasible)})"
    )
    if oracle.new_node_cost > 0 and got.n_scheduled > 0:
        ratio = (got.new_node_cost / got.n_scheduled) / (
            oracle.new_node_cost / oracle.n_scheduled
        )
        assert ratio <= PARITY + 1e-9, (
            f"seed {seed}: per-pod cost ratio {ratio:.4f}\n"
            f"native: {got.summary()}\noracle: {oracle.summary()}"
        )
    # over-scheduling must still be VALID: the >= floor above would let an
    # overcommit/limit-violating regression through without this
    errs = validate_solution(pods, provs, got, small_catalog,
                             unavailable=unavailable)
    assert not errs, f"seed {seed}: invalid native solution: {errs[:4]}"


def test_node_count_parity_on_spread_mix(small_catalog):
    """Cost-neutral size tie-break: at exactly equal $/pod the solver
    prefers fewer, larger nodes, so a config-2-shaped workload (mixed
    sizes, zone spread) must not buy a multiple of FFD's node count at
    equal cost — node count is real operational load (kubelet/API traffic,
    image pulls, ENI/IP consumption, spot exposure) even when the $ match.
    Round 2 shipped 1.68x nodes here; the gate holds the fix."""
    from karpenter_tpu.models.instancetype import GIB

    pods = []
    for d in range(8):
        sel = LabelSelector.of({"app": f"d{d}"})
        for i in range(250):
            pods.append(PodSpec(
                name=f"d{d}-{i}", labels={"app": f"d{d}"},
                requests={"cpu": 0.25 * (1 + d % 8), "memory": (0.5 + d % 6) * GIB},
                topology_spread=[TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"d{d}",
            ))
    provs = [Provisioner(name="default").with_defaults()]
    oracle = reference.solve(pods, provs, small_catalog)
    st = tensorize(pods, provs, small_catalog)
    tpu = solve_tensors(st).result
    assert not tpu.infeasible and not oracle.infeasible
    ratio = tpu.new_node_cost / oracle.new_node_cost
    assert ratio <= PARITY + 1e-9, f"cost ratio {ratio:.4f}"
    assert len(tpu.nodes) <= 1.15 * len(oracle.nodes), (
        f"node count {len(tpu.nodes)} vs FFD {len(oracle.nodes)}"
    )


def test_limit_cascade_five_provisioners(small_catalog):
    """A group cascading through FIVE limit-capped provisioners places
    exactly what the oracle places: the in-step creation is bounded at 4
    candidate picks, so the depth beyond that must come from the scheduler's
    host-side residue-convergence waves (solver/scheduler.py
    MAX_RESIDUE_WAVES; reference: karpenter.sh_provisioners.yaml:160-173
    limits + :305-314 weights)."""
    from karpenter_tpu.solver.scheduler import BatchScheduler

    provs = [
        Provisioner(
            name=f"capped{i}", weight=10 - i,
            limits={"cpu": 8.0},  # funds exactly one c5.2xlarge each
            requirements=[Requirement(L.INSTANCE_TYPE, IN, ["c5.2xlarge"])],
        ).with_defaults()
        for i in range(5)
    ]
    pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="d")
            for i in range(38)]  # needs 5 nodes at ~7.8 allocatable cpu each

    oracle = reference.solve(pods, provs, small_catalog)
    got = BatchScheduler(backend="tpu").solve(pods, provs, small_catalog)
    assert got.n_scheduled == oracle.n_scheduled, (
        f"scheduled tpu={got.n_scheduled} oracle={oracle.n_scheduled} "
        f"(tpu infeasible={len(got.infeasible)})"
    )
    assert len(got.nodes) == len(oracle.nodes) == 5
    assert {n.provisioner for n in got.nodes} == {f"capped{i}" for i in range(5)}
    assert abs(got.new_node_cost - oracle.new_node_cost) < 1e-6
    errs = validate_solution(pods, provs, got, small_catalog)
    assert not errs, f"invalid cascade solution: {errs[:4]}"


def test_fuzz_determinism(small_catalog):
    """Same tensors solved twice must produce the identical packing."""
    pods, provs, unavailable = random_scenario(3, small_catalog)
    st = tensorize(pods, provs, small_catalog, unavailable=unavailable)
    a = solve_tensors(st)
    b = solve_tensors(st)

    def canonical(res):
        # node names come from a global counter; compare packing shape, not ids
        idx = {n.name: i for i, n in enumerate(res.nodes)}
        return (
            {p: idx[n] for p, n in res.assignments.items()},
            [(n.instance_type, n.zone, n.capacity_type) for n in res.nodes],
        )

    assert canonical(a.result) == canonical(b.result)
    assert abs(a.result.new_node_cost - b.result.new_node_cost) < 1e-9
