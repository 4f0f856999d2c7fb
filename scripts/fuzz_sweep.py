"""Per-seed fuzz cost-ratio sweep: the distribution behind the ceilings.

The pytest gates (tests/test_fuzz_parity.py) assert per-seed ceilings and a
mean band; this prints the actual per-seed ratios so a scoring change can be
judged on the whole distribution before touching the ceilings.

    python scripts/fuzz_sweep.py [plain,existing,kubelet] [n_seeds] [--cached]
    python scripts/fuzz_sweep.py --delta [n_seeds] [chain_len]
    python scripts/fuzz_sweep.py --delta-wire [n_seeds] [chain_len]
    python scripts/fuzz_sweep.py --relax [n_seeds]
    python scripts/fuzz_sweep.py --hier [n_seeds]
    python scripts/fuzz_sweep.py --gang [n_seeds]

``--cached`` re-solves every scenario a second time through ONE scheduler
instance, so the second pass runs the incremental tensorize cache
(identity tier) — the sweep then also asserts the cached solve schedules
the same pods at the same cost and prints the hit/miss totals.

``--delta`` runs warm-start parity chains instead (ISSUE 6): solve a
random scenario, then perturb it ``chain_len`` times with random
add / remove / ICE / node-reclaim deltas through
``BatchScheduler.solve_delta``, asserting at EVERY step that (a) the
incremental result passes the ground-truth validator and (b) its cost per
scheduled pod stays within the 1.02x parity ceiling of a from-scratch
re-solve of the same pod set.

``--relax`` (ISSUE 11) drives random scenarios through the convex-
relaxation refinement rung (solver/relax.py) directly: per seed, the scan
solves the scenario, ``relax.refine`` refines it, and the sweep asserts
(a) the shipped solution NEVER costs more than the scan's (the min-of-two
construction, proven under fuzz, not just claimed), (b) the ground-truth
validator passes on the shipped solution, and (c) the schedulable-pod set
is unchanged.  Prints the outcome histogram.

``--hier`` (ISSUE 16) fuzzes the hierarchical decomposition
(solver/hierarchy.py): per seed, (a) a block-disjoint scenario (distinct
zone pins + spread selectors per deployment) must ship flat's EXACT
placement (node-name-independent canonical compare), (b) the LPT
partition must never split a constraint-reachability component across
blocks — asserted structurally on random adversarial scenarios under
forced block pressure — and (c) on an overlapping scenario the repair
pass must leave no pod unseated that flat seats.

``--gang`` (ISSUE 20) fuzzes the all-or-nothing gang contract
(karpenter_tpu/gang/, docs/GANGS.md): per seed, random scenarios whose
deployments are randomly promoted to gangs (some deliberately doomed by
an unsatisfiable member, some submitted with an incomplete roster) solve
through the full scheduler and the sweep HARD-asserts (a) no gang is
ever partially placed — every gang's members are all in ``assignments``
or all in ``infeasible`` with the typed ``GangUnplaced`` reason, (b) the
shipped solution passes the ground-truth validator, and (c) the
gang-free singleton subset's per-pod cost stays within the plain fuzz
ceiling of the reference oracle (the gang path must not tax ungrouped
pods).

``--delta-wire`` (ISSUE 10) drives the same random churn chains through a
REAL gRPC client/server pair — ``DeltaSession`` against an in-process
sidecar — asserting per step that (a) the client's merged view is
byte-identical to the server's live session chain (the wire protocol is
lossless), (b) the validator passes on the merged view, and (c) the cost
ceiling holds.  Covers the serving protocol end to end: session
establishment, delta-shaped replies, guard-trip full fallbacks, reclaims
and ICE accumulation over the wire.

CPU-pinned and repo-rooted: it never needs the chip.
"""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests"))

from test_fuzz_parity import (
    random_scenario, with_random_kubelet, random_existing_nodes,
)
from karpenter_tpu.models.catalog import generate_catalog
from karpenter_tpu.solver import reference
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.validate import validate_solution

argv = [a for a in sys.argv[1:]
        if a not in ("--cached", "--delta", "--delta-wire", "--relax",
                     "--hier", "--gang")]
cached = "--cached" in sys.argv[1:]
delta = "--delta" in sys.argv[1:]
delta_wire = "--delta-wire" in sys.argv[1:]
relax_mode = "--relax" in sys.argv[1:]
hier_mode = "--hier" in sys.argv[1:]
gang_mode = "--gang" in sys.argv[1:]
catalog = generate_catalog(full=False)


#: per-step cost-parity ceiling for the delta chains.  Wider than the 1.02
#: bound of the served cells (BENCHMARK.json cost_ratio_max) on
#: purpose: the fuzz perturbs TINY clusters adversarially — a 1-pod removal
#: can strand half a node, which is a rounding error at 20k pods but several
#: percent of a 20-pod scenario's bill; the KT_DELTA_MAX_FRAC fallback
#: bounds the drift, it cannot repack below the threshold.
DELTA_FUZZ_COST_CEILING = 1.06


def _isolate_labels(pods, tag: str):
    """Rewrite the pods' app-label namespace (labels + their own spread /
    affinity selectors, consistently) so cross-scenario label collisions
    cannot occur: two generators reusing 'app: d0' would otherwise mix an
    anti-affine deployment with a label-only one — tripping the solver's
    documented one-sided anti-affinity handling (the incoming pod's own
    terms are enforced; a later label-only pod is not re-checked against
    seated pods' terms), which is a pre-existing carve-out, not a
    delta-solve property."""
    import dataclasses

    from karpenter_tpu.models.pod import LabelSelector

    def remap_sel(sel):
        return LabelSelector(
            match_labels=tuple((k, f"{tag}-{v}") for k, v in sel.match_labels),
            match_expressions=sel.match_expressions,
        )

    out = []
    for i, p in enumerate(pods):
        q = dataclasses.replace(
            p,
            name=f"{tag}-{i}",
            labels={k: f"{tag}-{v}" for k, v in p.labels.items()},
            topology_spread=[
                dataclasses.replace(t, label_selector=remap_sel(t.label_selector))
                for t in p.topology_spread
            ],
            affinity_terms=[
                dataclasses.replace(t, label_selector=remap_sel(t.label_selector))
                for t in p.affinity_terms
            ],
        )
        out.append(q)
    return out


def run_delta_chains(n_seeds: int, chain_len: int) -> int:
    """Warm-start parity chains; returns the number of failing seeds."""
    import random

    failures = 0
    for seed in range(n_seeds):
        rng = random.Random(10_000 + seed)
        pods, provs, unavailable = random_scenario(seed, catalog)
        sched = BatchScheduler(backend="tpu")
        cur = sched.solve(pods, provs, catalog, unavailable=unavailable)
        # drop never-schedulable pods from the tracked problem: the chain
        # has no PodSpec objects for prev-infeasible names (the delta
        # contract: callers re-offer what they want retried), so the
        # reference solve must not score them either
        if cur.infeasible:
            doomed0 = set(cur.infeasible)
            pods = [p for p in pods if p.name not in doomed0]
        cur_pods = list(pods)
        unavail = set(unavailable or ())
        problems = []
        modes = []
        extra_seed = 500 + seed
        for step in range(chain_len):
            kind = rng.choice(("add", "remove", "ice", "reclaim", "mixed"))
            added, removed, iced = [], [], []
            if kind in ("add", "mixed"):
                fresh = random_scenario(extra_seed, catalog)[0]
                extra_seed += 1
                take = fresh[: rng.randint(1, max(2, len(cur_pods) // 25))]
                added = _isolate_labels(take, f"d{seed}c{step}")
            if kind in ("remove", "mixed") and cur.assignments:
                k = rng.randint(1, max(1, len(cur_pods) // 25))
                removed = rng.sample(sorted(cur.assignments),
                                     min(k, len(cur.assignments)))
            if kind == "ice":
                it = rng.choice(list(catalog))
                off = rng.choice(it.offerings)
                iced = [(it.name, off.zone, off.capacity_type)]
                unavail.add(iced[0])
            if kind == "reclaim":
                names = [n.name for n in cur.nodes] or [
                    n.name for n in cur.existing_nodes]
                if names:
                    iced = [rng.choice(names)]
            out = sched.solve_delta(
                cur, added=added, removed=removed, iced=iced,
                provisioners=provs, instance_types=catalog,
                unavailable=unavail,
            )
            cur = out.result
            modes.append(out.mode)
            doomed = set(removed)
            cur_pods = [p for p in cur_pods if p.name not in doomed] + list(added)
            # (a) placement validity of the incremental state
            errs = validate_solution(cur_pods, provs, cur, catalog)
            if errs:
                problems.append(f"step {step} ({out.mode}): {errs[:2]}")
            # (b) cost parity vs a from-scratch re-solve
            full = BatchScheduler(backend="tpu").solve(
                cur_pods, provs, catalog,
                unavailable=unavail or None)
            if full.new_node_cost > 0 and full.n_scheduled and cur.n_scheduled:
                r = (cur.new_node_cost / cur.n_scheduled) / (
                    full.new_node_cost / full.n_scheduled)
                if r > DELTA_FUZZ_COST_CEILING + 1e-9:
                    problems.append(
                        f"step {step} ({out.mode}): cost ratio {r:.4f}")
            if cur.n_scheduled < full.n_scheduled - max(
                    2, full.n_scheduled // 10):
                problems.append(
                    f"step {step} ({out.mode}): scheduled "
                    f"{cur.n_scheduled} < full {full.n_scheduled}")
        tag = "OK " if not problems else "FAIL"
        print(f"delta seed {seed}: {tag} modes={modes}"
              + (f" {problems}" if problems else ""))
        failures += bool(problems)
    return failures


def run_delta_wire_chains(n_seeds: int, chain_len: int) -> int:
    """Random churn chains through a REAL client/server pair; returns the
    number of failing seeds.  Per step: client-view == server-chain byte
    parity, validator clean, cost ceiling held."""
    import random

    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.service.client import DeltaSession
    from karpenter_tpu.service.server import SolverService, make_server

    reg = Registry()
    service = SolverService(BatchScheduler(backend="tpu", registry=reg),
                            registry=reg)
    srv, port = make_server(service, port=0)
    failures = 0
    try:
        for seed in range(n_seeds):
            rng = random.Random(30_000 + seed)
            pods, provs, unavailable = random_scenario(seed, catalog)
            sess = DeltaSession(f"127.0.0.1:{port}", timeout=120.0)
            cur = sess.solve(pods, provs, catalog, unavailable=unavailable)
            if cur.infeasible:
                doomed0 = set(cur.infeasible)
                pods = [p for p in pods if p.name not in doomed0]
            cur_pods = {p.name: p for p in pods}
            problems = []
            modes = []
            extra_seed = 900 + seed
            for step in range(chain_len):
                kind = rng.choice(("add", "remove", "reclaim", "mixed"))
                added, removed, iced = [], [], []
                if kind in ("add", "mixed"):
                    fresh = random_scenario(extra_seed, catalog)[0]
                    extra_seed += 1
                    take = fresh[: rng.randint(1, max(2, len(cur_pods) // 25))]
                    added = _isolate_labels(take, f"w{seed}c{step}")
                if kind in ("remove", "mixed") and cur.assignments:
                    k = rng.randint(1, max(1, len(cur_pods) // 25))
                    removed = rng.sample(sorted(cur.assignments),
                                         min(k, len(cur.assignments)))
                if kind == "reclaim":
                    names = [n.name for n in cur.nodes]
                    if names:
                        iced = [rng.choice(names)]
                cur = sess.solve_delta(added=added, removed=removed,
                                       iced=iced)
                doomed = set(removed)
                for n in doomed:
                    cur_pods.pop(n, None)
                for p in added:
                    cur_pods[p.name] = p
                # (a) wire losslessness: client view == server chain
                pipe = list(service._pipelines.values())[0]
                entry = pipe._delta_tab.get(sess.session_id)
                if entry is None:
                    problems.append(f"step {step}: session lost")
                    break
                modes.append(entry.epoch)
                if entry.prev.assignments != cur.assignments or \
                        entry.prev.infeasible != cur.infeasible or \
                        {n.name: sorted(p.name for p in n.pods)
                         for n in entry.prev.nodes} != \
                        {n.name: sorted(p.name for p in n.pods)
                         for n in cur.nodes}:
                    problems.append(f"step {step}: client diverged from "
                                    "server chain")
                # (b) ground-truth validity of the merged view
                errs = validate_solution(list(cur_pods.values()), provs,
                                         cur, catalog)
                if errs:
                    problems.append(f"step {step}: {errs[:2]}")
                # (c) cost ceiling vs from-scratch
                full = BatchScheduler(backend="tpu").solve(
                    list(cur_pods.values()), provs, catalog,
                    unavailable=set(sess._unavailable) or None)
                if (full.new_node_cost > 0 and full.n_scheduled
                        and cur.n_scheduled):
                    r = (cur.new_node_cost / cur.n_scheduled) / (
                        full.new_node_cost / full.n_scheduled)
                    if r > DELTA_FUZZ_COST_CEILING + 1e-9:
                        problems.append(f"step {step}: cost ratio {r:.4f}")
            tag = "OK " if not problems else "FAIL"
            print(f"delta-wire seed {seed}: {tag} epochs={modes}"
                  + (f" {problems}" if problems else ""))
            failures += bool(problems)
            sess.close()
    finally:
        srv.stop(grace=None)
        service.close()
    return failures


def _relax_mix(seed: int):
    """Seed-varied unconstrained complementary-resource block appended to
    each scenario so the rung has eligible mass (random tiny scenarios
    are mostly constraint-bearing — adversarial for the partition, but
    they would only ever exercise the 'skipped' outcome)."""
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import PodSpec

    pods = []
    for d in range(6):
        kind = (d + seed) % 3
        if kind == 0:
            cpu, mem = 1.0 + (d % 3) * 0.5, 0.25 * GIB
        elif kind == 1:
            cpu, mem = 0.1 + 0.05 * d, (4.0 + 2 * (d % 2)) * GIB
        else:
            cpu, mem = 0.5 * (1 + d % 2), 2.0 * GIB
        for i in range(12 + (seed * 7 + d * 3) % 30):
            pods.append(PodSpec(
                name=f"rxf{seed}-{d}-{i}", labels={"app": f"rxfz{seed}{d}"},
                requests={"cpu": cpu, "memory": mem},
                owner_key=f"rxf{seed}-{d}",
            ))
    return pods


def run_relax_seeds(n_seeds: int) -> int:
    """Random scenarios (plus an unconstrained mix block) straight through
    the relax rung; returns the number of failing seeds.  Every seed
    asserts the never-worse select, ground-truth validity, and an
    unchanged schedulable-pod set.  Scenario routing mirrors the
    scheduler's: preference-bearing pods harden first, and batches the
    device scan does not serve (ct-spread oracle routes, inexpressible
    carve-outs) are skipped — the rung never sees them in production."""
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.models.tensorize import (
        batch_needs_oracle, device_inexpressible, tensorize)
    from karpenter_tpu.solver import relax
    from karpenter_tpu.solver.scheduler import _harden_preferences
    from karpenter_tpu.solver.tpu import TpuSolver

    solver = TpuSolver()
    failures = 0
    outcomes = {}
    for seed in range(n_seeds):
        base, provs, unavailable = random_scenario(seed, catalog)
        pods = [_harden_preferences(p) for p in base] + _relax_mix(seed)
        if batch_needs_oracle(pods) or any(
                device_inexpressible(p) for p in pods):
            print(f"relax seed {seed}: SKIP (oracle-routed batch)")
            continue
        st = tensorize(pods, provs, catalog, unavailable=unavailable)
        scan = solver.solve(st, track_assignments=True).result
        scan_cost = scan.new_node_cost
        scan_scheduled = set(scan.assignments)
        reg = Registry()
        shipped, outcome = relax.refine(scan, st, registry=reg)
        outcomes[outcome] = outcomes.get(outcome, 0) + 1
        problems = []
        if shipped.new_node_cost > scan_cost + 1e-9:
            problems.append(
                f"shipped ${shipped.new_node_cost:.4f} > scan "
                f"${scan_cost:.4f} — never-worse violated")
        if set(shipped.assignments) != scan_scheduled:
            problems.append("schedulable-pod set changed")
        errs = validate_solution(pods, provs, shipped, catalog,
                                 unavailable=unavailable or ())
        if errs:
            problems.append(f"validator: {errs[:2]}")
        tag = "OK " if not problems else "FAIL"
        print(f"relax seed {seed}: {tag} {outcome}"
              + (f" {problems}" if problems else ""))
        failures += bool(problems)
    print(f"relax outcomes over {n_seeds} seeds: {outcomes}")
    return failures


def _hier_fuzz_scenario(seed: int, disjoint: bool):
    """Seed-varied deployment blocks — distinct spread selectors per
    deployment make each one its own coupling component; ``disjoint``
    additionally pins every deployment to its own zone, removing flat's
    last coupling channels (per-zone suffix backfill, co-residency) — the
    byte-parity construction."""
    import random

    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import DEFAULT_ZONES
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import (LabelSelector, PodSpec,
                                          TopologySpreadConstraint)

    rng = random.Random(77_000 + seed)
    nd = len(DEFAULT_ZONES) if disjoint else rng.randint(2, 5)
    pods = []
    for d in range(nd):
        sel = LabelSelector.of({"app": f"fz{seed}-{d}"})
        node_sel = ({L.ZONE: DEFAULT_ZONES[d % len(DEFAULT_ZONES)]}
                    if disjoint else {})
        cpu = 0.25 * rng.randint(1, 8)
        mem = GIB * (0.5 + rng.randint(0, 5))
        for i in range(rng.randint(20, 120)):
            pods.append(PodSpec(
                name=f"fz{seed}-{d}-{i}", labels={"app": f"fz{seed}-{d}"},
                requests={"cpu": cpu, "memory": mem},
                node_selector=dict(node_sel),
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"fz{seed}-{d}"))
    return pods


def _placement_canon(result):
    """Node-name-independent placement view: pod -> (instance type, zone,
    capacity type, co-resident pod multiset).  Two solves are
    placement-identical iff the canon maps match — node NAMES always
    differ (the process-global SimNode counter)."""
    by_node = {n.name: (n.instance_type, n.zone, n.capacity_type,
                        tuple(sorted(p.name for p in n.pods)))
               for n in result.nodes}
    return {pn: by_node.get(nn) for pn, nn in result.assignments.items()}


def run_hier_seeds(n_seeds: int) -> int:
    """Hierarchical-decomposition fuzz (ISSUE 16); returns the number of
    failing seeds.  Per seed: disjoint byte-parity, component-never-split
    under forced block pressure, repair completeness vs flat."""
    import numpy as np

    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver import hierarchy as H

    provs = [Provisioner(name="default").with_defaults()]
    sched = BatchScheduler(backend="tpu", compile_behind=False)
    failures = 0
    for seed in range(n_seeds):
        problems = []
        # (a) block-disjoint: hier must ship flat's exact placement.
        # relax=False on the flat reference: the flat scheduler path runs
        # the PR-11 relax rung's min(scan, relax+round) select on top of
        # the device scan, which can repack f64-epsilon-cheaper cost TIES
        # into different (equally priced) nodes; megabatch slots skip that
        # rung by design, so the decomposition's byte-parity claim is
        # scan-vs-scan
        dpods = _hier_fuzz_scenario(seed, disjoint=True)
        dflat = sched.solve(dpods, provs, catalog, relax=False)
        dhier = H.solve_hierarchical(sched, dpods, provs, catalog)
        if dhier is None:
            problems.append("disjoint: hierarchical path fell back")
        elif _placement_canon(dflat) != _placement_canon(dhier):
            # byte parity is the primary claim, but the flat scan and the
            # vmapped megabatch program are DIFFERENT compiled graphs —
            # their f32 score arithmetic can round a genuine price tie
            # (e.g. 2x m5.large vs 1x m5.xlarge) to opposite picks in the
            # last ulp.  A mismatch is acceptable ONLY as such a tie: same
            # pods seated, no infeasibility drift, and the node-cost
            # totals bitwise-equal at f32 (the scan's own accumulation
            # precision).  Anything wider is a real decomposition bug.
            fcost = np.float32(sum(n.price for n in dflat.nodes))
            hcost = np.float32(sum(n.price for n in dhier.nodes))
            tie = (set(dflat.assignments) == set(dhier.assignments)
                   and set(dflat.infeasible) == set(dhier.infeasible)
                   and fcost.tobytes() == hcost.tobytes())
            if not tie:
                diff = sum(1 for pn, v in _placement_canon(dflat).items()
                           if _placement_canon(dhier).get(pn) != v)
                problems.append(f"disjoint: {diff} pod placement(s) "
                                "diverged from flat beyond an f32 cost tie")
        # (b) never split a reachability component, even under block
        # pressure (fewer bins than components forces LPT packing) — on
        # the adversarial random scenarios, whose affinity/spread webs
        # produce multi-group components
        cpods, cprovs, unav = random_scenario(seed, catalog)
        st = tensorize(cpods, cprovs, catalog, unavailable=unav)
        comps = H.coupling_components(st)
        for max_blocks in (2, 3):
            masks = H.partition_blocks(st, comps, max_blocks)
            for ci, comp in enumerate(comps):
                owners = {bi for bi, m in enumerate(masks)
                          if bool(np.any(m[comp]))}
                whole = any(bool(np.all(m[comp])) for m in masks)
                if len(owners) != 1 or not whole:
                    problems.append(
                        f"component {ci} split across blocks {owners} "
                        f"at max_blocks={max_blocks}")
        # (c) repair completeness on an OVERLAPPING scenario (shared
        # zones): no pod flat seats may end up unseated hierarchically
        opods = _hier_fuzz_scenario(seed, disjoint=False)
        oflat = sched.solve(opods, provs, catalog, relax=False)
        ohier = H.solve_hierarchical(sched, opods, provs, catalog)
        if ohier is None:
            problems.append("overlap: hierarchical path fell back")
        else:
            lost = sorted(set(oflat.assignments) - set(ohier.assignments))
            if lost:
                problems.append(
                    f"overlap: {len(lost)} pod(s) flat seats are unseated "
                    f"hierarchically (e.g. {lost[:3]})")
        tag = "OK " if not problems else "FAIL"
        print(f"hier seed {seed}: {tag}"
              + (f" {problems}" if problems else ""))
        failures += bool(problems)
    return failures


def _gangify(seed: int, pods):
    """Randomly promote whole deployments (owner_key groups) to gangs:
    ~half the groups become gangs, one in four gangs is DOOMED by giving
    a member an unsatisfiable zone pin, and one in five is submitted with
    an incomplete roster (declared size > submitted members) — both must
    retract whole.  Returns (pods, gangs: {gid: [names]}, doomed: {gid})."""
    import dataclasses
    import random

    from karpenter_tpu.models import labels as L

    rng = random.Random(88_000 + seed)
    groups = {}
    for p in pods:
        groups.setdefault(p.owner_key or p.name, []).append(p)
    out, gangs, doomed = [], {}, set()
    for gi, (owner, members) in enumerate(sorted(groups.items())):
        if len(members) < 2 or rng.random() < 0.5:
            out.extend(members)
            continue
        gid = f"fzg{seed}-{gi}"
        size = len(members)
        kind = rng.random()
        if kind < 0.20:
            # incomplete roster: declare more ranks than the batch carries
            size = len(members) + rng.randint(1, 3)
            doomed.add(gid)
        marked = [dataclasses.replace(p, gang_id=gid, gang_size=size)
                  for p in members]
        if 0.20 <= kind < 0.40:
            # unsatisfiable member: a zone no catalog offering serves
            j = rng.randrange(len(marked))
            marked[j] = dataclasses.replace(
                marked[j],
                node_selector={**marked[j].node_selector,
                               L.ZONE: "zone-none"})
            doomed.add(gid)
        gangs[gid] = [p.name for p in marked]
        out.extend(marked)
    return out, gangs, doomed


def run_gang_seeds(n_seeds: int) -> int:
    """All-or-nothing gang fuzz (ISSUE 20); returns the number of failing
    seeds.  Per seed: no partial gang, typed retraction reasons,
    ground-truth validity, singleton-subset cost ceiling vs the gang-free
    oracle."""
    from test_fuzz_parity import FUZZ_PARITY

    failures = 0
    placed_total = retracted_total = 0
    for seed in range(n_seeds):
        problems = []
        base, provs, unavailable = random_scenario(seed, catalog)
        pods, gangs, doomed = _gangify(seed, base)
        sched = BatchScheduler(backend="tpu")
        res = sched.solve(pods, provs, catalog, unavailable=unavailable)
        # (a) the contract: every gang fully places or fully retracts
        for gid, names in gangs.items():
            placed = [n for n in names if n in res.assignments]
            if placed and len(placed) != len(names):
                problems.append(
                    f"gang {gid} PARTIAL: {len(placed)}/{len(names)} placed")
                continue
            if not placed:
                retracted_total += 1
                untyped = [n for n in names if n not in res.infeasible]
                if untyped:
                    problems.append(
                        f"gang {gid} retracted but {untyped[:3]} carry no "
                        "infeasible reason")
                elif not any(
                        str(res.infeasible[n]).startswith("GangUnplaced")
                        for n in names):
                    problems.append(
                        f"gang {gid} retracted without a typed "
                        f"GangUnplaced reason: {res.infeasible[names[0]]}")
            else:
                placed_total += 1
                if gid in doomed:
                    problems.append(
                        f"gang {gid} placed despite an engineered dooming")
        # (b) ground-truth validity of whatever shipped
        errs = validate_solution(pods, provs, res, catalog)
        if errs:
            problems.append(f"validator: {errs[:2]}")
        # (c) the gang path must not tax ungrouped pods: solve the
        # singleton subset alone (gang machinery armed, zero gangs) and
        # hold the plain fuzz ceiling vs the gang-free reference oracle
        singles = [p for p in pods if not p.gang_id]
        if singles:
            oracle = reference.solve(singles, provs, catalog,
                                     unavailable=unavailable)
            tpu = BatchScheduler(backend="tpu").solve(
                singles, provs, catalog, unavailable=unavailable)
            if (oracle.new_node_cost > 0 and tpu.n_scheduled
                    and oracle.n_scheduled):
                r = (tpu.new_node_cost / tpu.n_scheduled) / (
                    oracle.new_node_cost / oracle.n_scheduled)
                if r > FUZZ_PARITY + 1e-9:
                    problems.append(f"singleton cost ratio {r:.4f}")
        tag = "OK " if not problems else "FAIL"
        print(f"gang seed {seed}: {tag} gangs={len(gangs)} "
              f"doomed={len(doomed)}"
              + (f" {problems}" if problems else ""))
        failures += bool(problems)
    print(f"gang sweep: {placed_total} placed, {retracted_total} retracted "
          f"over {n_seeds} seeds")
    return failures


if relax_mode:
    n_seeds = int(argv[0]) if len(argv) > 0 else 25
    sys.exit(1 if run_relax_seeds(n_seeds) else 0)
if hier_mode:
    n_seeds = int(argv[0]) if len(argv) > 0 else 12
    sys.exit(1 if run_hier_seeds(n_seeds) else 0)
if gang_mode:
    n_seeds = int(argv[0]) if len(argv) > 0 else 20
    sys.exit(1 if run_gang_seeds(n_seeds) else 0)
if delta_wire:
    n_seeds = int(argv[0]) if len(argv) > 0 else 10
    chain_len = int(argv[1]) if len(argv) > 1 else 4
    sys.exit(1 if run_delta_wire_chains(n_seeds, chain_len) else 0)
if delta:
    n_seeds = int(argv[0]) if len(argv) > 0 else 12
    chain_len = int(argv[1]) if len(argv) > 1 else 4
    sys.exit(1 if run_delta_chains(n_seeds, chain_len) else 0)
suites = argv[0].split(",") if len(argv) > 0 else ["plain", "existing", "kubelet"]
n_seeds = int(argv[1]) if len(argv) > 1 else 40

for suite in suites:
    ratios = {}
    invalid = {}
    sched = BatchScheduler(backend="tpu") if cached else None
    for seed in range(n_seeds):
        pods, provs, unavailable = random_scenario(seed, catalog)
        kw = {}
        if suite == "kubelet":
            provs = with_random_kubelet(seed, provs)
            if all(p.kubelet is None for p in provs):
                continue
        if suite == "existing":
            kw["existing_nodes"] = random_existing_nodes(seed, catalog, provs)
        oracle = reference.solve(pods, provs, catalog, unavailable=unavailable, **kw)
        solver = sched or BatchScheduler(backend="tpu")
        tpu = solver.solve(
            pods, provs, catalog, unavailable=unavailable, **kw)
        if cached:
            # second pass: same pod objects through the same scheduler —
            # identity-tier tensorize cache; the answer must not move
            tpu2 = solver.solve(
                pods, provs, catalog, unavailable=unavailable, **kw)
            if (tpu2.n_scheduled != tpu.n_scheduled
                    or abs(tpu2.new_node_cost - tpu.new_node_cost) > 1e-6):
                invalid.setdefault(seed, []).append(
                    f"cached re-solve diverged: {tpu2.n_scheduled} pods "
                    f"${tpu2.new_node_cost:.3f} vs {tpu.n_scheduled} "
                    f"${tpu.new_node_cost:.3f}")
        errs = validate_solution(pods, provs, tpu, catalog)
        if errs:
            invalid[seed] = errs[:2]
        if oracle.new_node_cost > 0 and tpu.n_scheduled and oracle.n_scheduled:
            r = (tpu.new_node_cost / tpu.n_scheduled) / (
                oracle.new_node_cost / oracle.n_scheduled)
            ratios[seed] = round(r, 4)
        floor = oracle.n_scheduled - max(2, oracle.n_scheduled // (4 if suite == "existing" else 10))
        if tpu.n_scheduled < floor:
            invalid.setdefault(seed, []).append(
                f"scheduled {tpu.n_scheduled} < floor {floor}")
    vals = list(ratios.values())
    mean = sum(vals) / max(len(vals), 1)
    worst = sorted(ratios.items(), key=lambda kv: -kv[1])[:5]
    extra = ""
    if cached and sched is not None:
        c = sched._tensorize_cache
        extra = f" cache_hits={c.hits} misses={c.misses}"
    print(f"{suite}: n={len(vals)} mean={mean:.4f} worst={worst}{extra}")
    if invalid:
        print(f"  INVALID: {invalid}")
