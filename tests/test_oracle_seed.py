"""The oracle's topology seed (``solver/reference.py _Solver``): the counts
it leaves in ``topo`` for every key a placed group can read, the answers of
``reference.solve`` and of the capped-node reseat, and the counter that says
what the seed did, as the benchmark's ``oracle_seed_matches`` reads it.

The seed used to match every selector of every pod (placed and standing)
against every standing pod, one pod at a time.  It now holds only the placed
pods' selectors and matches each distinct label set once.  The old loop is
kept here, test-local, as the reference: :func:`per_pod_seed` and
:class:`PerPodSeedSolver`."""

import copy
import json
import os
import random

import harness_path  # noqa: F401 — puts benchmarks/ on sys.path
import numpy as np
import pytest
import run
import scrape

from karpenter_tpu.metrics import ORACLE_SEED, ORACLE_SEED_WHAT, Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import (
    DOES_NOT_EXIST,
    EXISTS,
    IN,
    NOT_IN,
    Requirement,
)
from karpenter_tpu.solver import reference
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.types import SimNode, SolveResult
from test_fuzz_parity import random_existing_nodes, random_scenario
from test_standing_cluster import full_nodes, web_pod

ZONES = ["zone-1a", "zone-1b", "zone-1c"]
CTS = [L.CAPACITY_TYPE_ON_DEMAND, L.CAPACITY_TYPE_SPOT, ""]


# ---- the seed before: every selector against every standing pod ---------

def per_pod_table(pods, existing_nodes):
    """The selectors of the placed AND the standing pods, as the old seed
    keyed them."""
    table = {}
    for p in list(pods) + [q for n in existing_nodes for q in n.pods]:
        for tsc in p.topology_spread:
            if tsc.hard:
                table[(tsc.label_selector, tsc.topology_key, "spread")] = (
                    tsc.label_selector)
        for t in p.affinity_terms:
            table[(t.label_selector, t.topology_key,
                   "anti" if t.anti else "affinity")] = t.label_selector
    return table


def per_pod_seed(pods, existing_nodes):
    """``(table, zone, node, ct, total)``: each standing pod matched against
    each selector of the old table, one pod at a time."""
    table = per_pod_table(pods, existing_nodes)
    zone, node, ct, total = {}, {}, {}, {}
    for n in existing_nodes:
        for p in n.pods:
            for key, sel in table.items():
                if not sel.matches(p.labels):
                    continue
                zone.setdefault(key, {}).setdefault(n.zone, 0)
                zone[key][n.zone] += 1
                node.setdefault(key, {}).setdefault(n.name, 0)
                node[key][n.name] += 1
                if n.capacity_type:
                    ct.setdefault(key, {}).setdefault(n.capacity_type, 0)
                    ct[key][n.capacity_type] += 1
                total[key] = total.get(key, 0) + 1
    return table, zone, node, ct, total


class PerPodSeedSolver(reference._Solver):
    """``_Solver`` with the old seed: its table and its counts replace what
    the new seed built, so placement (``_bind`` included) runs on them."""

    def __init__(self, pods, provisioners, instance_types, existing_nodes,
                 *rest) -> None:
        super().__init__(pods, provisioners, instance_types, existing_nodes,
                         *rest)
        table, zone, node, ct, total = per_pod_seed(pods, existing_nodes)
        self.selectors = table
        self.topo = reference._TopologyState()
        for key, counts in zone.items():
            self.topo.zone[key].update(counts)
        for key, counts in node.items():
            self.topo.node[key].update(counts)
        for key, counts in ct.items():
            self.topo.ct[key].update(counts)
        self.topo.total.update(total)


def canon(res: SolveResult):
    """A result with every node named by its place in ``res.nodes`` or
    ``res.existing_nodes`` (node names come off a process-wide counter, so
    two builds of one input name their nodes apart)."""
    names = {n.name: f"existing-{i}"
             for i, n in enumerate(res.existing_nodes)}
    names.update({n.name: f"new-{i}" for i, n in enumerate(res.nodes)})

    def node(n):
        return (names.get(n.name, n.name), n.instance_type, n.provisioner,
                n.zone, n.capacity_type, n.price,
                sorted((k, v) for k, v in n.labels.items()
                       if k != L.HOSTNAME),
                [p.name for p in n.pods])

    return ([node(n) for n in res.nodes],
            [node(n) for n in res.existing_nodes],
            {p: names.get(v, v) for p, v in res.assignments.items()},
            dict(res.infeasible), res.new_node_cost)


# ---- (a) the seed's counts ----------------------------------------------

def _selectors():
    return [
        LabelSelector.of({"app": "a"}),
        LabelSelector.of({"app": "b", "tier": "web"}),
        LabelSelector.of({}, [Requirement("tier", IN, ["web", "db"])]),
        LabelSelector.of({"app": "a"}, [Requirement("canary", DOES_NOT_EXIST)]),
        LabelSelector.of({}, [Requirement("app", NOT_IN, ["c"]),
                              Requirement("tier", EXISTS)]),
        LabelSelector.of({"app": "c"}),
        LabelSelector.of({"app": "none-of-them"}),
    ]


def _label_sets(rng):
    sets = [{"app": "a"}, {"app": "a", "canary": "1"},
            {"app": "b", "tier": "web"}, {"tier": "web", "app": "b"},
            {"app": "c", "tier": "db"}, {"tier": "db"}, {}]
    # distinct label dicts, one pod each
    sets += [{"app": rng.choice("abc"), "pod": str(i)} for i in range(6)]
    return sets


def _constraints(rng, sels):
    """A pod's spread and (anti-)affinity terms, drawn from ``sels``."""
    spread, terms = [], []
    for _ in range(rng.randrange(3)):
        spread.append(TopologySpreadConstraint(
            rng.randrange(1, 3),
            rng.choice([L.ZONE, L.HOSTNAME, L.CAPACITY_TYPE]),
            rng.choice(["DoNotSchedule", "DoNotSchedule", "ScheduleAnyway"]),
            rng.choice(sels)))
    for _ in range(rng.randrange(3)):
        terms.append(PodAffinityTerm(rng.choice(sels),
                                     rng.choice([L.ZONE, L.HOSTNAME]),
                                     anti=rng.random() < 0.5))
    return spread, terms


def random_cluster(seed: int, empty_table: bool = False):
    """``(pods, nodes)``: pods to place and standing nodes whose pods share
    label dicts, carry distinct ones, or equal items in another order, under
    selectors with ``matchExpressions``, on nodes with and without a
    capacity type."""
    rng = random.Random(seed)
    sels, label_sets = _selectors(), _label_sets(rng)
    pods = []
    for d in range(rng.randrange(1, 4)):
        spread, terms = (([], []) if empty_table
                         else _constraints(rng, sels))
        for i in range(rng.randrange(1, 4)):
            pods.append(PodSpec(name=f"p{d}-{i}",
                                labels=dict(rng.choice(label_sets)),
                                topology_spread=spread,
                                affinity_terms=terms, owner_key=f"p{d}"))
    nodes = []
    for k in range(rng.randrange(1, 12)):
        ct = rng.choice(CTS)
        node = SimNode(instance_type="m5.large", provisioner="default",
                       zone=rng.choice(ZONES), capacity_type=ct, price=0.1,
                       allocatable={"cpu": 2.0}, existing=True)
        for j in range(rng.randrange(0, 9)):
            labels = rng.choice(label_sets)
            if rng.random() < 0.5:
                labels = dict(labels)  # an equal dict, not the same one
            spread, terms = _constraints(rng, sels)
            node.pods.append(PodSpec(name=f"s{k}-{j}", labels=labels,
                                     topology_spread=spread,
                                     affinity_terms=terms))
        nodes.append(node)
    return pods, nodes


def _seeded(pods, nodes) -> reference._Solver:
    prov = Provisioner(name="default").with_defaults()
    return reference._Solver(pods, [prov], [], nodes, [], set(), True, None)


@pytest.mark.parametrize("seed", range(32))
def test_the_seed_counts_what_the_per_pod_loop_counts(seed):
    """For every key a placed group can read (the selectors of the pods
    being placed), ``zone`` / ``node`` / ``ct`` / ``total`` equal the per-pod
    loop's."""
    pods, nodes = random_cluster(seed)
    s = _seeded(pods, nodes)
    table, zone, node, ct, total = per_pod_seed(pods, nodes)
    read = set()
    for p in pods:
        for tsc in p.topology_spread:
            if tsc.hard:
                read.add((tsc.label_selector, tsc.topology_key, "spread"))
        for t in p.affinity_terms:
            read.add((t.label_selector, t.topology_key,
                      "anti" if t.anti else "affinity"))
    assert set(s.selectors) == read
    assert read <= set(table)
    for key in read:
        assert dict(s.topo.zone.get(key, {})) == zone.get(key, {}), key
        assert dict(s.topo.node.get(key, {})) == node.get(key, {}), key
        assert dict(s.topo.ct.get(key, {})) == ct.get(key, {}), key
        assert s.topo.total.get(key, 0) == total.get(key, 0), key


@pytest.mark.parametrize("seed", range(4))
def test_an_empty_table_skips_the_walk(seed):
    """Placed pods with no spread and no (anti-)affinity: no selector, no
    count, no standing pod tallied."""
    pods, nodes = random_cluster(seed, empty_table=True)
    s = _seeded(pods, nodes)
    assert s.selectors == {}
    assert not s.topo.zone and not s.topo.node and not s.topo.total
    assert (s.seed_pods, s.seed_matches) == (0, 0)


# ---- (b) the oracle's answers -------------------------------------------

def standing_cluster(seed: int, catalog, provs, pods):
    """``random_existing_nodes`` with standing pods on them: copies of the
    placed deployments' pods (their labels in another order now and then)
    and a standing deployment whose selectors no placed pod carries, on
    nodes of every capacity type and none."""
    nodes = random_existing_nodes(seed, catalog, provs)
    rng = np.random.default_rng(seed + 40_000)
    sel = LabelSelector.of({"app": "standing"})
    standing = PodSpec(
        name="", labels={"app": "standing", "tier": "bg"},
        requests={"cpu": 0.25}, owner_key="standing",
        topology_spread=[TopologySpreadConstraint(1, L.ZONE,
                                                  "DoNotSchedule", sel)],
        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)])
    for k, node in enumerate(nodes):
        ct = CTS[int(rng.integers(0, len(CTS)))]
        node.capacity_type = ct
        if ct:
            node.labels[L.CAPACITY_TYPE] = ct
        else:
            node.labels.pop(L.CAPACITY_TYPE, None)
        for j in range(int(rng.integers(0, 4))):
            q = copy.copy(pods[int(rng.integers(0, len(pods)))])
            q.name = f"standing-{k}-{j}"
            if rng.random() < 0.5:
                items = list(q.labels.items()) + [("pod-template-hash", "x")]
                if rng.random() < 0.5:
                    items.reverse()  # equal items in another order
                q.labels = dict(items)
            q.__dict__.pop("_group_key", None)
            node.pods.append(q)
        if rng.random() < 0.5:
            q = copy.copy(standing)
            q.name = f"standing-bg-{k}"
            node.pods.append(q)
    return nodes


@pytest.mark.parametrize("seed", sorted(set(range(12)) | {5, 17, 20, 23}))
def test_the_oracle_answers_as_with_the_per_pod_seed(
        seed, small_catalog, monkeypatch):
    """``reference.solve`` over ``random_scenario`` / ``random_existing_nodes``
    with standing pods: nodes, assignments, infeasible and prices equal the
    solve's with the per-pod seed (5, 17, 20 and 23 are the seeds the
    reseat's docstrings name)."""
    pods, provs, unavailable = random_scenario(seed, small_catalog)
    existing = standing_cluster(seed, small_catalog, provs, pods)
    got = reference.solve(pods, provs, small_catalog, existing_nodes=existing,
                          unavailable=unavailable, registry=Registry())
    monkeypatch.setattr(reference, "_Solver", PerPodSeedSolver)
    want = reference.solve(pods, provs, small_catalog,
                           existing_nodes=existing, unavailable=unavailable,
                           registry=Registry())
    assert canon(got) == canon(want)


# ---- (c) the capped-node reseat -----------------------------------------

def _labelled_fillers(nodes):
    """Give each filler a label set and a hostname anti-affinity of its own:
    selectors only the standing pods carry."""
    for k, n in enumerate(nodes):
        for p in n.pods:
            if p.owner_key == "filler":
                p.labels = {"app": "filler", "slot": str(k % 3)}
                p.affinity_terms = [PodAffinityTerm(
                    LabelSelector.of(p.labels), L.HOSTNAME, anti=True)]
                p.__dict__.pop("_group_key", None)
    return nodes


@pytest.mark.parametrize("fillers", ["plain", "labelled"])
def test_the_reseat_adopts_the_same_answer(
        fillers, full_catalog, monkeypatch):
    """``_reseat_capped`` over ``full_nodes`` (zone counts 7 / 6 / 6, every
    node full) and a new node priced above what its one spread pod needs:
    the oracle re-solve runs, and the answer it adopts equals the one the
    per-pod seed gives."""
    prov = Provisioner(name="default").with_defaults()
    big = next(t for t in full_catalog if t.name == "m6a.2xlarge")
    price = next(o.price for o in big.offerings if o.zone == "zone-1b"
                 and o.capacity_type == L.CAPACITY_TYPE_ON_DEMAND)

    def reseat():
        existing = full_nodes(full_catalog, prov, {
            "zone-1a": 7, "zone-1b": 6, "zone-1c": 6})
        if fillers == "labelled":
            _labelled_fillers(existing)
        waste = SimNode(
            instance_type=big.name, provisioner=prov.name, zone="zone-1b",
            capacity_type=L.CAPACITY_TYPE_ON_DEMAND, price=price,
            allocatable=dict(big.allocatable),
            labels={**big.labels(), L.ZONE: "zone-1b",
                    L.CAPACITY_TYPE: L.CAPACITY_TYPE_ON_DEMAND,
                    L.PROVISIONER_NAME: prov.name})
        waste.labels[L.HOSTNAME] = waste.name
        pod = web_pod("web-new")
        waste.pods.append(pod)
        result = SolveResult(nodes=[waste],
                             assignments={pod.name: waste.name},
                             infeasible={}, existing_nodes=existing)
        reg = Registry()
        sched = BatchScheduler("tpu", compile_behind=False, registry=reg)
        sched._reseat_capped(result, [prov], full_catalog, [], set(),
                             n_pods=1)
        return result, reg.counter(ORACLE_SEED)

    got, seed_counter = reseat()
    assert seed_counter.get({"what": "pods"}) == 2 * 19
    monkeypatch.setattr(reference, "_Solver", PerPodSeedSolver)
    want, _ = reseat()
    assert canon(got) == canon(want)
    (node,) = got.nodes
    assert node.instance_type != "m6a.2xlarge"  # the reseat was adopted
    assert node.zone in ("zone-1b", "zone-1c")


# ---- (d) the counter -----------------------------------------------------

def test_the_counter_counts_pods_tallied_and_label_sets_matched():
    """Three nodes, ten standing pods in three label sets (one of them in two
    orders), two selectors on the placed pod: 10 pods tallied, 3 x 2
    evaluations; a placed pod with no selector tallies nothing."""
    a, b = LabelSelector.of({"app": "a"}), LabelSelector.of({"app": "b"})
    pod = PodSpec(name="new", labels={"app": "a"},
                  topology_spread=[TopologySpreadConstraint(
                      1, L.ZONE, "DoNotSchedule", a)],
                  affinity_terms=[PodAffinityTerm(b, L.HOSTNAME, anti=True)])
    label_sets = [{"app": "a", "v": "1"}, {"v": "1", "app": "a"},
                  {"app": "b"}, {"app": "c"}]
    nodes = []
    for k, zone in enumerate(ZONES):
        node = SimNode(instance_type="m5.large", provisioner="default",
                       zone=zone, capacity_type=L.CAPACITY_TYPE_ON_DEMAND,
                       price=0.1, allocatable={"cpu": 2.0}, existing=True)
        for j in range(3 + (k == 0)):
            node.pods.append(PodSpec(name=f"s{k}-{j}",
                                     labels=dict(label_sets[(j + k) % 4])))
        nodes.append(node)
    reg = Registry()
    prov = Provisioner(name="default").with_defaults()
    reference.solve([pod], [prov], [], existing_nodes=nodes, registry=reg)
    seed = reg.counter(ORACLE_SEED)
    assert seed.get({"what": "pods"}) == 10
    # {app a, v 1} in two orders is two keys of the tally: 4 sets x 2
    assert seed.get({"what": "matches"}) == 4 * 2
    plain = PodSpec(name="plain", labels={"app": "a"})
    reference.solve([plain], [prov], [], existing_nodes=nodes, registry=reg)
    assert seed.get({"what": "pods"}) == 10
    assert seed.get({"what": "matches"}) == 8


def test_the_counter_is_born_at_zero_on_the_schedulers_registry():
    reg = Registry()
    BatchScheduler(backend="auto", registry=reg)
    for what in ORACLE_SEED_WHAT:
        assert reg.counter(ORACLE_SEED).has({"what": what})
        assert reg.counter(ORACLE_SEED).get({"what": what}) == 0.0
    assert 'karpenter_solver_oracle_seed_total{what="matches"} 0' in (
        reg.expose())


def test_distinct_label_sets_are_matched_one_each():
    """The tally's worst case: 10,000 standing pods, every one its own label
    set, one selector.  Each set is matched once, and the counts are the
    per-pod loop's."""
    sel = LabelSelector.of({"app": "web"})
    pod = PodSpec(name="new", labels={"app": "web"},
                  topology_spread=[TopologySpreadConstraint(
                      1, L.ZONE, "DoNotSchedule", sel)])
    nodes = []
    for k in range(100):
        node = SimNode(instance_type="m5.large", provisioner="default",
                       zone=ZONES[k % 3], capacity_type=CTS[k % 3],
                       price=0.1, allocatable={"cpu": 2.0}, existing=True)
        node.pods = [PodSpec(name=f"s{k}-{j}",
                             labels={"app": "web" if j % 4 else "db",
                                     "pod": f"{k}-{j}"})
                     for j in range(100)]
        nodes.append(node)
    s = _seeded([pod], nodes)
    assert (s.seed_pods, s.seed_matches) == (10_000, 10_000)
    _, zone, node, ct, total = per_pod_seed([pod], nodes)
    key = (sel, L.ZONE, "spread")
    assert s.topo.total[key] == total[key] == 7_500
    assert dict(s.topo.zone[key]) == zone[key]
    assert dict(s.topo.node[key]) == node[key]
    assert dict(s.topo.ct[key]) == ct[key]


# ---- the benchmark's ``oracle_seed_matches`` -----------------------------

bench = harness_path.bench  # the harness's fixture
BENCH_DIR = os.path.dirname(harness_path.HARNESS_TESTS)


def _metric():
    with open(os.path.join(BENCH_DIR, "metrics",
                           "oracle_seed_matches.json")) as f:
        spec = json.load(f)
    reader = run._module(os.path.join(BENCH_DIR, "readers",
                                      f"{spec['reader']}.py"),
                         f"reader_{spec['reader']}")
    return spec, reader


def test_the_metric_reads_matches_a_request_and_zero_without_the_family():
    """Through the harness's reader: a served window's scrape reads the
    ``matches`` label a request; a program without the family reads 0.0
    and does not raise."""
    spec, reader = _metric()
    reg = Registry()
    BatchScheduler(backend="oracle", registry=reg)
    before = scrape.parse_metrics(reg.expose())
    pods, nodes = random_cluster(3)
    prov = Provisioner(name="default").with_defaults()
    for _ in range(2):
        reference.solve(pods, [prov], [], existing_nodes=nodes, registry=reg)
    after = scrape.parse_metrics(reg.expose())
    matches = reg.counter(ORACLE_SEED).get({"what": "matches"})
    assert matches > 0
    got = reader.read({"before": before, "after": after, "requests": 2},
                      **spec["args"])
    assert got == matches / 2
    other = [("karpenter_solver_cold_start_fallbacks_total", {}, 2.0)]
    for ctx in ({"before": other, "after": other, "requests": 3},
                {"before": [], "after": [], "requests": 3}):
        assert reader.read(ctx, **spec["args"]) == 0.0


def test_the_declared_entry_is_the_file(bench):
    """``BENCHMARK.json`` declares the file's entry in ``per_layer``, with
    no ``workloads``: every cell that reports ``solve_ms`` reads it."""
    spec, _ = _metric()
    (decl,) = [m for m in bench["per_layer"]
               if m["name"] == "oracle_seed_matches"]
    assert decl == {k: spec[k] for k in ("name", "unit", "better", "source",
                                         "layer", "moves")}
    assert (decl["unit"], decl["better"], decl["source"], decl["layer"],
            decl["moves"]) == ("matches", "lower", "program_counter",
                               "host epilogues", "solve_ms")
    assert spec["reader"] == "counter_per_request"
    assert spec["args"] == {"metric": ORACLE_SEED,
                            "labels": [{"what": "matches"}]}
