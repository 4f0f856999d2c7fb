"""``longtail-15k``: the Kubernetes load test's 500-node cluster as a
benchmark configuration (PR 27), and what it made visible in the program.

(a) the configuration file is the source's shape and ``BENCHMARK.json`` names
    its cell;
(b) ONE NAMESPACE of it (the load test's 100-node instance: 3,000 pods in
    3 x 250 + 25 x 30 + 300 x 5 = 328 deployments) through
    ``BatchScheduler("tpu")`` on the CPU against the benchmark's plain FFD and
    validator;
(c) ``_nr_estimate`` against the slots the SCAN uses on such tensors, and the
    rungs the configurations of the benchmark get;
(d) the two counter families this PR adds, and the benchmark's metric files
    that read them.
"""

import copy
import importlib.util
import json
import os
import sys

import pytest

from karpenter_tpu.metrics import (
    SCAN_AXES,
    SCAN_AXIS,
    SCAN_SLOT_RETRIES,
    Registry,
)
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.solver import tpu as tpu_mod
from karpenter_tpu.solver.scheduler import BatchScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "longtail-15k"
CELL = "longtail.burst"
#: the driver's seeds pass 32 signed bits
SEEDS = (11, 2 ** 31 + 4099, 977)
#: this PR's per-layer metrics -> the label of the family each reads
NEW_METRICS = {"scan_groups": "groups", "scan_groups_padded": "groups_padded",
               "scan_node_slots": "node_slots",
               "scan_nodes_used": "nodes_used", "scan_slot_retries": None,
               # PR 38: the steps the program took, as it reports them
               "scan_steps_run": "steps_run"}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def harness():
    """The benchmark's generator, plain reference and scrape parser, loaded
    from their files (``benchmarks/`` is no package; ``plainref`` and the
    readers import their siblings by bare name)."""
    added = BENCH not in sys.path
    if added:
        sys.path.insert(0, BENCH)
    mods = {name: _load(os.path.join(BENCH, f"{name}.py"), name)
            for name in ("gen", "plainref", "scrape")}
    for name, mod in mods.items():
        sys.modules.setdefault(name, mod)
    yield mods
    if added:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg(harness):
    return harness["gen"].load_config(CONFIG)


# ---- (a) the file is the source's shape ------------------------------------


def test_the_configuration_is_the_load_tests_500_node_instance(harness, cfg):
    gen = harness["gen"]
    assert gen.pods_per_request(cfg) == 15_000 == 500 * 30
    cluster = gen.burst_pool(cfg, 1)[0]
    assert cluster.n_pods == 15_000 and len(cluster.groups) == 1_640
    by_size = {}
    for g in cluster.groups:
        by_size.setdefault(len(g["pods"]), []).append(g)
    # BIG / MEDIUM / SMALL_GROUP_SIZE, holding 1/4, 1/4 and 1/2 of the pods
    assert {n: len(gs) for n, gs in by_size.items()} == {
        250: 15, 30: 125, 5: 1_500}
    assert [n * len(by_size[n]) for n in (250, 30, 5)] == [
        3_750, 3_750, 7_500]
    assert {g["constraint"] for g in by_size[250] + by_size[30]} == {
        "zone_spread"}
    assert {g["constraint"] for g in by_size[5]} == {"none"}
    assert cfg["reduced"] == [] and all(
        t["replica_spread"] == 0 for t in cfg["deployments"])


def test_every_request_of_the_pool_differs_and_every_seed_sends_the_same_work(
        harness, cfg):
    gen = harness["gen"]
    pool = gen.burst_pool(cfg, 4) + gen.burst_pool(cfg, 3, 4)
    assert len({c.key for c in pool}) == 7
    shapes = [sorted((g["cpu"], g["memory"], len(g["pods"]), g["constraint"])
                     for g in gen.salted(pool[0], seed).groups)
              for seed in SEEDS]
    assert shapes[0] == shapes[1] == shapes[2]
    names = [{g["name"] for g in gen.salted(pool[0], seed).groups}
             for seed in SEEDS]
    assert not names[0] & names[1] and not names[1] & names[2]


@pytest.mark.parametrize("key", ["catalog", "provisioner_defaults",
                                 "provisioners", "layout"])
def test_catalog_provisioner_and_layout_are_c2s(harness, cfg, key):
    assert cfg[key] == harness["gen"].load_config("c2-50k-3az")[key]


def test_request_shapes_and_guarantees_are_c2s_none_weakened(harness, cfg):
    c2 = harness["gen"].load_config("c2-50k-3az")
    for t in cfg["deployments"]:
        assert (t["cpu"], t["memory_gib"], t["tolerations"]) == (
            c2["deployments"][0]["cpu"], c2["deployments"][0]["memory_gib"],
            [])
    mine, theirs = cfg["guarantees"], c2["guarantees"]
    assert set(mine) == set(theirs)
    for key in ("placed", "cost_ceiling", "cost_base", "session"):
        assert mine[key] == theirs[key]
    assert mine["cost_ceiling"] == 1.02
    for word in ("resource fit", "maxSkew 1", "taints", "provisioner filter",
                 "offerings and prices"):
        assert word in mine["valid"] and word in theirs["valid"]
    assert set(cfg["assumed"]) >= {"requests", "zone_spread", "namespaces",
                                   "replica_spread", "rotation", "seed"}


def test_benchmark_json_names_the_configuration_and_its_cell(bench, cfg):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "clusterloader2/testing/load/config.yaml" in entry["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "burst", 1)
    assert len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["pods_per_s"]["workloads"]
    # the cells that were there come first, as they were
    assert [w["name"] for w in bench["workloads"]][:2] == [
        "c2.burst", "c3.burst"]
    assert e2e["pods_per_s"]["workloads"][:2] == ["c2.burst", "c3.burst"]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_is_declared_as_its_file_says(bench, name):
    decl = next(m for m in bench["per_layer"] if m["name"] == name)
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert "workloads" not in decl  # every cell reports it
    for key in ("unit", "better", "source", "layer", "moves"):
        assert decl[key] == spec[key]
    assert (decl["source"], decl["layer"], decl["moves"], decl["better"]) == (
        "program_counter", "device program", "solve_ms", "lower")
    assert spec["reader"] == "counter_per_request"
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       f"{spec['reader']}.py"))


# ---- (b), (c), (d): one namespace through the device tier ------------------


@pytest.fixture(scope="module")
def namespace(harness, cfg):
    """One namespace of the load test — its 100-node instance, a fifth of the
    configuration's deployment counts at the source's sizes and shares —
    solved by ``BatchScheduler("tpu")`` once per seed, with what the registry
    and the tracer said of each solve."""
    gen, scrape = harness["gen"], harness["scrape"]
    small = copy.deepcopy(cfg)
    for t in small["deployments"]:
        assert t["count"] % 5 == 0
        t["count"] //= 5
    inputs = gen.ProgramInputs(small)
    reg = Registry()
    tracer = Tracer(registry=reg)
    sched = BatchScheduler("tpu", registry=reg, tracer=tracer)
    axis = reg.counter(SCAN_AXIS)
    zero = {"axes": {a: (axis.has({"axis": a}), axis.get({"axis": a}))
                     for a in SCAN_AXES},
            "retries": (reg.counter(SCAN_SLOT_RETRIES).has({}),
                        reg.counter(SCAN_SLOT_RETRIES).get()),
            "text": reg.expose()}
    solves = []
    for seed, cluster in zip(SEEDS, gen.burst_pool(small, len(SEEDS))):
        groups = gen.salted(cluster, seed).groups
        pods = inputs.pods(groups)
        st = tensorize(pods, inputs.provisioners, inputs.catalog)
        budget = tpu_mod._node_budget(st, 0, None)
        before = scrape.parse_metrics(reg.expose())
        trace = tracer.start("solve")
        with trace:
            res = sched.solve(pods, inputs.provisioners, inputs.catalog,
                              trace=trace)
        solves.append({
            "seed": seed, "groups": groups, "res": res, "G": st.G,
            "est": tpu_mod._nr_estimate(st, 0, budget),
            "dims": tpu_mod.solve_dims(st, NE=0, node_budget=budget),
            "before": before, "after": scrape.parse_metrics(reg.expose()),
            "spans": {s.name: dict(s.attrs) for s in trace.spans()},
        })
    return {"cfg": small, "inputs": inputs, "reg": reg, "zero": zero,
            "solves": solves,
            "rows": gen.load_catalog(small["catalog"]),
            "provs": gen.provisioners_plain(small)}


def _moved(harness, solve, axis=None):
    labels = {} if axis is None else {"axis": axis}
    metric = SCAN_SLOT_RETRIES if axis is None else SCAN_AXIS
    return harness["scrape"].delta(solve["before"], solve["after"], metric,
                                   **labels)


@pytest.mark.parametrize("k", range(len(SEEDS)), ids=[str(s) for s in SEEDS])
def test_one_namespace_against_the_plain_reference(harness, namespace, k):
    plainref = harness["plainref"]
    solve = namespace["solves"][k]
    assert sum(len(g["pods"]) for g in solve["groups"]) == 3_000
    assert len(solve["groups"]) == 328 == solve["G"]
    assert not solve["res"].infeasible and not solve["res"].served_cold
    verdict = plainref.compare(
        [(solve["groups"], plainref.Answer.of_result(solve["res"]))],
        namespace["provs"], namespace["rows"]["types"],
        namespace["rows"]["zones"],
        float(namespace["cfg"]["guarantees"]["cost_ceiling"]), unanswered=0)
    numbers = verdict["numbers"]
    assert numbers["unplaced"][0] == 0 and numbers["violations"][0] == 0, (
        verdict["first_violations"])
    assert numbers["cost_ratio_max"][0] <= 1.02
    assert verdict["correct"] is True


@pytest.mark.parametrize("k", range(len(SEEDS)), ids=[str(s) for s in SEEDS])
def test_the_estimate_holds_the_slots_the_scan_opens(harness, namespace, k):
    """What has to fit in ``NR`` is what the SCAN opens, not what the answer
    keeps after ``coalesce``: about 1.6 slots a deployment here, where the
    answer holds ~50 nodes.  The estimate stays between that and twice that,
    so no request runs a second time at the full budget."""
    solve = namespace["solves"][k]
    used = _moved(harness, solve, "nodes_used")
    assert len(solve["res"].nodes) < used / 4  # coalesce merged most of them
    assert used <= solve["est"] <= 2.0 * used, (used, solve["est"])
    assert solve["dims"]["NR"] == 1_024 and solve["dims"]["G"] == 432
    assert _moved(harness, solve) == 0  # no slot retry
    assert _moved(harness, solve, "node_slots") == solve["dims"]["NR"]


@pytest.mark.parametrize("config,G,G_pad,NR", [
    ("c2-50k-3az", 20, 32, 1_536),
    ("c3-10k-antiaffinity", 100, 112, 512),
    (CONFIG, 1_640, 2_240, 4_608),
])
def test_the_rungs_of_the_benchmarks_configurations(harness, config, G,
                                                    G_pad, NR):
    """c2's and c3's node-slot rungs are the ledger's; the long tail's is
    sized for one node a tiny deployment, which is what its scan opens."""
    gen = harness["gen"]
    full = gen.load_config(config)
    inputs = gen.ProgramInputs(full)
    nrs = set()
    for cluster in gen.burst_pool(full, 2):
        st = tensorize(inputs.pods(cluster.groups), inputs.provisioners,
                       inputs.catalog)
        dims = tpu_mod.solve_dims(
            st, NE=0, node_budget=tpu_mod._node_budget(st, 0, None))
        assert (st.G, dims["G"]) == (G, G_pad)
        nrs.add(dims["NR"])
    assert nrs == {NR}


def test_coalesce_keeps_the_order_a_full_sort_gives(namespace, monkeypatch):
    """The scan opens ~530 slots here and ``coalesce`` merges them into ~50
    nodes, hundreds of merges a bucket.  It keeps each bucket's order as a
    sorted list of keys and puts a merged node in at a bisection; the
    reference is the loop of before PR 28 (``tests/coalesce_reference.py``)
    made to sort the whole bucket again after every merge — and an absorbed
    name has to lead to the node that finally holds its pods."""
    import coalesce_reference
    from karpenter_tpu.solver import coalesce

    seen = {}
    real = coalesce.coalesce_new_nodes

    def capture(st, nodes, used_rows, node_groups=None):
        seen["args"] = coalesce_reference.twin(st, nodes, used_rows,
                                               node_groups)
        seen["got"] = real(st, nodes, used_rows, node_groups=node_groups)
        return seen["got"]

    monkeypatch.setattr(coalesce, "coalesce_new_nodes", capture)
    inputs = namespace["inputs"]
    pods = inputs.pods(namespace["solves"][1]["groups"])
    BatchScheduler("tpu", registry=Registry()).solve(
        pods, inputs.provisioners, inputs.catalog, relax=False)
    st, twins, rows, groups = seen["args"]
    assert len(twins) > 400

    def sort_again(lst, node, key):
        lst.append(node)
        lst.sort(key=key)

    monkeypatch.setattr(coalesce_reference, "insort", sort_again)
    want_nodes, want_renames = coalesce_reference.reference_coalesce(
        st, twins, rows, node_groups=groups)
    got_nodes, got_renames, _buckets = seen["got"]

    def canon(nodes):
        return sorted((n.instance_type, n.zone, n.capacity_type,
                       tuple(sorted(p.name for p in n.pods))) for n in nodes)

    assert canon(got_nodes) == canon(want_nodes)
    assert 40 < len(got_nodes) < 80 and len(got_renames) > 400
    # names differ between the two runs (a process-wide counter): compare
    # where each absorbed scan node's pods ended up
    for renames, nodes in ((got_renames, got_nodes),
                           (want_renames, want_nodes)):
        final = {n.name: n for n in nodes}
        assert set(renames.values()) <= set(final)
        assert not set(renames) & set(final)
    holder = {p.name: n.name for n in got_nodes for p in n.pods}
    for twin in twins:
        if twin.name in got_renames:
            assert {holder[p.name] for p in twin.pods} == {
                got_renames[twin.name]}


def test_both_families_exist_at_zero_before_the_first_solve(namespace):
    zero = namespace["zero"]
    assert zero["axes"] == {a: (True, 0) for a in SCAN_AXES}
    assert zero["retries"] == (True, 0)
    for axis in SCAN_AXES:
        assert f'{SCAN_AXIS}{{axis="{axis}"}} 0' in zero["text"]
    assert f"{SCAN_SLOT_RETRIES} 0" in zero["text"]


@pytest.mark.parametrize("k", range(len(SEEDS)), ids=[str(s) for s in SEEDS])
def test_a_solve_raises_the_axes_by_the_dims_it_ran_at(harness, namespace, k):
    solve = namespace["solves"][k]
    moved = {a: _moved(harness, solve, a) for a in SCAN_AXES}
    assert moved["groups"] == 328 and moved["groups_padded"] == 432
    # the rung is the shape the program was compiled at; it stopped after
    # the last group that has pods
    assert moved["steps_run"] == 328
    assert moved["node_slots"] == 1_024
    assert 328 < moved["nodes_used"] < 1_024
    # the span that fenced the scan says the same, and the selector axis
    fenced = solve["spans"].get("device_execute") or solve["spans"][
        "device_fence"]
    assert {a: fenced[a] for a in SCAN_AXES} == moved
    assert fenced["S"] == solve["dims"]["S"]


def test_the_relax_span_says_what_the_rung_ran_over(namespace):
    """The rung lifts the pods of unconstrained deployments that sit on nodes
    no constrained pod shares: its span says how many deployments and pods
    that is (here a few of the 300 five-pod deployments, never a spread
    one)."""
    from karpenter_tpu.solver import relax

    inputs = namespace["inputs"]
    pods = inputs.pods(namespace["solves"][0]["groups"])
    st = tensorize(pods, inputs.provisioners, inputs.catalog)
    scan = BatchScheduler("tpu", registry=Registry()).solve(
        pods, inputs.provisioners, inputs.catalog, relax=False)
    trace = Tracer(registry=Registry()).start("solve")
    with trace:
        relax.refine(scan, st, registry=Registry(), trace=trace)
    attrs = {s.name: dict(s.attrs) for s in trace.spans()}["relax"]
    assert 0 < attrs["groups"] <= 300
    assert attrs["groups"] <= attrs["eligible_pods"] <= 5 * attrs["groups"]
    assert attrs["outcome"] in ("improved", "tied", "fallback")


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_benchmarks_metric_file_reads_a_real_scrape(harness, namespace,
                                                       name):
    """``benchmarks/metrics/<name>.json`` is data: it names the family and
    the label by hand.  Read real scrapes of real solves through the
    benchmark's own reader, so that a renamed family or label cannot turn
    the metric into a silent 0.0 — which is what a program WITHOUT the
    family reads (the parent, measured with these files laid over it)."""
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reader = _load(os.path.join(BENCH, "readers", f"{spec['reader']}.py"),
                   f"reader_{name}")
    first, last = namespace["solves"][0], namespace["solves"][-1]
    ctx = {"before": first["before"], "after": last["after"],
           "requests": len(SEEDS)}
    got = reader.read(ctx, **spec["args"])
    family = spec["args"]["metric"]
    assert any(s[0] == family for s in ctx["after"]), family
    axis = NEW_METRICS[name]
    if axis is None:
        assert family == SCAN_SLOT_RETRIES and got == 0.0
    else:
        assert family == SCAN_AXIS
        want = sum(_moved(harness, s, axis)
                   for s in namespace["solves"]) / len(SEEDS)
        assert got == want > 0
    # ... and what a program with the family but not this label reads (the
    # parent of the PR that adds an axis)
    for without in ([s for s in ctx["after"] if s[0] != family],
                    [s for s in ctx["after"]
                     if s[0] != family or s[1].get("axis") != axis]):
        assert len(without) < len(ctx["after"])
        assert reader.read({**ctx, "before": without, "after": without},
                           **spec["args"]) == 0.0
