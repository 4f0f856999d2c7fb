"""``coalesce_new_nodes`` against the loop it replaced (PR 28).

The merge pass is a strictly sequential greedy — first feasible pair of the
window, merge, put back in order, look again — so "faster" may only mean
that each merge costs less: the merges, their order, the names drawn for
them and the nodes that come out have to be those of the old loop
(``tests/coalesce_reference.py``, its body verbatim) on the same input.

(a) the equivalence, over inputs captured from real solves on the CPU: a
    namespace of the long tail (plain order, hundreds of merges), a c3-shaped
    batch (hostname anti-affinity, two provisioners: the capped order), a
    provisioner with limits (the capacity term), an untracked solve, buckets
    of one node, and both orders again through a window of 8 nodes, where
    nodes slide in and out of it at every merge;
(b) the ``coalesce`` span and ``karpenter_solver_coalesce_total``, and the
    benchmark's two metric files read over real scrapes.
"""

import copy
import json
import os

import pytest

import coalesce_reference
from coalesce_reference import reference_coalesce, twin
from karpenter_tpu.metrics import COALESCE, COALESCE_WHAT, Registry
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.solver import coalesce, types
from karpenter_tpu.solver.scheduler import BatchScheduler
from test_longtail_config import BENCH, ROOT, _load, harness  # noqa: F401

#: this PR's per-layer metrics
NEW_METRICS = ("coalesce_ms", "coalesce_merges")


def _solve_and_capture(monkeypatch_ctx, pods, provisioners, catalog):
    """What ``_extract`` handed the merge pass in one real solve (the device
    tier on the CPU), and what the registry and the tracer said of it."""
    seen = []
    real = coalesce.coalesce_new_nodes

    def capture(st, nodes, used_rows, node_groups=None):
        seen.append(twin(st, nodes, used_rows, node_groups))
        seen.append(real(st, nodes, used_rows, node_groups=node_groups))
        return seen[-1]

    reg = Registry()
    tracer = Tracer(registry=reg)
    sched = BatchScheduler("tpu", registry=reg, tracer=tracer)
    zero = {w: (reg.counter(COALESCE).has({"what": w}),
                reg.counter(COALESCE).get({"what": w}))
            for w in COALESCE_WHAT}
    zero["text"] = reg.expose()
    monkeypatch_ctx.setattr(coalesce, "coalesce_new_nodes", capture)
    trace = tracer.start("solve")
    with trace:
        res = sched.solve(pods, provisioners, catalog, relax=False,
                          trace=trace)
    monkeypatch_ctx.undo()
    assert not res.infeasible and not res.served_cold
    return {"args": seen[-2], "returned": seen[-1], "calls": len(seen) // 2,
            "res": res, "reg": reg, "zero": zero, "trace": trace}


@pytest.fixture(scope="module")
def longtail(harness):
    """One namespace of ``longtail-15k`` (3,000 pods in 328 deployments, a
    fifth of the configuration): the scan opens ~530 nodes of one small
    deployment each, in three buckets — the plain order."""
    gen = harness["gen"]
    cfg = copy.deepcopy(gen.load_config("longtail-15k"))
    for t in cfg["deployments"]:
        t["count"] //= 5
    inputs = gen.ProgramInputs(cfg)
    groups = gen.salted(gen.burst_pool(cfg, 1)[0], 2 ** 31 + 4099).groups
    with pytest.MonkeyPatch.context() as mp:
        got = _solve_and_capture(mp, inputs.pods(groups), inputs.provisioners,
                                 inputs.catalog)
    return {**got, "inputs": inputs, "groups": groups}


@pytest.fixture(scope="module")
def c3_shaped(harness):
    """24 services of 30 +-10 % pods with hostname anti-affinity on their own
    selector, every second one tolerating the dedicated provisioner's taint:
    c3 at a fourteenth — the hostname-capped order."""
    gen = harness["gen"]
    cfg = copy.deepcopy(gen.load_config("c3-10k-antiaffinity"))
    (t,) = cfg["deployments"]
    t["count"], t["replicas"] = 24, 30
    inputs = gen.ProgramInputs(cfg)
    assert len(inputs.provisioners) == 2
    groups = gen.salted(gen.burst_pool(cfg, 1)[0], 977).groups
    assert {g["constraint"] for g in groups} == {"hostname_anti_affinity"}
    assert sum(len(g["pods"]) for g in groups) == 720
    with pytest.MonkeyPatch.context() as mp:
        return _solve_and_capture(mp, inputs.pods(groups), inputs.provisioners,
                                  inputs.catalog)


@pytest.fixture(scope="module")
def limited(longtail):
    """The same namespace under a provisioner with finite limits: a merge
    may not raise the raw capacity it replaces."""
    inputs = longtail["inputs"]
    prov = Provisioner(name="default", limits={"cpu": 4_000.0,
                                               "memory": 2.0 ** 45}
                       ).with_defaults()
    with pytest.MonkeyPatch.context() as mp:
        return _solve_and_capture(mp, inputs.pods(longtail["groups"]), [prov],
                                  inputs.catalog)


@pytest.fixture(scope="module")
def selective(longtail):
    """The same namespace with two deployments in five pinned by node
    selector, to one category or to one family: the candidates differ in
    which groups admit them, and a merged node has to suit every group on
    it."""
    from karpenter_tpu.models import labels as L

    pins = {1: {L.INSTANCE_CATEGORY: "m"}, 3: {L.INSTANCE_FAMILY: "c5"}}
    inputs = longtail["inputs"]
    pods = []
    for gi, g in enumerate(longtail["groups"]):
        for pod in inputs.pods([g]):
            if gi % 5 in pins:
                pod.node_selector = dict(pins[gi % 5])
            pods.append(pod)
    with pytest.MonkeyPatch.context() as mp:
        return _solve_and_capture(mp, pods, inputs.provisioners,
                                  inputs.catalog)


def _untracked(args):
    st, nodes, rows, _groups = args
    return st, nodes, rows, None


def _one_node_buckets(args):
    """The first node of every bucket: nothing to pair it with."""
    st, nodes, rows, groups = args
    first = {}
    for n in nodes:
        first.setdefault((n.provisioner, n.zone, n.capacity_type), n)
    return st, list(first.values()), rows, groups


#: case -> (fixture, what to make of its captured input, window, merges)
CASES = {
    "longtail-plain-order": ("longtail", None, None, (400, 600)),
    "c3-shaped-capped-order": ("c3_shaped", None, None, (50, 200)),
    "limited-provisioner": ("limited", None, None, (5, 600)),
    "node-selectors": ("selective", None, None, (200, 600)),
    "node-selectors-untracked": ("selective", _untracked, None, (0, 0)),
    "untracked": ("longtail", _untracked, None, (400, 600)),
    "one-node-buckets": ("longtail", _one_node_buckets, None, (0, 0)),
    "one-node": ("c3_shaped", lambda a: (a[0], a[1][:1], a[2], a[3]), None,
                 (0, 0)),
    "longtail-window-of-8": ("longtail", None, 8, (300, 600)),
    "c3-shaped-window-of-8": ("c3_shaped", None, 8, (20, 200)),
    "c3-shaped-window-of-2": ("c3_shaped", None, 2, (1, 600)),
}


def _canon(nodes):
    return sorted((n.instance_type, n.zone, n.capacity_type, n.price,
                   tuple(sorted(p.name for p in n.pods))) for n in nodes)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_new_loop_makes_the_old_loops_merges(case, request, monkeypatch):
    fixture, reshape, window, (lo, hi) = CASES[case]
    args = request.getfixturevalue(fixture)["args"]
    if reshape is not None:
        args = reshape(args)
    if window is not None:
        monkeypatch.setattr(coalesce, "FRAG_WINDOW", window)
        monkeypatch.setattr(coalesce_reference, "FRAG_WINDOW", window)
    # names are a tie-break of the order: both loops draw from one start
    start = types._node_next
    st, nodes, rows, groups = twin(*args)
    want, want_renames = reference_coalesce(st, nodes, rows,
                                            node_groups=groups)
    drawn = types._node_next - start
    monkeypatch.setattr(types, "_node_next", start)
    st, nodes, rows, groups = twin(*args)
    got, got_renames, buckets = coalesce.coalesce_new_nodes(
        st, nodes, rows, node_groups=groups)
    merges = len(nodes) - len(got)
    assert lo <= merges <= hi, merges
    assert merges == len(nodes) - len(want) == drawn
    assert types._node_next - start == drawn  # one name a merge, no more
    assert buckets == len({(n.provisioner, n.zone, n.capacity_type)
                           for n in nodes})
    assert _canon(got) == _canon(want)
    # with the names drawn from one start the answers are equal as they
    # stand: the nodes in their order, and where every absorbed name leads
    assert [n.name for n in got] == [n.name for n in want]
    assert got_renames == want_renames
    assert list(got_renames) == list(want_renames)
    assert len(got_renames) == (2 * merges if merges else 0)
    holder = {p.name: n.name for n in got for p in n.pods}
    final = {n.name for n in got}
    assert set(got_renames.values()) <= final
    assert not set(got_renames) & final
    for node in args[1]:
        at = got_renames.get(node.name, node.name)
        assert {holder[p.name] for p in node.pods} == {at}
    for node in got:
        assert node.labels["kubernetes.io/hostname"] == node.name
        assert not node.existing


def test_the_capped_order_had_something_to_hold(c3_shaped):
    """The c3-shaped batch is a capped solve with two buckets at least, and
    no node of its answer holds two pods of one service."""
    st, nodes, _rows, groups = c3_shaped["args"]
    assert (st.g_host_spread >= 0).all() and groups is not None
    assert len({n.provisioner for n in nodes}) == 2
    for node in c3_shaped["res"].nodes:
        apps = [p.labels["app"] for p in node.pods]
        assert len(apps) == len(set(apps))


def test_the_limited_bucket_took_the_capacity_term(limited):
    st = limited["args"][0]
    assert (st.prov_limits < coalesce._NO_LIMIT).any()


def test_the_selectors_split_the_candidates(selective):
    """Some candidate suits one pinned deployment and not another, so the
    label term decides merges here; every pinned pod sits on a node of its
    category or family."""
    from karpenter_tpu.models import labels as L

    F = coalesce.label_feasibility(selective["args"][0])
    assert len({row.tobytes() for row in F}) >= 3
    by_name = {n.name: n for n in selective["res"].nodes}
    pinned = 0
    for pod_name, node_name in selective["res"].assignments.items():
        node = by_name[node_name]
        pod = next(p for p in node.pods if p.name == pod_name)
        for key, value in pod.node_selector.items():
            pinned += 1
            kind = node.instance_type.split(".")[0]
            assert (kind if key == L.INSTANCE_FAMILY else kind[0]) == value
    assert pinned > 1_000


# ---- (b) the span, the counter family and the benchmark's metric files -----


@pytest.mark.parametrize("fixture", ["longtail", "c3_shaped", "limited"])
def test_the_family_exists_at_zero_before_the_first_solve(fixture, request):
    zero = request.getfixturevalue(fixture)["zero"]
    for what in COALESCE_WHAT:
        assert zero[what] == (True, 0)
        assert f'{COALESCE}{{what="{what}"}} 0' in zero["text"]


@pytest.mark.parametrize("fixture", ["longtail", "c3_shaped", "limited"])
def test_a_solve_raises_the_family_by_what_the_pass_returned(fixture, request):
    solve = request.getfixturevalue(fixture)
    assert solve["calls"] == 1
    nodes_in = len(solve["args"][1])
    out, renames, buckets = solve["returned"]
    counter = solve["reg"].counter(COALESCE)
    assert counter.get({"what": "nodes_in"}) == nodes_in
    assert counter.get({"what": "merges"}) == nodes_in - len(out) > 0
    assert len(renames) == 2 * (nodes_in - len(out))
    assert len(solve["res"].nodes) <= len(out)  # reseat may empty a node
    # the span sits inside `extract` and says the same
    spans = {s.name: s for s in solve["trace"].spans()}
    assert "coalesce" in [c.name for c in spans["extract"].children]
    assert dict(spans["coalesce"].attrs) == {
        "nodes_in": nodes_in, "nodes_out": len(out),
        "merges": nodes_in - len(out), "buckets": buckets}
    assert 0 < spans["coalesce"].duration_s <= spans["extract"].duration_s


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_is_declared_as_its_file_says(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended after what PR 27 left, in this order (later PRs append on)
    assert names.index(name) == names.index(
        "scan_slot_retries") + 1 + NEW_METRICS.index(name)
    decl = bench["per_layer"][names.index(name)]
    assert decl["name"] == spec["name"] == name
    assert "workloads" not in decl  # every cell reports it
    for key in ("unit", "better", "source", "layer", "moves"):
        assert decl[key] == spec[key]
    assert (decl["layer"], decl["moves"], decl["better"]) == (
        "host epilogues", "solve_ms", "lower")
    assert decl["source"] == {"coalesce_ms": "program_span",
                              "coalesce_merges": "program_counter"}[name]
    assert spec["reader"] == "counter_per_request"
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       f"{spec['reader']}.py"))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_benchmarks_metric_file_reads_a_real_scrape(harness, longtail,
                                                       name):
    """``benchmarks/metrics/<name>.json`` names the family and its label by
    hand: read a real scrape through the benchmark's own reader, so that a
    renamed span or label cannot turn the metric into the silent 0.0 that a
    program WITHOUT them reads (the parent, under these files)."""
    scrape = harness["scrape"]
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reader = _load(os.path.join(BENCH, "readers", f"{spec['reader']}.py"),
                   f"reader_{name}")
    after = scrape.parse_metrics(longtail["reg"].expose())
    before = scrape.parse_metrics(longtail["zero"]["text"])
    ctx = {"before": before, "after": after, "requests": 1}
    got = reader.read(ctx, **spec["args"])
    family = spec["args"]["metric"]
    assert any(s[0] == family for s in after), family
    if name == "coalesce_merges":
        assert family == COALESCE
        assert got == len(longtail["args"][1]) - len(longtail["returned"][0])
    else:
        span = {s.name: s for s in longtail["trace"].spans()}["coalesce"]
        assert got == pytest.approx(span.duration_s * 1000.0, abs=2e-3)
    assert got > 0
    without = [s for s in after if s[0] != family]
    assert reader.read({**ctx, "before": without, "after": without},
                       **spec["args"]) == 0.0
