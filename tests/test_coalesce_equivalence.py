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
    nodes slide in and out of it at every merge; a capped bucket built by
    hand in which a node is pushed out of the window and let back in among
    nodes that entered meanwhile, and a bucket where nothing merges (PR 36:
    the pass computes a pair's verdict when the walk reaches it);
(b) the ``coalesce`` span and ``karpenter_solver_coalesce_total``, and the
    benchmark's metric files read over real scrapes;
(c) the two callers of ``apply_coalesce`` hand it the same kind of input.
"""

import copy
import json
import os

import numpy as np
import pytest

import coalesce_reference
from coalesce_reference import reference_coalesce, twin
from karpenter_tpu.metrics import COALESCE, COALESCE_WHAT, Registry
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.solver import coalesce, native, types
from karpenter_tpu.solver.scheduler import BatchScheduler
from test_longtail_config import BENCH, ROOT, _load, harness  # noqa: F401

#: PR 28's per-layer metrics
NEW_METRICS = ("coalesce_ms", "coalesce_merges")
#: PR 36's: the pair verdicts the pass computed
PAIRS = "coalesce_pairs"


def _twin(st, nodes, used_rows, node_groups):
    """``twin`` for the pass under test: the reference reads which groups a
    node holds, the pass how many pods of each (``twin`` keeps the groups)."""
    st, twins, rows, _groups = twin(st, nodes, used_rows, node_groups)
    return (st, twins, rows, None if node_groups is None else {
        id(t): dict(node_groups[id(n)])
        for n, t in zip(nodes, twins) if id(n) in node_groups})


def _solve_and_capture(monkeypatch_ctx, pods, provisioners, catalog):
    """What ``_extract`` handed the merge pass in one real solve (the device
    tier on the CPU), and what the registry and the tracer said of it."""
    seen = []
    real = coalesce.coalesce_new_nodes

    def capture(st, nodes, used_rows, node_groups=None):
        seen.append(_twin(st, nodes, used_rows, node_groups))
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


@pytest.fixture(scope="module")
def pushed_out(c3_shaped):
    """Five nodes of one bucket on the c3-shaped tensors, one pod each of
    services A, B and C (hostname anti-affinity: a node holds one pod of a
    service), sized so that through a window of THREE the capped order reads

        [a1 b1 c1] a2 b2   ->  a1 + b1 = m; a2 and b2 move up a rank:
        [a2 b2 m] c1       ->  c1 is pushed out, m enters; a2 + b2 = m2:
        [m c1 m2]          ->  c1 is back, beside an m it was never asked about

    (m, c1) fits one node and (m, m2) shares services, so a pass that asked
    every pair of the window would merge m + c1; the rule asks a pair only
    when the later of the two ENTERS beside the other, so it is c1 + m2."""
    st, nodes, _rows, _groups = c3_shaped["args"]
    like = next(n for n in nodes if n.provisioner == "default")
    whole = np.array([like.allocatable[r] for r in st.vocab.resources])
    picked, rows, held = [], {}, {}
    for name, g, share in (("a1", 0, .10), ("b1", 1, .15), ("c1", 2, .30),
                           ("a2", 0, .20), ("b2", 1, .22)):
        node = copy.copy(like)  # its type, its zone, its price
        node.name = f"built-{name}"
        node.pods = [st.groups[g].pods[int(name[1]) - 1]]
        picked.append(node)
        rows[id(node)] = whole * share
        held[id(node)] = {g: 1}
    return {"args": (st, picked, rows, held)}


@pytest.fixture(scope="module")
def full_nodes(longtail):
    """The long tail's nodes, every one of them as full as the largest type
    there is: no two fit one node."""
    st, nodes, rows, groups = longtail["args"]
    full = np.asarray(st.cand_alloc, dtype=np.float64).max(axis=0)
    return {"args": (st, nodes, {key: full.copy() for key in rows}, groups)}


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
    "capped-pushed-out-and-back": ("pushed_out", None, 3, (3, 3)),
    "capped-never-pushed-out": ("pushed_out", None, None, (3, 3)),
    "nothing-merges": ("full_nodes", None, None, (0, 0)),
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
    st, nodes, rows, groups = _twin(*args)
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


def _pods_together(args, window, monkeypatch):
    monkeypatch.setattr(coalesce, "FRAG_WINDOW", window)
    st, nodes, rows, groups = _twin(*args)
    got = coalesce.coalesce_new_nodes(st, nodes, rows, node_groups=groups)
    return got, sorted(sorted(p.name for p in n.pods) for n in got[0])


def test_a_node_let_back_in_is_not_asked_about_who_came_meanwhile(
        pushed_out, monkeypatch):
    """The trap of a pass that computes verdicts on demand: the window of 3
    holds (m, c1) ahead of (c1, m2), and both can merge — a window of 64,
    which pushes nothing out, merges m + c1.  Through the window of 3 the
    pair was never askable, and the merge is c1 + m2."""
    args = pushed_out["args"]
    a1, b1, c1, a2, b2 = ([p.name for p in n.pods] for n in args[1])
    _got, wide = _pods_together(args, 64, monkeypatch)
    assert wide == sorted([sorted(a1 + b1 + c1), sorted(a2 + b2)])
    got, narrow = _pods_together(args, 3, monkeypatch)
    assert narrow == sorted([sorted(a1 + b1), sorted(c1 + a2 + b2)])
    # (a1, b1), (a2, b2), (m, m2) and (c1, m2) at the least; never all ten
    assert 4 <= got.pairs < 10


def test_a_bucket_where_nothing_merges_asks_each_pair_of_its_window_once(
        full_nodes):
    """What the eager rule asked of such a bucket — every pair of its first
    window, once — is what the walk comes to, and the pass hands back the
    nodes it was given."""
    st, nodes, rows, groups = full_nodes["args"]
    sizes = {}
    for n in nodes:
        key = (n.provisioner, n.zone, n.capacity_type)
        sizes[key] = sizes.get(key, 0) + 1
    assert max(sizes.values()) > coalesce.FRAG_WINDOW
    got = coalesce.coalesce_new_nodes(st, nodes, rows, node_groups=groups)
    assert got[1] == {} and got[2] == len(sizes)
    assert sorted(map(id, got[0])) == sorted(map(id, nodes))
    assert got.pairs == sum(w * (w - 1) // 2 for w in (
        min(n, coalesce.FRAG_WINDOW) for n in sizes.values()))


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
    pairs = solve["returned"].pairs
    assert dict(spans["coalesce"].attrs) == {
        "nodes_in": nodes_in, "nodes_out": len(out),
        "merges": nodes_in - len(out), "buckets": buckets, "pairs": pairs}
    # the work count: raised by what the span says, and a pass that merges
    # asked at least one pair a merge
    assert counter.get({"what": "pairs"}) == pairs >= nodes_in - len(out)
    assert 0 < spans["coalesce"].duration_s <= spans["extract"].duration_s


def test_the_long_tail_asks_a_handful_of_pairs_a_merge(longtail, c3_shaped):
    """The work-count guard (no clock in it): the eager rule asked every pair
    a node new to the window formed, ~126 a merge; the walk asks the pairs
    it reaches.  Where nine pairs in ten cannot merge (the capped batch) it
    reaches more of them, and still not the window."""
    for solve, most in ((longtail, 16), (c3_shaped, 64)):
        merges = len(solve["args"][1]) - len(solve["returned"][0])
        assert merges <= solve["returned"].pairs < most * merges


# ---- (c) one signature, both callers ---------------------------------------


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_both_tiers_hand_the_pass_the_same_kind_of_input(c3_shaped,
                                                         monkeypatch):
    """``TpuSolver._extract`` and ``native.solve_tensors_native`` feed one
    ``apply_coalesce``: on one set of tensors (hostname caps: the counts
    matter) each hands it, per node, what the node has in use and how many
    pods of which group it holds, and each answer keeps the caps and says
    where every pod ended up."""
    st = c3_shaped["args"][0]
    seen = []
    real = coalesce.coalesce_new_nodes

    def capture(st, nodes, used_rows, node_groups=None):
        seen.append(_twin(st, nodes, used_rows, node_groups))
        return real(st, nodes, used_rows, node_groups=node_groups)

    monkeypatch.setattr(coalesce, "coalesce_new_nodes", capture)
    cold = native.solve_tensors_native(st)
    assert not cold.infeasible
    requests = np.asarray(st.requests, dtype=np.float64)
    group_of = {p.name: g for g, grp in enumerate(st.groups) for p in grp.pods}
    for _st, nodes, rows, groups in (seen[0], c3_shaped["args"]):
        assert len(nodes) > 100
        for node in nodes:
            held = groups[id(node)]
            assert held == {g: sum(group_of[p.name] == g for p in node.pods)
                            for g in held}
            assert sum(held.values()) == len(node.pods) > 0
            np.testing.assert_allclose(
                rows[id(node)],
                sum(n * requests[g] for g, n in held.items()), rtol=1e-5)
    for res in (cold, c3_shaped["res"]):
        assert len(res.nodes) < len(seen[0][1])  # the pass merged
        by_name = {n.name: n for n in res.nodes}
        assert len(res.assignments) == 720
        for pod_name, node_name in res.assignments.items():
            assert pod_name in {p.name for p in by_name[node_name].pods}
        for node in res.nodes:
            apps = [p.labels["app"] for p in node.pods]
            assert len(apps) == len(set(apps))


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


# ---- PR 36: the pair verdicts the pass computed -----------------------------


def test_coalesce_pairs_is_declared_as_its_file_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "metrics", f"{PAIRS}.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended after what PR 33 left (later PRs append on): nothing moved
    assert names.index(PAIRS) == names.index("decode_catalog_held") + 1
    decl = bench["per_layer"][names.index(PAIRS)]
    assert decl == {k: spec[k] for k in ("name", "unit", "better", "source",
                                         "layer", "moves")}
    assert decl == {"name": PAIRS, "unit": "pairs", "better": "lower",
                    "source": "program_counter", "layer": "host epilogues",
                    "moves": "solve_ms"}  # no `workloads`: every cell
    assert names.count(PAIRS) == 1
    assert spec["reader"] == "counter_per_request"
    assert spec["args"] == {"metric": COALESCE, "labels": [{"what": "pairs"}]}
    assert "pairs" in COALESCE_WHAT


def test_coalesce_pairs_reads_a_real_scrape(harness, longtail):
    """Through the benchmark's own reader the file reads what the span says;
    a program without the label (the parent, under this file) reads 0.0."""
    scrape = harness["scrape"]
    with open(os.path.join(BENCH, "metrics", f"{PAIRS}.json")) as f:
        spec = json.load(f)
    reader = _load(os.path.join(BENCH, "readers", f"{spec['reader']}.py"),
                   f"reader_{PAIRS}")
    after = scrape.parse_metrics(longtail["reg"].expose())
    before = scrape.parse_metrics(longtail["zero"]["text"])
    ctx = {"before": before, "after": after, "requests": 1}
    span = {s.name: s for s in longtail["trace"].spans()}["coalesce"]
    got = reader.read(ctx, **spec["args"])
    assert got == dict(span.attrs)["pairs"] == longtail["returned"].pairs > 0
    without = [s for s in after if s[1].get("what") != "pairs"]
    assert len(without) == len(after) - 1  # the parent: family, no label
    assert reader.read({**ctx, "before": without, "after": without},
                       **spec["args"]) == 0.0
    assert reader.read({**ctx, "before": [], "after": []},
                       **spec["args"]) == 0.0
