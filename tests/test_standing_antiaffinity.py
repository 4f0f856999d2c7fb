"""One-per-host services on dedicated capacity standing under one session:
a node a solver buys carries its provisioner's taints and labels
on every backend and out of every builder (the scan's extraction, the native
tier, the oracle, the merge pass, the relax rung's rounding), so a later step
that meets it as an existing node keeps pods that do not tolerate it off it;
c3's ``reconcile`` stream places every pod validly in every step; and a
served session's step names the existing-node seed in a span and a counter.

(a) fails for the device path where bought nodes come out with no taints:
the next step's seed then opens them to every pod."""

import json
import os

import harness_path
import numpy as np
import pytest
import run
import scrape

from karpenter_tpu.metrics import (
    SCAN_EXISTING,
    SCAN_EXISTING_WHAT,
    SOLVER_COLD_FALLBACKS,
    Registry,
)
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    Taint,
    Toleration,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.service.client import DeltaSession
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver import coalesce, relax
from karpenter_tpu.solver.scheduler import BatchScheduler

bench = harness_path.bench  # the harness's fixture
BENCH_DIR = os.path.dirname(harness_path.HARNESS_TESTS)
GIB = float(2 ** 30)
TAINT = Taint(key="dedicated", value="svc", effect="NoSchedule")
TEAM = {"team": "svc"}


def provisioners():
    """c3's two: the tainted one first by weight, with a label of its own."""
    return [
        Provisioner(name="dedicated", weight=10, taints=[TAINT],
                    labels=dict(TEAM)).with_defaults(),
        Provisioner(name="default", weight=5).with_defaults(),
    ]


def svc_pod(name: str, app: str, tolerate: bool, cpu: float = 0.5) -> PodSpec:
    """A pod of a one-per-host service, as c3's deployments make them."""
    return PodSpec(
        name=name, labels={"app": app}, owner_key=app,
        requests={"cpu": cpu, "memory": 1.0 * GIB},
        tolerations=[Toleration(key="dedicated", operator="Equal",
                                value="svc", effect="NoSchedule")]
        if tolerate else [],
        affinity_terms=[PodAffinityTerm(LabelSelector.of({"app": app}),
                                        L.HOSTNAME, anti=True)])


def carries_its_provisioners(node, provs) -> bool:
    prov = next(p for p in provs if p.name == node.provisioner)
    return (node.taints == list(prov.taints)
            and all(node.labels.get(k) == v for k, v in prov.labels.items())
            and node.labels[L.HOSTNAME] == node.name
            and node.labels[L.PROVISIONER_NAME] == prov.name)


# ---- (a) every builder stamps the provisioner; the next step respects it --


@pytest.mark.parametrize("backend", ["tpu", "native", "oracle"])
def test_a_bought_node_keeps_a_non_tolerating_service_off_it(
        backend, small_catalog):
    """Forty tolerating pods buy forty dedicated nodes with room to spare
    (a batch that size keeps the device tier's own nodes: four would come
    back from the reseat's oracle); a step that adds four pods of a service
    without the toleration seats none of them there (a device scan or native
    tier whose nodes drop their taints puts all four on the dedicated
    nodes)."""
    provs = provisioners()
    sched = BatchScheduler(backend, compile_behind=False, registry=Registry())
    res = sched.solve([svc_pod(f"t-{i}", "t", True) for i in range(40)],
                      provs, small_catalog)
    assert res.infeasible == {}
    dedicated = {n.name for n in res.nodes if n.provisioner == "dedicated"}
    assert len(dedicated) == 40
    assert all(carries_its_provisioners(n, provs) for n in res.nodes)
    out = sched.solve_delta(res, [svc_pod(f"u-{i}", "u", False)
                                  for i in range(4)],
                            provisioners=provs, instance_types=small_catalog)
    got = out.result
    assert got.infeasible == {}
    seats = {got.assignments[f"u-{i}"] for i in range(4)}
    assert not seats & dedicated, (out.mode, seats)
    assert all(carries_its_provisioners(n, provs) for n in got.nodes)


def test_the_merge_pass_stamps_the_node_it_makes(small_catalog, monkeypatch):
    """Tiny tolerating deployments, a node each out of the scan: the nodes
    the merge pass makes of them are the dedicated provisioner's."""
    provs = provisioners()
    made = []
    real = coalesce.coalesce_new_nodes

    def spy(st, nodes, used_rows, node_groups=None):
        done = real(st, nodes, used_rows, node_groups=node_groups)
        before = {id(n) for n in nodes}
        made.extend(n for n in done[0] if id(n) not in before)
        return done

    monkeypatch.setattr(coalesce, "coalesce_new_nodes", spy)
    pods = [PodSpec(name=f"tiny{d}-0", labels={"app": f"tiny{d}"},
                    owner_key=f"tiny{d}", requests={"cpu": 0.1 * (d + 1)},
                    tolerations=list(svc_pod("x", "x", True).tolerations))
            for d in range(6)]
    sched = BatchScheduler("tpu", compile_behind=False, registry=Registry())
    res = sched.solve(pods, provs, small_catalog, relax=False)
    assert res.infeasible == {}
    assert made and {n.provisioner for n in made} == {"dedicated"}
    assert all(carries_its_provisioners(n, provs) for n in made)


def test_the_relax_rounding_stamps_the_nodes_it_buys(small_catalog):
    """``relax._materialize`` over a rounding that bought one node of each
    provisioner's cheapest candidate."""
    provs = provisioners()
    st = tensorize([svc_pod("t-0", "t", True)], provs, small_catalog)
    rounding = relax._Rounding(st, np.zeros_like(st.prov_limits,
                                                 dtype=np.float64))
    for name in ("dedicated", "default"):
        pi = st.prov_names.index(name)
        ci = next(ci for ci in range(st.C) if st.cand_prov[ci] == pi
                  and st.cand_avail[ci].any())
        rounding.buy(ci, 1, float(np.min(st.cand_price[ci])))
    nodes, assignments = relax._materialize(st, rounding, {})
    assert assignments == {}
    assert [n.provisioner for n in nodes] == ["dedicated", "default"]
    assert all(carries_its_provisioners(n, provs) for n in nodes)
    assert [n.taints for n in nodes] == [[TAINT], []]


# ---- (b) c3's standing stream, in process, one pass ----------------------

CONFIG = "standing-10k-antiaffinity"
SEED = 2 ** 31 + 4099


def test_the_standing_stream_is_valid_after_every_step():
    """``traffic/reconcile.json`` over c3's standing cluster at 200 pods, in
    process through ``solve_delta`` for one pass (60 steps), the answer
    compared with the plain reference after EVERY step: no pod left out, no
    violation (with untainted bought nodes the third step put a service
    without the toleration on the dedicated nodes its first solve bought),
    no cold tier."""
    import gen
    import plainref

    cfg, traffic = gen.load_config(CONFIG), gen.load_traffic("reconcile")
    rows = gen.load_catalog(cfg["catalog"])
    provs = gen.provisioners_plain(cfg)
    steps = gen.Steps(cfg, traffic, SEED, 0.02)
    inputs = gen.ProgramInputs(cfg)
    reg = Registry()
    sched = BatchScheduler("tpu", compile_behind=False, registry=reg)
    res = sched.solve(inputs.pods(steps.cluster.groups), inputs.provisioners,
                      inputs.catalog)
    assert res.infeasible == {}
    modes = []
    for k in range(len(steps.deck_def)):
        step = steps.next()
        added = inputs.pods(steps.cluster.groups,
                            {step["group"]: step["added"]}
                            ) if step["added"] else []
        out = sched.solve_delta(res, added, step["removed"],
                                provisioners=inputs.provisioners,
                                instance_types=inputs.catalog)
        res = out.result
        modes.append(out.mode)
        verdict = plainref.compare(
            [(steps.settle().groups, plainref.Answer.of_result(res), None)],
            provs, rows["types"], rows["zones"], 1.02, 0)
        assert verdict["correct"], (k, step["kind"], out.mode,
                                    verdict["numbers"])
    assert "scan" in modes
    assert reg.counter(SOLVER_COLD_FALLBACKS).get() == 0
    got = {w: reg.counter(SCAN_EXISTING).get({"what": w})
           for w in SCAN_EXISTING_WHAT}
    assert got["nodes"] > 0 and got["taint_closed"] > 0


# ---- (c) a served session's step: the seed's span and counter ------------

M_SUM = "karpenter_trace_span_duration_seconds_sum"


def find(node, name):
    if node["name"] == name:
        return node
    for child in node.get("spans", ()):
        got = find(child, name)
        if got is not None:
            return got
    return None


def test_a_served_step_names_the_seed_and_the_views_keep_the_taints(
        small_catalog):
    """A device-tier sidecar: forty tolerating pods establish the session,
    four pods without the toleration come in one ``scan`` step.  The step's
    ``device_prepare`` holds ``seed_existing`` and the counter family moved
    by the forty nodes seeded and the forty (group, node) pairs their taint
    closed; the client's merged view carries the provisioners' taints, and
    so does the view a forced re-establish (``full_resends``) brings."""
    provs = provisioners()
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="tpu", registry=reg,
                                       compile_behind=False), registry=reg)
    srv, port = make_server(svc, port=0)
    sess = DeltaSession(f"127.0.0.1:{port}", registry=Registry())
    try:
        zero = scrape.parse_metrics(reg.expose())
        assert [scrape.delta([], zero, SCAN_EXISTING, what=w)
                for w in SCAN_EXISTING_WHAT] == [0.0, 0.0]
        sess.solve([svc_pod(f"t-{i}", "t", True) for i in range(40)], provs,
                   small_catalog)
        before = scrape.parse_metrics(reg.expose())
        sess.solve_delta(added=[svc_pod(f"u-{i}", "u", False)
                                for i in range(4)])
        after = scrape.parse_metrics(reg.expose())
        view = sess.result()
        trees = [t if isinstance(t, dict) else t.to_dict()
                 for t in svc.tracer.flight.traces()]
        sess._epoch += 7  # the sidecar no longer knows this chain
        sess.solve_delta(added=[svc_pod("u-4", "u", False)])
        assert sess.full_resends == 2
        again = sess.result()
    finally:
        sess.close()
        srv.stop(grace=None)
        svc.close()

    def moved(metric, **labels):
        return scrape.delta(before, after, metric, **labels)

    assert moved(SCAN_EXISTING, what="nodes") == 40.0
    assert moved(SCAN_EXISTING, what="taint_closed") == 40.0
    assert moved(M_SUM, span="seed_existing") > 0.0
    # the benchmark's files read the same, the step being one request
    one = {"before": before, "after": after, "requests": 1}
    assert read_metric("scan_existing_nodes", one) == 40.0
    assert read_metric("scan_existing_taint_closed", one) == 40.0
    assert read_metric("seed_existing_ms", one) == pytest.approx(
        1000.0 * moved(M_SUM, span="seed_existing"))
    step = [t for t in trees if find(t, "delta") is not None][-1]
    assert find(step, "delta")["attrs"]["mode"] == "scan"
    seed = find(find(step, "device_prepare"), "seed_existing")
    assert seed["attrs"] == {"nodes": 40, "taint_closed": 40}
    for got in (view, again):
        assert got.infeasible == {}
        assert {n.provisioner for n in got.nodes} == {"dedicated", "default"}
        assert all(carries_its_provisioners(n, provs) for n in got.nodes)
        on = {got.assignments[p.name] for n in got.nodes for p in n.pods
              if p.name.startswith("u-")}
        assert not on & {n.name for n in got.nodes
                         if n.provisioner == "dedicated"}


# ---- the benchmark's three files for the seed ----------------------------

#: name -> unit; all "lower", layer "H2D + dispatch", moving solve_ms
SEED_METRICS = {"seed_existing_ms": "ms", "scan_existing_nodes": "nodes",
                "scan_existing_taint_closed": "pairs"}


def spec(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "metrics", f"{name}.json")) as f:
        return json.load(f)


def read_metric(name: str, ctx: dict):
    s = spec(name)
    reader = run._module(os.path.join(BENCH_DIR, "readers",
                                      f"{s['reader']}.py"),
                         f"reader_{s['reader']}")
    return reader.read(ctx, **s["args"])


@pytest.mark.parametrize("name", sorted(SEED_METRICS))
def test_a_program_without_the_family_reads_zero_and_never_raises(name):
    """A program without the family is traced with these files too."""
    other = [("karpenter_solver_cold_start_fallbacks_total", {}, 2.0)]
    ctx = {"before": other, "after": other, "requests": 3}
    assert read_metric(name, ctx) == 0.0
    assert read_metric(name, {**ctx, "before": [], "after": []}) == 0.0
    assert read_metric(name, {**ctx, "requests": 0}) is None


def test_the_declared_entries_are_the_files_appended_at_the_end(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("seed_existing_ms")
    block = bench["per_layer"][at:at + len(SEED_METRICS)]
    assert [m["name"] for m in block] == list(SEED_METRICS)
    for decl in block:
        s = spec(decl["name"])
        assert decl == {k: s[k] for k in ("name", "unit", "better", "source",
                                          "layer", "moves")}
        assert (decl["unit"], decl["better"], decl["layer"], decl["moves"]
                ) == (SEED_METRICS[decl["name"]], "lower", "H2D + dispatch",
                      "solve_ms")
        assert s["reader"] == "counter_per_request"
    cell = next(w for w in bench["workloads"] if w["name"] == "c3.reconcile")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reconcile", 4)
    # a burst cell reports them too, and reads 0.0 with no existing nodes
    got = run.read_layer_metrics(bench, "c3.burst",
                                 {"before": [], "after": [], "requests": 2})
    assert {n: got[n]["value"] for n in SEED_METRICS} == {
        n: 0.0 for n in SEED_METRICS}


def test_a_scheduler_that_never_compiles_behind_exposes_the_compile_family():
    """A session of constrained pods warms nothing in the background, so the
    compile histogram had no series before its first background compile
    and the benchmark's ``compiles`` read nothing: the cell's traced line
    was refused.  It is exposed at 0 from construction now."""
    reg = Registry()
    BatchScheduler("tpu", compile_behind=False, registry=reg)
    got = scrape.parse_metrics(reg.expose())
    s = spec("compiles")
    reader = run._module(os.path.join(BENCH_DIR, "readers",
                                      f"{s['reader']}.py"),
                         f"reader_{s['reader']}")
    assert reader.read({"before": got, "after": got, "requests": 1},
                       **s["args"]) == 0.0


def _canon(result):
    return sorted((n.instance_type, n.zone, n.capacity_type, n.price,
                   tuple(sorted(p.name for p in n.pods)))
                  for n in result.nodes), sorted(result.infeasible)


def test_a_smaller_standing_cluster_reuses_the_program_a_larger_one_built(
        small_catalog):
    """The window's steps compiled anew wherever the standing cluster fell
    below an existing-node rung it had grown past: forty existing nodes
    (rung 48) build a program, twenty (rung 32) then run on it, with no
    new program and the answer a solver compiled at their own rung gives."""
    from karpenter_tpu.solver.tpu import TpuSolver, _dims_key, solve_dims

    provs = provisioners()
    solver = TpuSolver(registry=Registry())
    standing = solver.solve(tensorize(
        [svc_pod(f"t-{i}", "t", True) for i in range(40)], provs,
        small_catalog)).result.nodes
    assert len(standing) == 40
    st = tensorize([svc_pod(f"u-{i}", "u", False) for i in range(4)], provs,
                   small_catalog)

    def solve(s, nodes):
        return s.solve(st, existing_nodes=[n.snapshot() for n in nodes],
                       max_nodes=len(nodes) + 4).result

    solve(solver, standing)
    ready = set(solver._ready)
    small = solve_dims(st, NE=20, node_budget=24)
    assert small["NE_pad"] == 32 and _dims_key(small) not in ready
    got = solve(solver, standing[:20])
    assert solver._ready == ready  # no program built for rung 32
    fresh = TpuSolver(registry=Registry())
    assert _canon(got) == _canon(solve(fresh, standing[:20]))
    assert _dims_key(small) in fresh._ready
