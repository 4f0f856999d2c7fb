"""The device loop takes a step only up to the last group that has pods
(PR 38): its trip count is read off the batch on the device, the ``G`` rung
stays the SHAPE of the group axis and the compile signature.

(a) every batch kind through ``_run_scan`` at its own rung and with
    ``_host_arrays(dims=...)`` forced one and two ``G`` rungs up: the carry
    (all twelve elements), the take matrix and the ``SolveResult`` are those
    of a plain ``lax.scan`` over the whole rung (the loop this PR replaced),
    and ``steps_run`` is ``st.G`` whatever the rung;
(b) a group of no pods: in the middle it is stepped over as before, at the
    end it is not reached;
(c) the compile signature of a batch is what it was before this PR;
(d) a megabatch of two slots of different lengths runs the longer slot's
    steps and keeps per-slot parity with the serial solves.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from karpenter_tpu.metrics import SCAN_AXIS, Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.catalog import generate_catalog
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
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.solver import tpu as tpu_mod
from karpenter_tpu.solver.types import SimNode, advance_node_counter

CARRY = ("res", "row_zone", "row_dom", "row_cand", "row_price", "selcnt",
         "active", "n_used", "zc", "tot", "prov_used", "infeasible")


def _group(tag, n, cpu, mem=1.0, **kw):
    return [PodSpec(name=f"{tag}-{i}", labels={"app": tag},
                    requests={"cpu": cpu, "memory": mem * GIB},
                    owner_key=tag, **kw) for i in range(n)]


def _default():
    return [Provisioner(name="default").with_defaults()]


def zone_spread(catalog):
    pods = []
    for gi in range(6):
        sel = LabelSelector.of({"app": f"web{gi}"})
        pods += _group(f"web{gi}", 40 + 7 * gi, 0.25 * (1 + gi % 4),
                       topology_spread=[TopologySpreadConstraint(
                           1, L.ZONE, "DoNotSchedule", sel)])
    pods += _group("plain", 30, 0.5)
    return pods, _default(), ()


def hostname_anti_with_taints(catalog):
    tainted = Provisioner(
        name="dedicated", weight=10,
        taints=[Taint("dedicated", L.EFFECT_NO_SCHEDULE, "svc")],
    ).with_defaults()
    pods = []
    for gi in range(9):
        sel = LabelSelector.of({"app": f"svc{gi}"})
        tol = ([Toleration(key="dedicated", operator="Equal", value="svc")]
               if gi % 2 else [])
        pods += _group(f"svc{gi}", 8 + gi, 0.5, tolerations=tol,
                       affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME,
                                                       anti=True)])
    return pods, [tainted] + _default(), ()


def pod_affinity(catalog):
    sel_a = LabelSelector.of({"app": "anchor"})
    pods = _group("anchor", 6, 1.0,
                  affinity_terms=[PodAffinityTerm(sel_a, L.ZONE)])
    pods += _group("near", 20, 0.5,
                   affinity_terms=[PodAffinityTerm(sel_a, L.ZONE)])
    sel_h = LabelSelector.of({"app": "pair"})
    pods += _group("pair", 4, 0.25,
                   affinity_terms=[PodAffinityTerm(sel_h, L.HOSTNAME)])
    pods += _group("rest", 25, 0.5, 2.0)
    return pods, _default(), ()


def existing_nodes(catalog):
    it = next(t for t in catalog if t.name == "m5.2xlarge")
    nodes = []
    for k, zone in enumerate(("zone-1a", "zone-1b", "zone-1a")):
        node = SimNode(
            instance_type=it.name, provisioner="default", zone=zone,
            capacity_type=L.CAPACITY_TYPE_ON_DEMAND,
            price=it.offerings[0].price, allocatable=dict(it.allocatable),
            labels={**it.labels(), L.ZONE: zone,
                    L.CAPACITY_TYPE: L.CAPACITY_TYPE_ON_DEMAND},
            existing=True,
        )
        node.pods.extend(_group(f"old{k}", 2, 0.5))
        nodes.append(node)
    sel = LabelSelector.of({"app": "web"})
    pods = _group("web", 30, 0.5, topology_spread=[TopologySpreadConstraint(
        1, L.ZONE, "DoNotSchedule", sel)])
    pods += _group("batch", 40, 1.0, 2.0)
    pods += _group("tiny", 5, 0.25)
    return pods, _default(), tuple(nodes)


def long_tail(catalog):
    """The load test's shape in small: a few large deployments, a long tail
    of five-pod ones, 150 groups on the 192 rung."""
    pods = []
    for gi in range(150):
        n = 60 if gi % 50 == 0 else (12 if gi % 10 == 0 else 5)
        sel = LabelSelector.of({"app": f"d{gi}"})
        spread = ([TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)]
                  if n > 5 else [])
        pods += _group(f"d{gi}", n, 0.25 * (1 + gi % 6), float(1 + gi % 3),
                       topology_spread=spread)
    return pods, _default(), ()


KINDS = {f.__name__: f for f in (zone_spread, hostname_anti_with_taints,
                                 pod_affinity, existing_nodes, long_tail)}


@partial(jax.jit, static_argnames=("NR", "Z"))
def whole_rung_scan(consts, init, NR, Z):
    """The loop before this PR: one step for every row of the rung."""
    step = tpu_mod._make_step(consts, NR, Z, True)
    G = consts["counts"].shape[0]
    return jax.lax.scan(step, init, jnp.arange(G, dtype=jnp.int32))


def _device_inputs(solver, st, existing, dims=None, counts=None):
    nb = tpu_mod._node_budget(st, len(existing), None)
    np_consts, feas, np_init, dims = solver._host_arrays(
        st, existing, node_budget=nb, track_assignments=True, full_nr=False,
        dims=dims)
    if counts is not None:
        np_consts["counts"] = np.pad(
            counts, (0, dims["G"] - len(counts))).astype(
                np_consts["counts"].dtype)
    consts = {k: jnp.asarray(v) for k, v in np_consts.items()}
    consts["F"], consts["dom_ok"] = tpu_mod.feasibility_jit(
        jnp.asarray(feas["pm"]), consts["requests"],
        jnp.asarray(feas["gp_ok"]), jnp.asarray(feas["cand_vw"]),
        jnp.asarray(feas["cand_vb"]), consts["cand_alloc"],
        consts["cand_prov"], jnp.asarray(feas["key_check"]),
        jnp.asarray(feas["dom_vw"]), jnp.asarray(feas["dom_vb"]),
        zone_key=st.vocab.key_id[L.ZONE],
        ct_key=st.vocab.key_id[L.CAPACITY_TYPE])
    return consts, tuple(jnp.asarray(v) for v in np_init), dims


def _plan(result):
    """Node names come off a process-wide counter: compare what is on them."""
    return (sorted((n.instance_type, n.zone, n.capacity_type,
                    round(n.price, 6), tuple(sorted(p.name for p in n.pods)))
                   for n in list(result.nodes) + list(result.existing_nodes)),
            sorted(result.infeasible))


def _rungs_up(g_pad, n):
    out = [g_pad]
    for _ in range(n):
        out.append(tpu_mod._rung(out[-1] + 1, 16, 128))
    return out


@pytest.fixture(scope="module")
def catalog():
    # ``coalesce`` breaks ties of its order by node name, and names come off
    # a process-wide counter: keep every name of this module at one width,
    # so that two extractions of one carry merge in one order
    advance_node_counter(100_000)
    return generate_catalog(full=False)


@pytest.fixture(scope="module")
def solved(catalog):
    """Each batch kind at its own ``G`` rung and one and two rungs up, by the
    program and by the whole-rung scan."""
    out = {}
    for kind, build in KINDS.items():
        pods, provs, existing = build(catalog)
        st = tensorize(pods, provs, catalog)
        solver = tpu_mod.TpuSolver(registry=Registry())
        own = tpu_mod.solve_dims(
            st, NE=len(existing),
            node_budget=tpu_mod._node_budget(st, len(existing), None))
        runs = []
        for g_pad in _rungs_up(own["G"], 2):
            consts, init, dims = _device_inputs(
                solver, st, existing, dims=dict(own, G=g_pad))
            carry, takes, steps = tpu_mod._run_scan(
                consts, init, dims["NR"], dims["Z"], True)
            ref_carry, ref_takes = whole_rung_scan(
                consts, init, dims["NR"], dims["Z"])
            runs.append({
                "G_pad": g_pad,
                "carry": [np.asarray(x) for x in carry],
                "takes": np.asarray(takes), "steps": int(steps),
                "ref_carry": [np.asarray(x) for x in ref_carry],
                "ref_takes": np.asarray(ref_takes),
                "result": solver._extract(st, carry, takes, existing,
                                          len(existing), 0.0, 0.0).result,
            })
        out[kind] = {"st": st, "runs": runs, "own": own}
    return out


@pytest.mark.parametrize("up", (0, 1, 2), ids=("own", "up1", "up2"))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_loop_is_the_whole_rung_scan(solved, kind, up):
    run = solved[kind]["runs"][up]
    st = solved[kind]["st"]
    assert run["G_pad"] > st.G or up == 0
    assert run["steps"] == st.G
    for name, got, want in zip(CARRY, run["carry"], run["ref_carry"]):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert np.array_equal(got, want), name
    assert run["takes"].shape == (run["G_pad"], solved[kind]["own"]["NR"])
    assert run["takes"].dtype == run["ref_takes"].dtype == np.int32
    assert np.array_equal(run["takes"], run["ref_takes"])
    assert not run["takes"][st.G:].any()
    assert run["takes"][:st.G].sum() + run["carry"][11].sum() == int(
        st.counts.sum())


@pytest.mark.parametrize("up", (1, 2), ids=("up1", "up2"))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_higher_rung_gives_the_same_answer(solved, kind, up):
    base, run = solved[kind]["runs"][0], solved[kind]["runs"][up]
    G = solved[kind]["st"].G
    for name, got, want in zip(CARRY[:-1], run["carry"], base["carry"]):
        assert np.array_equal(got, want), name
    assert np.array_equal(run["carry"][11][:G], base["carry"][11][:G])
    assert not run["carry"][11][G:].any()
    assert np.array_equal(run["takes"][:G], base["takes"][:G])
    assert _plan(run["result"]) == _plan(base["result"])


#: which groups lose their pods -> the steps the loop then takes, both of G
EMPTIED = {"middle": (lambda G: [G // 2], lambda G: G),
           "last": (lambda G: [G - 1], lambda G: G - 1),
           "last_three": (lambda G: range(G - 3, G), lambda G: G - 3),
           "all": (range, lambda G: 0)}


@pytest.mark.parametrize("emptied", sorted(EMPTIED))
def test_a_group_of_no_pods(solved, emptied):
    """A regrouped batch (the consolidation sweep's what-ifs) can leave a
    group without pods anywhere: the bound is the last group that HAS pods,
    so one in the middle is stepped over and one at the end is not
    reached; either way the answer is the whole-rung scan's."""
    st = solved["long_tail"]["st"]
    which, steps = EMPTIED[emptied]
    counts = np.asarray(st.counts).copy()
    counts[list(which(st.G))] = 0
    solver = tpu_mod.TpuSolver(registry=Registry())
    consts, init, dims = _device_inputs(solver, st, (), counts=counts)
    carry, takes, got_steps = tpu_mod._run_scan(
        consts, init, dims["NR"], dims["Z"], True)
    ref_carry, ref_takes = whole_rung_scan(consts, init, dims["NR"],
                                           dims["Z"])
    assert int(got_steps) == steps(st.G)
    for name, got, want in zip(CARRY, carry, ref_carry):
        assert np.array_equal(np.asarray(got), np.asarray(want)), name
    assert np.array_equal(np.asarray(takes), np.asarray(ref_takes))


def test_the_compile_signature_is_the_parents(solved):
    """``solve_dims`` / ``_dims_key`` / ``signature`` key the rungs as they
    did: this tuple was read off the parent commit's program for the same
    batch."""
    st = solved["long_tail"]["st"]
    want = (("C", 64), ("D", 6), ("G", 192), ("K", 18), ("NE_pad", 16),
            ("NR", 1024), ("P", 4), ("R", 4), ("S", 16), ("W", 1), ("Z", 3),
            ("a", 1), ("b", 1), ("track", True))
    nb = tpu_mod._node_budget(st, 0, None)
    dims = tpu_mod.solve_dims(st, NE=0, node_budget=nb)
    assert tpu_mod._dims_key(dims) == want
    assert tpu_mod.TpuSolver(registry=Registry()).signature(st) == want


def test_an_untracked_solve_keeps_its_outputs(solved):
    st = solved["zone_spread"]["st"]
    solver = tpu_mod.TpuSolver(registry=Registry())
    run, init, _ne = solver.prepare(st, track_assignments=False)
    carry, ys, steps = run(init)
    base = solved["zone_spread"]["runs"][0]
    assert int(steps) == st.G
    assert ys.shape == (base["G_pad"],) and not np.asarray(ys).any()
    for name, got, want in zip(CARRY, carry, base["carry"]):
        assert np.array_equal(np.asarray(got), want), name


@pytest.mark.parametrize("how", ("solve", "solve_async"))
def test_the_counter_and_the_span_say_what_the_program_said(solved, how):
    from karpenter_tpu.obs.trace import Tracer

    st = solved["long_tail"]["st"]
    reg = Registry()
    solver = tpu_mod.TpuSolver(registry=reg)
    axis = reg.counter(SCAN_AXIS)
    assert axis.has({"axis": "steps_run"})
    assert axis.get({"axis": "steps_run"}) == 0
    trace = Tracer(registry=reg).start("solve")
    with trace:
        if how == "solve":
            out = solver.solve(st, trace=trace)
        else:
            out = solver.solve_async(st, trace=trace).result()
    assert axis.get({"axis": "steps_run"}) == st.G == 150
    assert axis.get({"axis": "groups_padded"}) == 192
    spans = {s.name: dict(s.attrs) for s in trace.spans()}
    fenced = spans["device_execute" if how == "solve" else "device_fence"]
    assert (fenced["steps_run"], fenced["groups"],
            fenced["groups_padded"]) == (150, 150, 192)
    assert _plan(out.result) == _plan(solved["long_tail"]["runs"][0]["result"])


@pytest.fixture(scope="module")
def two_slots(catalog):
    """Two requests of one compile bucket, 4 and 7 groups long."""
    def tenant(tag, n_groups):
        pods = []
        for gi in range(n_groups):
            sel = LabelSelector.of({"app": f"{tag}{gi}"})
            pods += _group(f"{tag}{gi}", 10 + gi, 0.25 * (1 + gi % 5),
                           float(1 + gi % 3),
                           topology_spread=[TopologySpreadConstraint(
                               1, L.ZONE, "DoNotSchedule", sel)])
        return tensorize(pods, _default(), catalog)

    reg = Registry()
    solver = tpu_mod.TpuSolver(registry=reg)
    sts = [tenant("short", 4), tenant("long", 7)]
    assert solver.mega_signature(sts[0], slots=2) == solver.mega_signature(
        sts[1], slots=2)
    serial = [solver.solve(st) for st in sts]
    before = reg.counter(SCAN_AXIS).get({"axis": "steps_run"})
    pending = solver.solve_many_async([dict(st=st) for st in sts])
    outs = pending.results()
    return {"sts": sts, "serial": serial, "outs": outs, "pending": pending,
            "counted": reg.counter(SCAN_AXIS).get({"axis": "steps_run"})
            - before}


@pytest.mark.parametrize("slot", (0, 1), ids=("short", "long"))
def test_a_megabatch_slot_is_its_serial_solve(two_slots, slot):
    out, want = two_slots["outs"][slot], two_slots["serial"][slot]
    assert _plan(out.result) == _plan(want.result)
    assert out.n_used == want.n_used
    assert np.array_equal(out.takes, want.takes)
    assert not out.takes[two_slots["sts"][slot].G:].any()


def test_a_megabatch_runs_its_longest_slots_steps(two_slots):
    """One loop, one bound: every slot takes the longer slot's 7 steps (the
    shorter one's last three are steps for groups of no pods), and each
    slot's count says so."""
    assert [st.G for st in two_slots["sts"]] == [4, 7]
    assert int(np.asarray(two_slots["pending"].steps)) == 7
    assert two_slots["counted"] == 14
