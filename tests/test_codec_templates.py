"""Pods decoded by template (``service/codec.PodTemplates``, ISSUE 26).

The contract under test: ``decode_request`` builds one ``PodSpec`` per
distinct pod shape and stamps the rest from it, and nobody can tell — every
pod equals what ``decode_pod`` gives over the plain wire (one ``pb.Pod`` a
pod), in the same order, with the same uids; two pods that differ in
anything but ``name`` never share a template; pods that share field
containers are never written through one another; on the old wire a
request of all-distinct pods pays for a short probe only; and the door
says how many pods it stamped.
"""

import copy
import dataclasses

import pytest
from test_fuzz_parity import random_existing_nodes, random_scenario

from karpenter_tpu import gang
from karpenter_tpu.metrics import (
    REQUEST_DECODE_HOW,
    REQUEST_DECODE_PODS,
    Registry,
)
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement
from karpenter_tpu.models.scenarios import spread_deployments
from karpenter_tpu.models.volume import (
    VOLUME_BINDING_WAIT,
    PersistentVolumeClaim,
    StorageClass,
    VolumeTopology,
)
from karpenter_tpu.obs.recorder import FlightRecorder
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.service import codec
from karpenter_tpu.service import solver_pb2 as pb
from karpenter_tpu.service.client import RemoteScheduler
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver import scheduler as scheduler_mod
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.solver.types import SimNode

PROBE = codec.PodTemplates.PROBE


def fields(pod: PodSpec) -> dict:
    """Every declared field of ``pod`` but its uid (memos left out)."""
    return {f.name: getattr(pod, f.name) for f in dataclasses.fields(pod)
            if f.name != "uid"}


def on_the_wire(**kw) -> pb.SolveRequest:
    """The request as the sidecar's handler sees it: encoded, serialised,
    parsed."""
    return pb.SolveRequest.FromString(
        codec.encode_request(**kw).SerializeToString())


def plain_node(n) -> pb.ExistingNode:
    """``n`` on the wire, one ``encode_pod`` a pod."""
    bare = dataclasses.replace(n, pods=[])
    bare.name = n.name
    out = codec.encode_node(bare)
    out.pods.extend(codec.encode_pod(p) for p in n.pods)
    return out


def old_wire(pods, provisioners, instance_types, existing_nodes=(),
             daemonsets=(), **kw) -> pb.SolveRequest:
    """The request as a client without the pods by shape sends it, and as
    the sidecar's handler sees it: one ``encode_pod`` a pod, the request's
    own in ``pods``."""
    req = codec.encode_request([], provisioners, instance_types, **kw)
    req.pods.extend(codec.encode_pod(p) for p in pods)
    req.existing_nodes.extend(plain_node(n) for n in existing_nodes)
    req.daemonsets.extend(codec.encode_pod(p) for p in daemonsets)
    return pb.SolveRequest.FromString(req.SerializeToString())


def plain_pods(req) -> list:
    """The request's own pods as the plain wire carries them: its pods by
    shape, one ``pb.Pod`` a pod, then ``pods``."""
    out = []
    for name, i in zip(req.pod_names, req.pod_shape):
        pod = pb.Pod()
        pod.CopyFrom(req.pod_shapes[i])
        pod.name = name
        out.append(pod)
    return out + list(req.pods)


def default_prov():
    return Provisioner(name="default").with_defaults()


def node_with(pods, name="n-1"):
    return SimNode(instance_type="m5.large", provisioner="default",
                   zone="zone-1a", capacity_type="on-demand", price=0.1,
                   allocatable={"cpu": 64.0}, pods=list(pods), existing=True,
                   name=name)


# ---- (a) parity with decode_pod ------------------------------------------


def c2_shaped(catalog):
    pods = spread_deployments(20, 30, tag="c2")
    return dict(pods=pods, provisioners=[default_prov()],
                instance_types=catalog,
                daemonsets=[PodSpec(name=f"ds-{i}", requests={"cpu": 0.1},
                                    is_daemon=True) for i in range(3)],
                existing_nodes=[node_with(spread_deployments(2, 4, tag="c2"),
                                          name=f"n-{k}") for k in range(3)])


def c3_shaped(catalog):
    pods = []
    for s in range(25):
        sel = LabelSelector.of({"app": f"svc{s}"})
        tols = ([Toleration("dedicated", "Equal", "svc", "NoSchedule")]
                if s % 2 else [])
        pods += [PodSpec(name=f"svc{s}-{i}", labels={"app": f"svc{s}"},
                         requests={"cpu": 0.5, "memory": GIB},
                         tolerations=list(tols), owner_key=f"svc{s}",
                         affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME,
                                                         anti=True)])
                 for i in range(12)]
    return dict(pods=pods, provisioners=[default_prov()],
                instance_types=catalog)


def fuzzed(seed):
    def build(catalog):
        pods, provs, unavailable = random_scenario(seed, catalog)
        return dict(pods=pods, provisioners=provs, instance_types=catalog,
                    unavailable=unavailable,
                    existing_nodes=random_existing_nodes(seed, catalog, provs),
                    daemonsets=[PodSpec(name=f"ds-{i}", requests={"cpu": 0.1})
                                for i in range(2)])
    build.__name__ = f"fuzz{seed}"
    return build


@pytest.mark.parametrize(
    "build", [c2_shaped, c3_shaped] + [fuzzed(s) for s in range(6)],
    ids=lambda f: f.__name__)
def test_by_template_equals_decode_pod_field_for_field(build, small_catalog):
    req = on_the_wire(**build(small_catalog))
    shapes = codec.PodTemplates()
    got = codec.decode_request(req, shapes)
    got_pods = (got["pods"] + [p for n in got["existing_nodes"]
                               for p in n.pods] + got["daemonsets"])
    want_pods = [codec.decode_pod(p) for p in (
        plain_pods(req) + [p for n in req.existing_nodes for p in n.pods]
        + list(req.daemonsets))]
    assert [p.name for p in got_pods] == [p.name for p in want_pods]
    for a, b in zip(got_pods, want_pods):
        assert fields(a) == fields(b)
        # a stamped pod is a whole PodSpec: nothing the constructor sets is
        # missing, nothing memoised came along
        assert set(vars(a)) == set(vars(b))
    # uids are drawn in request order, one each, as the constructor does
    uids = [p.uid for p in got_pods]
    assert uids == list(range(uids[0], uids[0] + len(uids)))
    assert shapes.templated_pods + shapes.plain_pods == len(got_pods)
    assert shapes.templated_pods > 0
    # and the answer is the same scheduling input: equal group keys
    assert ([p.group_key() for p in got_pods]
            == [p.group_key() for p in want_pods])


def test_nothing_memoised_on_a_handed_out_pod_leaks_through_the_table():
    pods = spread_deployments(1, 3, tag="memo")
    req = old_wire(pods=pods, provisioners=[], instance_types=[])
    shapes = codec.PodTemplates()
    first, = shapes.decode(req.pods[:1])
    first.group_key()                      # memoised on the first pod
    first.labels = {"app": "rebound"}      # and a field rebound
    later = shapes.decode(req.pods[1:])
    assert all("_group_key" not in vars(p) for p in later)
    assert [p.labels for p in later] == [{"app": "memo0"}] * 2


def test_a_warm_request_and_a_lone_node_decode_by_template_too():
    ds = [PodSpec(name=f"ds-{i}", requests={"cpu": 0.1}) for i in range(4)]
    node = node_with(spread_deployments(1, 5, tag="w"))
    warm = pb.WarmRequest()
    warm.daemonsets.extend(codec.encode_pod(p) for p in ds)
    warm.existing_nodes.append(codec.encode_node(node))
    got = codec.decode_warm_request(
        pb.WarmRequest.FromString(warm.SerializeToString()))
    assert [fields(p) for p in got["daemonsets"]] == [fields(p) for p in ds]
    lone = codec.decode_node(codec.encode_node(node))
    for n in (got["existing_nodes"][0], lone):
        assert [fields(p) for p in n.pods] == [fields(p) for p in node.pods]
        assert n.pods[1].labels is n.pods[0].labels


# ---- (b) what never shares a template ------------------------------------


def base_pod(**kw) -> PodSpec:
    args = dict(
        name="base-0", namespace="ns", labels={"app": "x", "tier": "web"},
        requests={"cpu": 0.5, "memory": GIB},
        node_selector={L.ZONE: "zone-1a"},
        required_affinity_terms=[[Requirement(L.ARCH, IN, ["amd64"])]],
        tolerations=[Toleration("team", "Equal", "a", "NoSchedule")],
        topology_spread=[TopologySpreadConstraint(
            1, L.ZONE, "DoNotSchedule", LabelSelector.of({"app": "x"}))],
        affinity_terms=[PodAffinityTerm(LabelSelector.of({"app": "x"}),
                                        L.HOSTNAME, anti=True)],
        priority=3, deletion_cost=2.0, owner_key="x",
        volume_zone_requirements=[Requirement(L.ZONE, IN, ["zone-1a"])],
        gang_id="g", gang_size=2)
    args.update(kw)
    return PodSpec(**args)


ONE_FIELD_OFF = {
    "a_label": dict(labels={"app": "x", "tier": "api"}),
    "one_label_more": dict(labels={"app": "x", "tier": "web", "v": "2"}),
    "a_request_by_1e-9": dict(requests={"cpu": 0.5 + 1e-9, "memory": GIB}),
    "a_tolerations_effect": dict(
        tolerations=[Toleration("team", "Equal", "a", "NoExecute")]),
    "gang_size": dict(gang_size=3),
    "gang_id": dict(gang_id="h"),
    "namespace": dict(namespace="other"),
    "node_selector": dict(node_selector={L.ZONE: "zone-1b"}),
    "an_affinity_value": dict(
        required_affinity_terms=[[Requirement(L.ARCH, IN, ["arm64"])]]),
    "max_skew": dict(topology_spread=[TopologySpreadConstraint(
        2, L.ZONE, "DoNotSchedule", LabelSelector.of({"app": "x"}))]),
    "a_soft_spread": dict(topology_spread=[TopologySpreadConstraint(
        1, L.ZONE, "ScheduleAnyway", LabelSelector.of({"app": "x"}))]),
    "anti_or_not": dict(affinity_terms=[PodAffinityTerm(
        LabelSelector.of({"app": "x"}), L.HOSTNAME, anti=False)]),
    "priority": dict(priority=4),
    "deletion_cost": dict(deletion_cost=2.5),
    "owner": dict(owner_key="y"),
    "a_volume_zone": dict(
        volume_zone_requirements=[Requirement(L.ZONE, IN, ["zone-1b"])]),
}


@pytest.mark.parametrize("change", sorted(ONE_FIELD_OFF))
def test_pods_one_field_apart_never_share_a_template(change):
    twin = base_pod(name="base-1")
    other = base_pod(name="other-0", **ONE_FIELD_OFF[change])
    wire = [codec.encode_pod(p) for p in (base_pod(), other, twin)]
    shapes = codec.PodTemplates()
    got = shapes.decode(wire)
    assert [fields(p) for p in got] == [fields(codec.decode_pod(p))
                                        for p in wire]
    assert fields(got[1]) != {**fields(got[0]), "name": "other-0"}
    assert shapes.templates == 2
    assert (shapes.templated_pods, shapes.plain_pods) == (1, 2)
    assert got[2].labels is got[0].labels
    assert got[1].labels is not got[0].labels


@pytest.mark.parametrize("name,keyed", [
    ("", False),                  # proto3 leaves the field out: named by uid
    ("p", True),
    ("n" * 127, True),            # the longest one-byte length
    ("n" * 128, True),            # the first two-byte varint
    ("n" * 200, True),
    ("n" * 16383, True),          # the longest two-byte length
    ("n" * 16384, False),         # a three-byte length: decoded plainly
    ("pod-é中", True),   # the length counts bytes, not characters
    ("é" * 64, True),        # 128 bytes in 64 characters
], ids=lambda v: None if isinstance(v, bool) else f"{len(v)}ch")
def test_a_name_of_any_length_decodes_right(name, keyed):
    wire = [codec.encode_pod(base_pod(name=n or "unnamed"))
            for n in ("first", name, name, "last")]
    if not name:
        wire[1].name = wire[2].name = ""
    shapes = codec.PodTemplates()
    got = shapes.decode(wire)
    want = [codec.decode_pod(p) for p in wire]
    if name:
        assert [fields(p) for p in got] == [fields(p) for p in want]
    else:
        # the constructor names an unnamed pod by its own uid
        assert [p.name for p in got] == [
            "first", f"pod-{got[1].uid}", f"pod-{got[2].uid}", "last"]
        assert fields(got[1]) == {**fields(got[0]), "name": got[1].name}
    assert shapes.templates == 1
    assert shapes.templated_pods == (3 if keyed else 1)
    assert shapes.plain_pods == (1 if keyed else 3)


def test_bytes_that_do_not_open_with_the_name_field_give_no_key():
    """A key is cut only behind a prefix that is exactly tag 0x0a, the
    length, the name; anything else decodes plainly."""
    p = codec.encode_pod(base_pod(name="abc"))
    key = codec.PodTemplates._key
    assert key(p, "abc") == p.SerializeToString()[5:]
    assert key(p, "abd") is None and key(p, "ab") is None
    assert key(p, "abcd") is None and key(p, "") is None
    unnamed = codec.encode_pod(base_pod(name="x"))
    unnamed.name = ""
    assert key(unnamed, "") is None
    # a pod whose other fields are all defaults: the key is empty, not None
    bare = pb.Pod(name="bare")
    assert key(bare, "bare") == b""


# ---- (c) the aliasing guard ----------------------------------------------


def guarded_request(catalog):
    """Deployments that meet every writer the package has: a gang with a
    soft zone spread (``_harden_preferences``, then the gang epilogue's
    pinned what-ifs), a deployment with a bound zonal volume, a plain
    one."""
    soft = LabelSelector.of({"app": "ring"})
    pods = [PodSpec(name=f"ring-{i}", labels={"app": "ring"},
                    requests={"cpu": 3.0, "memory": 4 * GIB},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.ZONE, "ScheduleAnyway", soft)],
                    owner_key="ring", gang_id="ring", gang_size=6)
            for i in range(6)]
    pods += [PodSpec(name=f"db-{i}", labels={"app": "db"},
                     requests={"cpu": 1.0, "memory": 2 * GIB},
                     volume_zone_requirements=[
                         Requirement(L.ZONE, IN, ["zone-1a", "zone-1b"])],
                     owner_key="db") for i in range(5)]
    pods += [PodSpec(name=f"web-{i}", labels={"app": "web"},
                     requests={"cpu": 0.5, "memory": GIB}, owner_key="web")
             for i in range(8)]
    return on_the_wire(pods=pods, provisioners=[default_prov()],
                       instance_types=catalog)


def test_no_writer_reaches_a_sibling_through_a_shared_container(
        small_catalog, monkeypatch):
    got = codec.decode_request(guarded_request(small_catalog))
    pods = got["pods"]
    by_owner = {}
    for p in pods:
        by_owner.setdefault(p.owner_key, []).append(p)
    # siblings do share their containers, or the guard guards nothing
    for sibs in by_owner.values():
        for f in ("labels", "requests", "node_selector", "tolerations",
                  "topology_spread", "affinity_terms", "volume_claims",
                  "required_affinity_terms", "preferred_affinity_terms",
                  "volume_zone_requirements"):
            assert all(getattr(p, f) is getattr(sibs[0], f) for p in sibs), f
    before = {p.name: copy.deepcopy(fields(p)) for p in pods}

    hardened, pinned = [], []
    inner_harden = scheduler_mod._harden_preferences
    inner_pack = gang._try_pack

    def harden_spy(pod, keep=None):
        out = inner_harden(pod, keep)
        if out is not pod:
            hardened.append(pod.name)
        return out

    def pack_spy(result, gid, members, **kw):
        pinned.append(gid)
        return inner_pack(result, gid, members, **kw)

    monkeypatch.setattr(scheduler_mod, "_harden_preferences", harden_spy)
    monkeypatch.setattr(gang, "_try_pack", pack_spy)

    # the volume injector re-pins ONE pod of the deployment whose claim
    # bound since (the claim list itself arrives by rebinding: it is not on
    # the wire); its siblings keep the zones they came with
    vt = VolumeTopology()
    vt.apply_class(StorageClass(name="ebs",
                                volume_binding_mode=VOLUME_BINDING_WAIT,
                                allowed_zones=("zone-1c",)))
    vt.apply_claim(PersistentVolumeClaim(name="claim", storage_class="ebs"))
    repinned = by_owner["db"][2]
    repinned.volume_claims = ["claim"]
    assert vt.inject(repinned) == []
    assert repinned.volume_zone_requirements == [
        Requirement(L.ZONE, IN, ["zone-1c"])]

    res = BatchScheduler(backend="oracle", registry=Registry()).solve(
        pods, got["provisioners"], got["instance_types"])
    assert not res.infeasible
    assert set(hardened) >= {f"ring-{i}" for i in range(6)}
    assert pinned == ["ring"]

    changed = {repinned.name: ("volume_claims", "volume_zone_requirements")}
    for p in pods:
        now = fields(p)
        for f in changed.get(p.name, ()):
            assert now.pop(f) != before[p.name][f]
        want = {k: v for k, v in before[p.name].items() if k in now}
        assert now == want, p.name


@pytest.mark.parametrize("field,value", [
    ("labels", {"app": "mine"}),
    ("requests", {"cpu": 9.0}),
    ("node_selector", {L.ZONE: "zone-1c"}),
    ("tolerations", [Toleration("k", "Exists")]),
    ("topology_spread", []),
    ("affinity_terms", []),
    ("required_affinity_terms", [[Requirement(L.ARCH, IN, ["arm64"])]]),
    ("preferred_affinity_terms", [[Requirement(L.ARCH, IN, ["arm64"])]]),
    ("volume_claims", ["c"]),
    ("volume_zone_requirements", []),
])
def test_rebinding_a_field_of_one_pod_leaves_its_siblings_alone(field, value):
    """The convention stated at ``PodSpec``, from the writer's side: copy
    the pod, rebind the field."""
    wire = [codec.encode_pod(base_pod(name=f"s-{i}")) for i in range(3)]
    got = codec.PodTemplates().decode(wire)
    before = [copy.deepcopy(fields(p)) for p in got]
    for k in (0, 1):                   # the first of a shape, and a stamped one
        q = copy.copy(got[k])
        setattr(q, field, value)
        assert getattr(q, field) == value
    assert [fields(p) for p in got] == before


# ---- (d) all-distinct pods fall back to the plain decode -----------------


def distinct(n, tag="u"):
    return [PodSpec(name=f"{tag}-{i}", labels={"app": tag, "i": str(i)},
                    requests={"cpu": 0.25}) for i in range(n)]


def replicas(n, tag="r"):
    return [PodSpec(name=f"{tag}-{i}", labels={"app": tag},
                    requests={"cpu": 0.25}) for i in range(n)]


@pytest.mark.parametrize("lead,tail,templated,templates", [
    # all distinct: the probe gives up, and what follows is not even keyed
    (distinct(PROBE), replicas(40), 0, PROBE),
    # a request shorter than the probe is never judged
    (distinct(PROBE - 1), [], 0, PROBE - 1),
    # exactly half hits at the probe: the table stays
    (distinct(PROBE // 2 - 1) + replicas(PROBE // 2 + 1, "h"),
     replicas(40), PROBE // 2 + 39, PROBE // 2 + 1),
    # one hit short of half: it goes
    (distinct(PROBE // 2) + replicas(PROBE // 2, "h"),
     replicas(40), PROBE // 2 - 1, PROBE // 2 + 1),
    # replicas first: what is distinct later costs its keys and stays right
    (replicas(PROBE), distinct(300), PROBE - 1, 301),
], ids=["all_distinct", "short", "half_hits", "under_half", "replicas_first"])
def test_the_table_watches_its_own_hit_share(lead, tail, templated,
                                             templates):
    wire = [codec.encode_pod(p) for p in lead + tail]
    shapes = codec.PodTemplates()
    got = shapes.decode(wire)
    assert [fields(p) for p in got] == [fields(p) for p in lead + tail]
    assert shapes.templated_pods == templated
    assert shapes.plain_pods == len(wire) - templated
    assert shapes.templates == templates


def test_a_table_that_gave_up_stays_plain_for_the_rest_of_its_request():
    req = old_wire(
        pods=distinct(PROBE + 10), provisioners=[], instance_types=[],
        existing_nodes=[node_with(replicas(20, "on-node"))],
        daemonsets=replicas(5, "ds"))
    shapes = codec.PodTemplates()
    got = codec.decode_request(req, shapes)
    assert (shapes.templated_pods, shapes.plain_pods) == (0, PROBE + 35)
    assert len(got["existing_nodes"][0].pods) == 20
    uids = [p.uid for p in got["pods"] + got["existing_nodes"][0].pods
            + got["daemonsets"]]
    assert uids == list(range(uids[0], uids[0] + PROBE + 35))
    # no table outlives its request: the next one starts over
    again = codec.PodTemplates()
    codec.decode_request(old_wire(
        pods=replicas(30), provisioners=[], instance_types=[]), again)
    assert (again.templates, again.templated_pods) == (1, 29)


# ---- (e) what the door says ----------------------------------------------


@pytest.fixture(scope="module")
def served():
    reg = Registry()
    flight = FlightRecorder(registry=reg)
    tracer = Tracer(registry=reg, flight=flight)
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg,
                                       tracer=tracer), registry=reg)
    decoded = reg.counter(REQUEST_DECODE_PODS)
    # zero-initialised like the other families: both samples exist before
    # the first request
    assert all(decoded.has({"how": how}) and decoded.get({"how": how}) == 0
               for how in REQUEST_DECODE_HOW)
    srv, port = make_server(svc, port=0)
    yield {"svc": svc, "reg": reg, "flight": flight, "port": port}
    srv.stop(grace=None)
    svc.close()


def deployments(nd, per, tag):
    return [PodSpec(name=f"{tag}{d}-{i}", labels={"app": f"{tag}{d}"},
                    requests={"cpu": 0.25 * (1 + d), "memory": GIB},
                    owner_key=f"{tag}{d}")
            for d in range(nd) for i in range(per)]


@pytest.mark.parametrize("pods,first,again", [
    (deployments(5, 40, "dep"), (5, 195), (5, 195)),
    (deployments(1, 1, "lone"), (1, 0), (1, 0)),
    # the old wire's table gave up at its probe; by shape the client did,
    # and every later pod is a shape of its own
    (distinct(PROBE + 88, "odd"), (PROBE, 0), (PROBE + 88, 0)),
], ids=["5x40", "one_pod", "all_distinct"])
def test_the_door_counts_what_it_stamped(served, small_catalog, pods,
                                         first, again):
    """(templates, templated pods) at the door: the client's first request
    goes one message a pod (it has not heard the sidecar yet), the next by
    shape; the first pod of each shape is built plainly either way."""
    decoded = served["reg"].counter(REQUEST_DECODE_PODS)
    remote = RemoteScheduler(f"127.0.0.1:{served['port']}", backend="oracle",
                             registry=Registry())
    try:
        for want, catalog in ((first, "decoded"), (again, "held")):
            templates, templated = want
            before = {how: decoded.get({"how": how})
                      for how in REQUEST_DECODE_HOW}
            res = remote.solve(pods, [default_prov()], small_catalog)
            assert not res.infeasible and len(res.assignments) == len(pods)
            moved = {how: decoded.get({"how": how}) - before[how]
                     for how in REQUEST_DECODE_HOW}
            assert moved == {"templated": templated,
                             "plain": len(pods) - templated}
            tree = served["flight"].traces()[-1].to_dict()
            door = {c["name"]: c
                    for c in tree["spans"]}["request_decode"]["attrs"]
            assert door == {"n_pods": len(pods), "templates": templates,
                            "templated_pods": templated,
                            "catalog": catalog}
    finally:
        remote.close()
    text = served["reg"].expose()
    assert f'{REQUEST_DECODE_PODS}{{how="templated"}}' in text
    assert f'{REQUEST_DECODE_PODS}{{how="plain"}}' in text


def test_the_direct_path_counts_too(small_catalog, monkeypatch):
    """``KT_SOLVE_PIPELINE=0`` (and every in-process caller of
    ``SolverService.Solve``) goes through the same door."""
    monkeypatch.setenv("KT_SOLVE_PIPELINE", "0")
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    try:
        resp = svc.Solve(on_the_wire(
            pods=deployments(3, 10, "dir"), provisioners=[default_prov()],
            instance_types=small_catalog), None)
    finally:
        svc.close()
    assert len(resp.assignments) == 30
    decoded = reg.counter(REQUEST_DECODE_PODS)
    assert decoded.get({"how": "templated"}) == 27
    assert decoded.get({"how": "plain"}) == 3


# ---- the benchmark's metric file reads this family ------------------------


def test_the_benchmarks_metric_file_reads_what_the_door_counts(
        served, small_catalog, monkeypatch):
    """``benchmarks/metrics/decode_templated_pods.json`` is data: it names
    the family and the label by hand.  Read a real scrape of a served
    request through the benchmark's own reader, so that a renamed family
    or label cannot turn the metric into a silent 0.0 (which is what a
    program WITHOUT the family reads: the parent, measured with these
    files laid over it)."""
    import importlib.util
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")

    def load(path, name):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    with open(os.path.join(bench, "metrics",
                           "decode_templated_pods.json")) as f:
        metric = json.load(f)
    assert (metric["layer"], metric["moves"], metric["unit"],
            metric["better"]) == ("server parse + decode", "solve_ms",
                                  "pods", "higher")
    scrape = load(os.path.join(bench, "scrape.py"), "scrape")
    monkeypatch.setitem(sys.modules, "scrape", scrape)  # the reader's import
    reader = load(os.path.join(bench, "readers", f"{metric['reader']}.py"),
                  "reader_under_test")
    before = scrape.parse_metrics(served["reg"].expose())
    remote = RemoteScheduler(f"127.0.0.1:{served['port']}", backend="oracle",
                             registry=Registry())
    try:
        for k in range(2):
            remote.solve(deployments(5, 40, f"bm{k}"), [default_prov()],
                         small_catalog)
    finally:
        remote.close()
    after = scrape.parse_metrics(served["reg"].expose())
    ctx = {"before": before, "after": after, "requests": 2}
    assert reader.read(ctx, **metric["args"]) == 195.0
    without = [s for s in after if s[0] != REQUEST_DECODE_PODS]
    assert reader.read({**ctx, "before": without, "after": without},
                       **metric["args"]) == 0.0
