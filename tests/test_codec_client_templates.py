"""The client's side of the wire by pod shape (``service/codec.PodShapes``,
ISSUE 30).

The contract under test: ``encode_request`` builds one ``pb.Pod`` per
distinct pod shape and writes the rest as their name plus that pod's bytes,
and nobody can tell — the request equals the one ``encode_pod`` per pod
builds, pod for pod and byte for byte, so the sidecar's ``PodTemplates``
hits exactly as before; two pods that differ in anything ``encode_pod``
reads but ``name`` never share a template; a request of all-distinct pods
pays for a short probe only; nothing is remembered from one request to the
next; ``decode_response`` seats the caller's own pods; and the client says
how many pods it wrote from a template.
"""

import dataclasses

import pytest
from test_codec_templates import (
    ONE_FIELD_OFF,
    base_pod,
    c2_shaped,
    c3_shaped,
    default_prov,
    deployments,
    distinct,
    fuzzed,
    node_with,
    replicas,
)

from karpenter_tpu.metrics import (
    REQUEST_ENCODE_HOW,
    REQUEST_ENCODE_PODS,
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
from karpenter_tpu.models.requirements import IN, NOT_IN, Requirement
from karpenter_tpu.obs.recorder import FlightRecorder
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.service import codec
from karpenter_tpu.service import solver_pb2 as pb
from karpenter_tpu.service.client import RemoteScheduler
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver.scheduler import BatchScheduler

PROBE = codec.PodShapes.PROBE


# ---- the reference: one encode_pod per pod, as the parent built it --------


def plain_node(n) -> pb.ExistingNode:
    bare = dataclasses.replace(n, pods=[])
    bare.name = n.name
    out = codec.encode_node(bare)
    out.pods.extend(codec.encode_pod(p) for p in n.pods)
    return out


def plain_request(pods, provisioners, instance_types, existing_nodes=(),
                  daemonsets=(), **kw) -> pb.SolveRequest:
    """What ``encode_request`` built before it knew pod shapes: the
    envelope from the codec, every pod through ``encode_pod``."""
    req = codec.encode_request([], provisioners, instance_types, **kw)
    req.pods.extend(codec.encode_pod(p) for p in pods)
    req.existing_nodes.extend(plain_node(n) for n in existing_nodes)
    req.daemonsets.extend(codec.encode_pod(p) for p in daemonsets)
    return req


def wire_pods(req) -> list:
    return (list(req.pods) + [p for n in req.existing_nodes for p in n.pods]
            + list(req.daemonsets))


def parsed(req):
    return type(req).FromString(req.SerializeToString())


def door_counts(req) -> tuple:
    """What the sidecar's table makes of ``req`` as it arrives."""
    shapes = codec.PodTemplates()
    codec.decode_request(parsed(req), shapes)
    return shapes.templates, shapes.templated_pods, shapes.plain_pods


# ---- (a) the request equals the plain build -------------------------------


def longtail_shaped(catalog):
    """Deployments of 50 / 12 / 5 with 1/4, 1/4, 1/2 of the pods: most
    shapes hold five pods."""
    pods = []
    for d, per in enumerate([50] * 2 + [12] * 8 + [5] * 40):
        sel = LabelSelector.of({"app": f"lt{d}"})
        spread = ([TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)]
                  if per > 5 else [])
        pods += [PodSpec(name=f"lt{d}-{i}", labels={"app": f"lt{d}"},
                         requests={"cpu": 0.25 * (1 + d % 8),
                                   "memory": (0.5 + d % 6) * GIB},
                         topology_spread=list(spread), owner_key=f"lt{d}")
                 for i in range(per)]
    return dict(pods=pods, provisioners=[default_prov()],
                instance_types=catalog,
                daemonsets=replicas(3, "ds"),
                existing_nodes=[node_with(replicas(6, "on"), name="n-0"),
                                node_with(distinct(4, "odd"), name="n-1")])


BUILDS = [c2_shaped, c3_shaped, longtail_shaped] + [fuzzed(s)
                                                    for s in range(8)]


@pytest.mark.parametrize("build", BUILDS, ids=lambda f: f.__name__)
def test_by_shape_equals_the_plain_build(build, small_catalog):
    kw = build(small_catalog)
    shapes = codec.PodShapes()
    got = codec.encode_request(**kw, shapes=shapes)
    want = plain_request(**kw)
    assert got == want
    # pod for pod, in the same order, and byte for byte
    got_pods, want_pods = wire_pods(got), wire_pods(want)
    assert [p.name for p in got_pods] == [p.name for p in want_pods]
    for a, b in zip(got_pods, want_pods):
        assert a.SerializeToString() == b.SerializeToString()
        assert a.SerializeToString().startswith(codec._name_field(a.name))
    assert got.SerializeToString() == want.SerializeToString()
    assert shapes.templated_pods + shapes.plain_pods == len(want_pods)
    assert shapes.templated_pods > 0
    assert shapes.shapes <= shapes.plain_pods
    # the sidecar saw the same request
    assert door_counts(got) == door_counts(want)


def test_a_warm_request_and_a_lone_node_encode_by_shape_too():
    ds = replicas(4, "ds")
    nodes = [node_with(replicas(5, "w"), name="n-0"),
             node_with(replicas(3, "w") + distinct(2, "x"), name="n-1")]
    got = codec.encode_warm_request([default_prov()], [], daemonsets=ds,
                                    existing_nodes=nodes)
    want = codec.encode_warm_request([default_prov()], [])
    want.daemonsets.extend(codec.encode_pod(p) for p in ds)
    want.existing_nodes.extend(plain_node(n) for n in nodes)
    assert got == want
    assert got.SerializeToString() == want.SerializeToString()
    assert codec.encode_node(nodes[1]) == plain_node(nodes[1])
    # a node is everything encode_node wrote before, and its pods
    assert codec.encode_node(nodes[0]).labels == nodes[0].labels


def test_one_table_serves_pods_nodes_and_daemonsets():
    shapes = codec.PodShapes()
    codec.encode_request(
        replicas(10), [], [], daemonsets=replicas(3),
        existing_nodes=[node_with(replicas(4)), node_with(replicas(2))],
        shapes=shapes)
    assert (shapes.shapes, shapes.templated_pods, shapes.plain_pods) == (
        1, 18, 1)


# ---- (b) what never shares a template -------------------------------------

#: changes ``test_codec_templates`` lacks: a difference the WIRE holds and
#: Python's ``==`` might not, by the pb.Pod field it lands in
MORE_FIELDS_OFF = {
    "a_request_less": dict(requests={"cpu": 0.5}),
    "a_request_of_minus_zero": (dict(requests={"cpu": 0.0, "memory": GIB}),
                                dict(requests={"cpu": -0.0, "memory": GIB})),
    "a_selector_more": dict(node_selector={L.ZONE: "zone-1a", "k": "v"}),
    "an_affinity_term_more": dict(required_affinity_terms=[
        [Requirement(L.ARCH, IN, ["amd64"])],
        [Requirement(L.ARCH, IN, ["arm64"])]]),
    "an_affinity_operator": dict(required_affinity_terms=[
        [Requirement(L.ARCH, NOT_IN, ["amd64"])]]),
    "a_toleration_less": dict(tolerations=[]),
    "a_spreads_selector": dict(topology_spread=[TopologySpreadConstraint(
        1, L.ZONE, "DoNotSchedule", LabelSelector.of({"app": "y"}))]),
    "a_spreads_key": dict(topology_spread=[TopologySpreadConstraint(
        1, L.HOSTNAME, "DoNotSchedule", LabelSelector.of({"app": "x"}))]),
    "an_affinitys_key": dict(affinity_terms=[PodAffinityTerm(
        LabelSelector.of({"app": "x"}), L.ZONE, anti=True)]),
    "a_cost_of_minus_zero": (dict(deletion_cost=0.0),
                             dict(deletion_cost=-0.0)),
    "a_volume_zone_less": dict(volume_zone_requirements=[]),
}

#: every field of pb.Pod but ``name`` -> the cases above that move it.  A
#: wire field added to ``encode_pod`` lands here first: the list is checked
#: against the descriptor, and each case against ``_shape_key``.
WIRE_FIELD_CASES = {
    "namespace": ["namespace"],
    "labels": ["a_label", "one_label_more"],
    "requests": ["a_request_by_1e-9", "a_request_less",
                 "a_request_of_minus_zero"],
    "node_selector": ["node_selector", "a_selector_more"],
    "required_affinity": ["an_affinity_value", "an_affinity_term_more",
                          "an_affinity_operator"],
    "tolerations": ["a_tolerations_effect", "a_toleration_less"],
    "spread": ["max_skew", "a_soft_spread", "a_spreads_selector",
               "a_spreads_key"],
    "affinity": ["anti_or_not", "an_affinitys_key"],
    "priority": ["priority"],
    "deletion_cost": ["deletion_cost", "a_cost_of_minus_zero"],
    "owner": ["owner"],
    "volume_zone_requirements": ["a_volume_zone", "a_volume_zone_less"],
    "gang_id": ["gang_id"],
    "gang_size": ["gang_size"],
}
CASES = {**ONE_FIELD_OFF, **MORE_FIELDS_OFF}


def test_every_wire_field_of_a_pod_has_a_case():
    assert set(WIRE_FIELD_CASES) == {
        f.name for f in pb.Pod.DESCRIPTOR.fields} - {"name"}
    listed = [c for cases in WIRE_FIELD_CASES.values() for c in cases]
    assert sorted(listed) == sorted(CASES)


@pytest.mark.parametrize("field,change", [
    (f, c) for f, cases in sorted(WIRE_FIELD_CASES.items()) for c in cases])
def test_pods_one_field_apart_never_share_a_shape(field, change):
    case = CASES[change]
    ours, theirs = case if isinstance(case, tuple) else ({}, case)
    pods = [base_pod(**ours), base_pod(name="other-0", **theirs),
            base_pod(name="base-1", **ours)]
    # the case moves exactly the wire field it is listed under
    a, b = codec.encode_pod(pods[0]), codec.encode_pod(pods[1])
    b.name = a.name
    assert a != b
    b.ClearField(field)
    a.ClearField(field)
    assert a == b
    shapes = codec.PodShapes()
    got = pb.SolveRequest()
    shapes.extend(got, "pods", pods)
    assert list(got.pods) == [codec.encode_pod(p) for p in pods]
    assert shapes.shapes == 2
    assert (shapes.templated_pods, shapes.plain_pods) == (1, 2)


def test_labels_in_another_order_keep_their_own_bytes():
    """A map's bytes follow its order; the messages are equal either way.
    The key keeps the two apart, so each pod's bytes are its own build's."""
    pods = [base_pod(name="a-0"),
            base_pod(name="b-0", labels={"tier": "web", "app": "x"}),
            base_pod(name="a-1")]
    shapes = codec.PodShapes()
    got = pb.SolveRequest()
    shapes.extend(got, "pods", pods)
    want = [codec.encode_pod(p) for p in pods]
    assert list(got.pods) == want
    assert ([p.SerializeToString() for p in got.pods]
            == [p.SerializeToString() for p in want])
    assert (shapes.shapes, shapes.templated_pods) == (2, 1)


def test_a_value_that_does_not_hash_encodes_plainly():
    sel = LabelSelector(match_labels=[("app", "x")])   # a list: no hash
    pods = [PodSpec(name=f"u-{i}", labels={"app": "x"},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.ZONE, "DoNotSchedule", sel)]) for i in range(3)]
    with pytest.raises(TypeError):
        hash(codec._shape_key(pods[0]))
    shapes = codec.PodShapes()
    got = pb.SolveRequest()
    shapes.extend(got, "pods", pods)
    assert list(got.pods) == [codec.encode_pod(p) for p in pods]
    assert (shapes.shapes, shapes.templated_pods, shapes.plain_pods) == (
        0, 0, 3)


@pytest.mark.parametrize("name", [
    "",                   # proto3 leaves the field out
    "p",
    "n" * 127,            # the longest one-byte length
    "n" * 128,            # the first two-byte varint
    "n" * 16383,          # the longest two-byte length
    "n" * 16384,          # the first three-byte one
    "pod-é中",            # the length counts bytes, not characters
    "é" * 64,             # 128 bytes in 64 characters
    b"raw",               # upb takes bytes for a string field: plainly
], ids=lambda v: f"{len(v)}{'b' if isinstance(v, bytes) else 'ch'}")
def test_a_name_of_any_length_encodes_right(name):
    pods = [base_pod(name=n or "unnamed") for n in ("first", name, name,
                                                    "last")]
    pods[1].name = pods[2].name = name
    shapes = codec.PodShapes()
    got = pb.SolveRequest()
    shapes.extend(got, "pods", pods)
    want = [codec.encode_pod(p) for p in pods]
    assert list(got.pods) == want
    assert ([p.SerializeToString() for p in got.pods]
            == [p.SerializeToString() for p in want])
    templated = 1 if isinstance(name, bytes) else 3
    assert (shapes.shapes, shapes.templated_pods, shapes.plain_pods) == (
        1, templated, 4 - templated)
    # and the sidecar's key is cut from the same bytes as the parent's
    key = codec.PodTemplates._key
    assert ([key(p, p.name) for p in parsed(got).pods]
            == [key(p, p.name) for p in want])


def test_a_first_pod_without_a_name_is_a_template_too():
    pods = [base_pod(name=f"p-{i}") for i in range(3)]
    pods[0].name = ""
    got = pb.SolveRequest()
    codec.PodShapes().extend(got, "pods", pods)
    assert list(got.pods) == [codec.encode_pod(p) for p in pods]
    assert [p.name for p in got.pods] == ["", "p-1", "p-2"]


# ---- (c) all-distinct pods fall back to the plain build -------------------


@pytest.mark.parametrize("lead,tail,templated,n_shapes", [
    # all distinct: the probe gives up, and what follows is not even keyed
    (distinct(PROBE), replicas(40), 0, PROBE),
    (distinct(PROBE + 1), replicas(40), 0, PROBE),
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
    (replicas(PROBE + 1), [], PROBE, 1),
], ids=["all_distinct_512", "all_distinct_513", "short_511", "half_hits",
        "under_half", "replicas_first", "replicas_513"])
def test_the_table_watches_its_own_hit_share(lead, tail, templated, n_shapes):
    pods = lead + tail
    shapes = codec.PodShapes()
    got = pb.SolveRequest()
    shapes.extend(got, "pods", pods)
    want = [codec.encode_pod(p) for p in pods]
    assert list(got.pods) == want
    assert shapes.templated_pods == templated
    assert shapes.plain_pods == len(pods) - templated
    assert shapes.shapes == n_shapes
    # the probe is the door's own: both sides judge the same pods
    door = codec.PodTemplates()
    door.decode(parsed(got).pods)
    assert (door.templated_pods, door.templates) == (templated, n_shapes)


def test_a_table_that_gave_up_stays_plain_for_the_rest_of_its_request():
    kw = dict(pods=distinct(PROBE + 10), provisioners=[], instance_types=[],
              existing_nodes=[node_with(replicas(20, "on-node"))],
              daemonsets=replicas(5, "ds"))
    shapes = codec.PodShapes()
    got = codec.encode_request(**kw, shapes=shapes)
    assert got == plain_request(**kw)
    assert (shapes.templated_pods, shapes.plain_pods) == (0, PROBE + 35)
    # no table outlives its request: the next one starts over
    again = codec.PodShapes()
    codec.encode_request(replicas(30), [], [], shapes=again)
    assert (again.shapes, again.templated_pods) == (1, 29)


# ---- (d) nothing is remembered --------------------------------------------


@pytest.mark.parametrize("field,value", [
    ("labels", {"app": "mine"}),
    ("requests", {"cpu": 9.0}),
    ("node_selector", {L.ZONE: "zone-1c"}),
    ("tolerations", [Toleration("k", "Exists")]),
    ("topology_spread", []),
    ("affinity_terms", []),
    ("required_affinity_terms", [[Requirement(L.ARCH, IN, ["arm64"])]]),
    ("volume_zone_requirements", []),
    ("namespace", "elsewhere"),
    ("priority", 9),
    ("deletion_cost", 7.5),
    ("owner_key", "someone"),
    ("gang_id", "other"),
    ("gang_size", 5),
])
def test_a_second_encode_sees_a_field_rebound_since_the_first(field, value):
    pods = [base_pod(name=f"s-{i}") for i in range(4)]
    first = codec.encode_request(pods, [], [])
    pods[0].group_key()                  # a memo on a pod is not a shape key
    setattr(pods[2], field, value)
    assert not any(k.startswith("_") and k != "_group_key"
                   for p in pods for k in vars(p))
    shapes = codec.PodShapes()
    second = codec.encode_request(pods, [], [], shapes=shapes)
    assert second == plain_request(pods, [], [])
    assert second != first
    assert second.pods[2] == codec.encode_pod(pods[2])
    assert (shapes.shapes, shapes.templated_pods) == (2, 2)


def test_a_container_written_in_place_between_two_encodes_shows_too():
    """The table holds bytes and value keys, never a caller's container."""
    pods = [base_pod(name=f"s-{i}") for i in range(3)]
    codec.encode_request(pods, [], [])
    pods[1].labels["tier"] = "api"
    again = codec.encode_request(pods, [], [])
    assert again == plain_request(pods, [], [])
    assert dict(again.pods[1].labels) == {"app": "x", "tier": "api"}


# ---- (e) decode_response seats the caller's pods --------------------------


def answer(*nodes) -> pb.SolveResponse:
    resp = pb.SolveResponse(solve_ms=1.5)
    for i, names in enumerate(nodes):
        resp.nodes.append(pb.NewNode(
            name=f"node-{i}", instance_type="m5.large", provisioner="default",
            zone="zone-1a", capacity_type="on-demand", price=0.1,
            pod_names=names))
        for n in names:
            resp.assignments[n] = f"node-{i}"
    resp.infeasible["lost"] = "no room"
    return resp


def test_the_seated_pods_are_the_callers_in_the_replys_order():
    pods = replicas(6, "p")
    resp = answer(["p-4", "p-0", "p-5"], ["p-2"], [])
    got = codec.decode_response(resp, pods)
    assert [[id(p) for p in n.pods] for n in got.nodes] == [
        [id(pods[4]), id(pods[0]), id(pods[5])], [id(pods[2])], []]
    bare = codec.decode_response(resp)
    assert [(n.name, n.instance_type, n.provisioner, n.zone, n.capacity_type,
             n.price, [p.name for p in n.pods]) for n in got.nodes] == [
        (n.name, n.instance_type, n.provisioner, n.zone, n.capacity_type,
         n.price, [p.name for p in n.pods]) for n in bare.nodes]
    assert (got.assignments, got.infeasible, got.solve_ms) == (
        bare.assignments, bare.infeasible, bare.solve_ms)


def test_a_name_the_caller_did_not_send_gets_the_stub():
    pods = replicas(2, "p")
    got = codec.decode_response(answer(["p-1", "stranger", "p-0"]), pods)
    seated = got.nodes[0].pods
    assert seated[0] is pods[1] and seated[2] is pods[0]
    assert seated[1].name == "stranger" and seated[1].requests == {}
    assert all(seated[1] is not p for p in pods)


def test_of_two_pods_with_one_name_the_later_is_seated():
    pods = replicas(3, "p")
    pods[2].name = "p-0"
    got = codec.decode_response(answer(["p-0", "p-1", "p-0"]), pods)
    assert [id(p) for p in got.nodes[0].pods] == [
        id(pods[2]), id(pods[1]), id(pods[2])]


@pytest.mark.parametrize("pods", [None, [], ()],
                         ids=["none", "empty_list", "empty_tuple"])
def test_without_pods_every_name_gets_a_stub_as_before(pods):
    resp = answer(["p-0", "p-1"], ["p-2"])
    got = (codec.decode_response(resp) if pods is None
           else codec.decode_response(resp, pods))
    assert [[p.name for p in n.pods] for n in got.nodes] == [
        ["p-0", "p-1"], ["p-2"]]
    stubs = [p for n in got.nodes for p in n.pods]
    assert len({id(p) for p in stubs}) == 3
    assert all(p.requests == {} and p.labels == {} for p in stubs)
    assert got.assignments == {"p-0": "node-0", "p-1": "node-0",
                               "p-2": "node-1"}
    assert got.infeasible == {"lost": "no room"}


# ---- (f) over real gRPC: the same result, and the client says what it sent -


@pytest.fixture(scope="module")
def served():
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    srv, port = make_server(svc, port=0)
    yield {"port": port}
    srv.stop(grace=None)
    svc.close()


def shape_of(result, pods) -> tuple:
    """A result without the sidecar's node names (a process counter)."""
    order = {n.name: i for i, n in enumerate(result.nodes)}
    return ([(n.instance_type, n.provisioner, n.zone, n.capacity_type,
              n.price, n.allocatable, [id(p) for p in n.pods])
             for n in result.nodes],
            [(name, order[node]) for name, node in result.assignments.items()],
            dict(result.infeasible))


@pytest.mark.parametrize("pods,n_shapes,templated", [
    (deployments(5, 40, "dep"), 5, 195),
    (deployments(1, 1, "lone"), 1, 0),
    (distinct(PROBE + 88, "odd"), PROBE, 0),
    (deployments(3, 20, "big") + [PodSpec(
        name="whale", requests={"cpu": 1e6})], 4, 57),
], ids=["5x40", "one_pod", "all_distinct", "one_infeasible"])
def test_a_solve_over_grpc_returns_what_the_parent_returned(
        served, small_catalog, pods, n_shapes, templated):
    creg = Registry()
    tracer = Tracer(registry=creg, flight=FlightRecorder(registry=creg))
    remote = RemoteScheduler(f"127.0.0.1:{served['port']}", backend="oracle",
                             registry=creg)
    encoded = creg.counter(REQUEST_ENCODE_PODS)
    # zero-initialised like the client's other families
    assert all(encoded.has({"how": how}) and encoded.get({"how": how}) == 0
               for how in REQUEST_ENCODE_HOW)
    provs = [default_prov()]
    try:
        with tracer.start("provision") as trace:
            got = remote.solve(pods, provs, small_catalog, trace=trace)
        # the parent's client: plain request, stubs, then the re-attach loop
        resp = remote.client.solve_raw(plain_request(
            pods, provs, small_catalog, backend="oracle"))
    finally:
        remote.close()
    want = codec.decode_response(resp)
    by_name = {p.name: p for p in pods}
    for node in want.nodes:
        node.pods = [by_name.get(p.name, p) for p in node.pods]
    assert shape_of(got, pods) == shape_of(want, pods)
    assert len(got.assignments) + len(got.infeasible) == len(pods)
    assert {id(p) for n in got.nodes for p in n.pods} <= {id(p) for p in pods}

    assert {how: encoded.get({"how": how}) for how in REQUEST_ENCODE_HOW} == {
        "templated": templated, "plain": len(pods) - templated}
    spans = {sp.name: sp for sp in trace.spans()}
    assert spans["encode"].attrs == {
        "n_pods": len(pods), "shapes": n_shapes, "templated_pods": templated,
        "catalog": "full"}
    assert {"remote", "encode", "rpc", "decode"} <= set(spans)
    text = creg.expose()
    assert f'{REQUEST_ENCODE_PODS}{{how="templated"}}' in text
    assert f'{REQUEST_ENCODE_PODS}{{how="plain"}}' in text
