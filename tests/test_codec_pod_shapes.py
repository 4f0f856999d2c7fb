"""The request's pods by shape (``SolveRequest.pod_shapes`` / ``pod_names``
/ ``pod_shape``), at the sidecar's door.

The contract under test: a request whose pods cross as each distinct pod
once plus a name and an index a pod decodes, field for field and uid for
uid, to what ``decode_pod`` gives over the plain wire (one ``pb.Pod`` a
pod) — whatever the names, whatever the shapes, delta steps included; a
request from a client without these fields (only ``pods``) decodes and
solves as before; the pods by shape come first, then an old client's; and
what does not add up is refused at the door, as a malformed gang is.
"""

import dataclasses

import grpc
import pytest
from test_codec_templates import (
    PROBE,
    base_pod,
    default_prov,
    deployments,
    distinct,
    fields,
    node_with,
    old_wire,
    on_the_wire,
    plain_pods,
    replicas,
)

from karpenter_tpu.metrics import REQUEST_DECODE_PODS, Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodSpec,
    TopologySpreadConstraint,
)
from karpenter_tpu.service import codec
from karpenter_tpu.service import solver_pb2 as pb
from karpenter_tpu.service.client import (
    DeltaSession,
    FleetClient,
    RemoteScheduler,
    SolverClient,
)
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver.scheduler import BatchScheduler


def named(names):
    """One pod of ``base_pod``'s shape a name, "" included."""
    pods = [base_pod() for _ in names]
    for p, name in zip(pods, names):
        p.name = name
    return pods


def unhashable(n):
    sel = LabelSelector(match_labels=[("app", "x")])   # a list: no hash
    return [PodSpec(name=f"u-{i}", labels={"app": "x"},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.ZONE, "DoNotSchedule", sel)]) for i in range(n)]


#: requests by shape, each against the plain wire
CASES = {
    "empty_names": lambda: named(["", "", ""]),
    "one_empty_name_among_named": lambda: named(["a", "", "c", ""]),
    "name_of_127_bytes": lambda: named(["n" * 127, "m" * 127]),
    "name_of_128_bytes": lambda: named(["n" * 128, "m" * 128]),
    "name_of_16_kib": lambda: named(["n" * 16384, "m" * 20000]),
    "non_ascii_names": lambda: named(["pod-é中", "é" * 64, "☃"]),
    "unhashable_shape": lambda: unhashable(4),
    "all_distinct_past_the_probe": lambda: distinct(PROBE + 40),
    "replicas_then_distinct": lambda: replicas(PROBE) + distinct(300),
    "deployments": lambda: deployments(5, 40, "dep"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pods_by_shape_decode_as_the_plain_wire(case):
    pods = CASES[case]()
    req = on_the_wire(pods=pods, provisioners=[], instance_types=[])
    assert not req.pods and len(req.pod_names) == len(pods)
    want = [codec.decode_pod(p) for p in plain_pods(req)]
    shapes = codec.PodTemplates()
    got = codec.decode_request(req, shapes)["pods"]
    assert [p.name for p in got] == [
        p.name or f"pod-{g.uid}" for p, g in zip(pods, got)]
    assert len(got) == len(want)
    for a, b in zip(got, want):
        if a.name.startswith("pod-"):
            # the constructor names an unnamed pod by its own uid
            assert b.name.startswith("pod-")
            assert fields(a) == {**fields(b), "name": a.name}
        else:
            assert fields(a) == fields(b)
        assert set(vars(a)) == set(vars(b))
    uids = [p.uid for p in got]
    assert uids == list(range(uids[0], uids[0] + len(uids)))
    # a shape is decoded once, at its first pod; every later pod stamped
    n = len(req.pod_shapes)
    assert (shapes.templated_pods, shapes.plain_pods) == (len(pods) - n, n)
    assert shapes.templates == n
    # pods of one shape share its containers
    by_shape = {}
    for p, i in zip(got, req.pod_shape):
        assert p.labels is by_shape.setdefault(i, p).labels


def test_an_old_client_decodes_and_solves_as_before(small_catalog):
    """A request with only ``pods`` goes the old wire's way: the same pods,
    the same counts, the same answer as the same pods by shape."""
    pods = deployments(5, 40, "old") + distinct(3, "odd")
    provs = [default_prov()]
    old = old_wire(pods=pods, provisioners=provs,
                   instance_types=small_catalog)
    assert not old.pod_names and len(old.pods) == len(pods)
    shapes = codec.PodTemplates()
    got = codec.decode_request(old, shapes)["pods"]
    assert [fields(p) for p in got] == [
        fields(codec.decode_pod(p)) for p in old.pods]
    assert (shapes.templated_pods, shapes.plain_pods) == (195, 8)
    new = on_the_wire(pods=pods, provisioners=provs,
                      instance_types=small_catalog)
    svc = SolverService(BatchScheduler(backend="oracle", registry=Registry()),
                        registry=Registry())
    try:
        a, b = svc.Solve(old, None), svc.Solve(new, None)
    finally:
        svc.close()
    assert dict(a.assignments).keys() == {p.name for p in pods}
    assert dict(a.infeasible) == dict(b.infeasible)
    order = {n.name: i for i, n in enumerate(a.nodes)}
    border = {n.name: i for i, n in enumerate(b.nodes)}
    assert [(n.instance_type, n.zone, list(n.pod_names)) for n in a.nodes] \
        == [(n.instance_type, n.zone, list(n.pod_names)) for n in b.nodes]
    assert {k: order[v] for k, v in a.assignments.items()} == {
        k: border[v] for k, v in b.assignments.items()}


def test_pods_by_shape_come_first_then_an_old_clients():
    new = replicas(4, "new")
    old = replicas(3, "old")
    req = on_the_wire(pods=new, provisioners=[], instance_types=[])
    req.pods.extend(codec.encode_pod(p) for p in old)
    got = codec.decode_request(req)["pods"]
    assert [p.name for p in got] == [p.name for p in new + old]
    uids = [p.uid for p in got]
    assert uids == list(range(uids[0], uids[0] + 7))


def test_nodes_and_daemonsets_keep_one_message_a_pod():
    ds = replicas(3, "ds")
    nodes = [node_with(replicas(4, "on"), name="n-0")]
    req = on_the_wire(pods=replicas(5), provisioners=[], instance_types=[],
                      existing_nodes=nodes, daemonsets=ds)
    assert [p.name for p in req.daemonsets] == [p.name for p in ds]
    assert list(req.existing_nodes[0].pods) == [
        codec.encode_pod(p) for p in nodes[0].pods]
    got = codec.decode_request(req)
    assert [fields(p) for p in got["existing_nodes"][0].pods] == [
        fields(p) for p in nodes[0].pods]
    assert [fields(p) for p in got["daemonsets"]] == [fields(p) for p in ds]


def test_a_delta_requests_added_pods_go_by_shape(small_catalog):
    added = deployments(2, 6, "add")
    req = pb.SolveRequest.FromString(codec.encode_request(
        added, (), (), session_id="s-1", base_epoch=3, delta=True,
        removed_pods=["gone-0"]).SerializeToString())
    assert not req.pods and list(req.pod_names) == [p.name for p in added]
    assert len(req.pod_shapes) == 2
    got = codec.decode_request(req)["pods"]
    assert [fields(p) for p in got] == [
        fields(codec.decode_pod(p)) for p in plain_pods(req)]
    assert codec.decode_delta_fields(req)["removed"] == ["gone-0"]


@pytest.fixture()
def served():
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    srv, port = make_server(svc, port=0)
    yield svc, port, reg
    srv.stop(grace=None)
    svc.close()


def test_a_session_step_stamps_its_added_pods(served, small_catalog):
    svc, port, reg = served
    decoded = reg.counter(REQUEST_DECODE_PODS)
    sess = DeltaSession(f"127.0.0.1:{port}")
    try:
        sess.solve(deployments(3, 10, "base"), [default_prov()],
                   small_catalog)
        before = decoded.get({"how": "templated"})
        plain_before = decoded.get({"how": "plain"})
        added = deployments(2, 4, "step")
        res = sess.solve_delta(added=added)
    finally:
        sess.close()
    assert {p.name for p in added} <= set(res.assignments)
    # two shapes of four pods each: two decoded, six stamped
    assert decoded.get({"how": "templated"}) - before == 6
    assert decoded.get({"how": "plain"}) - plain_before == 2


@pytest.mark.parametrize("how", ["by_shape", "old_wire"])
def test_a_malformed_gang_is_still_refused_at_the_door(served, small_catalog,
                                                       how):
    _svc, port, _reg = served
    member = dict(requests={"cpu": 0.5}, gang_id="gb", gang_size=4)
    bad = [PodSpec(name="gb-0", **member),
           PodSpec(name="gb-1", **{**member, "gang_size": 6})]
    build = on_the_wire if how == "by_shape" else old_wire
    req = build(pods=bad, provisioners=[default_prov()],
                instance_types=small_catalog)
    client = SolverClient(f"127.0.0.1:{port}")
    client.pods_by_shape = True     # it has heard this sidecar before
    try:
        with pytest.raises(grpc.RpcError) as exc:
            client.solve_raw(req)
    finally:
        client.close()
    assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT


@pytest.mark.parametrize("break_it", ["index_past_the_shapes",
                                      "a_name_without_an_index",
                                      "an_index_without_a_name"])
def test_pods_by_shape_that_do_not_add_up_are_refused(served, small_catalog,
                                                      break_it):
    _svc, port, _reg = served
    req = on_the_wire(pods=replicas(3), provisioners=[default_prov()],
                      instance_types=small_catalog)
    if break_it == "index_past_the_shapes":
        req.pod_shape[1] = len(req.pod_shapes)
    elif break_it == "a_name_without_an_index":
        req.pod_names.append("extra")
    else:
        req.pod_shape.append(0)
    with pytest.raises(codec.MalformedRequest):
        codec.decode_request(req)
    client = SolverClient(f"127.0.0.1:{port}")
    client.pods_by_shape = True     # it has heard this sidecar before
    try:
        with pytest.raises(grpc.RpcError) as exc:
            client.solve_raw(req)
    finally:
        client.close()
    assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_a_fleet_routes_a_request_by_its_pod_count_either_way():
    pods = replicas(300)
    key = FleetClient.bucket_affinity_key
    new = codec.encode_request(pods, [default_prov()], [])
    old = old_wire(pods=pods, provisioners=[default_prov()],
                   instance_types=[])
    assert key(new) == key(old) == "bucket:g512:c0:p1:a1"


def test_every_shape_is_nameless_and_every_name_is_the_callers():
    pods = deployments(3, 5, "nm")
    pods = [dataclasses.replace(p, name=f"x{i}") for i, p in enumerate(pods)]
    req = codec.encode_request(pods, [], [])
    assert [s.name for s in req.pod_shapes] == ["", "", ""]
    assert list(req.pod_names) == [p.name for p in pods]
    assert list(req.pod_shape) == [0] * 5 + [1] * 5 + [2] * 5


# ---- a sidecar older than the pods by shape --------------------------------


class Rollback(SolverService):
    """While ``old``, a sidecar from before the pods by shape: it skips
    ``SolveRequest`` 23-25 as an older parser does, and its replies never
    say it reads them."""

    old = True

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        #: the fields each Solve came with: "by_shape" or "one_by_one"
        self.wires = []

    def Solve(self, request, context):
        self.wires.append("by_shape" if request.pod_names else "one_by_one")
        if self.old:
            for field in ("pod_shapes", "pod_names", "pod_shape"):
                request.ClearField(field)
        resp = super().Solve(request, context)
        if self.old:
            resp.ClearField("pods_by_shape")
        return resp


@pytest.fixture()
def rollback():
    reg = Registry()
    svc = Rollback(BatchScheduler(backend="oracle", registry=reg),
                   registry=reg)
    srv, port = make_server(svc, port=0)
    yield svc, f"127.0.0.1:{port}"
    srv.stop(grace=None)
    svc.close()


def test_a_client_sends_by_shape_only_once_the_sidecar_said_it_reads_them(
        rollback, small_catalog):
    svc, target = rollback
    svc.old = False
    remote = RemoteScheduler(target, backend="oracle", registry=Registry())
    try:
        for k in range(3):
            pods = deployments(3, 10, f"up{k}")
            res = remote.solve(pods, [default_prov()], small_catalog)
            assert set(res.assignments) == {p.name for p in pods}
    finally:
        remote.close()
    assert svc.wires == ["one_by_one", "by_shape", "by_shape"]


def test_an_old_sidecar_gets_every_pod_one_message_a_pod(rollback,
                                                         small_catalog):
    svc, target = rollback
    remote = RemoteScheduler(target, backend="oracle", registry=Registry())
    try:
        for k in range(3):
            pods = deployments(3, 10, f"old{k}")
            res = remote.solve(pods, [default_prov()], small_catalog)
            assert set(res.assignments) == {p.name for p in pods}
            assert not res.infeasible
    finally:
        remote.close()
    assert svc.wires == ["one_by_one"] * 3
    assert remote.client.pods_by_shape is False


def test_a_sidecar_rolled_back_under_the_client_solves_every_pod(
        rollback, small_catalog):
    """The client heard a new sidecar; the next reply comes from an old one
    that solved none of the pods sent by shape: the request goes once more,
    one message a pod, and the client sends so from then on."""
    svc, target = rollback
    svc.old = False
    remote = RemoteScheduler(target, backend="oracle", registry=Registry())
    try:
        remote.solve(deployments(2, 5, "new"), [default_prov()],
                     small_catalog)
        assert remote.client.pods_by_shape is True
        svc.old = True
        for k in range(2):
            pods = deployments(3, 10, f"back{k}")
            res = remote.solve(pods, [default_prov()], small_catalog)
            assert set(res.assignments) == {p.name for p in pods}
            assert not res.infeasible
    finally:
        remote.close()
    assert svc.wires == ["one_by_one", "by_shape", "one_by_one",
                         "one_by_one"]
    assert remote.client.pods_by_shape is False


def test_a_session_step_meeting_a_rolled_back_sidecar_re_establishes(
        rollback, small_catalog):
    """A delta step by shape that an old sidecar applied without its added
    pods never leaves the chain diverged: its second send meets the epoch
    the first moved on, and the session re-establishes by one full solve
    that carries every pod of the ledger."""
    svc, target = rollback
    svc.old = False
    sess = DeltaSession(target)
    try:
        base = deployments(3, 10, "base")
        sess.solve(base, [default_prov()], small_catalog)
        sess.solve_delta(added=deployments(1, 4, "one"))
        resends = sess.full_resends
        svc.old = True
        added = deployments(2, 4, "two")
        res = sess.solve_delta(added=added)
        later = deployments(1, 3, "three")
        res2 = sess.solve_delta(added=later)
    finally:
        sess.close()
    assert {p.name for p in added} <= set(res.assignments)
    assert {p.name for p in base + added + later} <= set(res2.assignments)
    assert sess.full_resends == resends + 1
    # the step the old sidecar took without its pods, its second send,
    # the re-establishing solve, the next step: one message a pod
    assert svc.wires == ["one_by_one", "by_shape", "by_shape", "one_by_one",
                         "one_by_one", "one_by_one"]


@pytest.mark.parametrize("case", ["replicas", "empty_names",
                                  "after_old_wire_pods"])
def test_pods_one_by_one_is_the_old_wire(case):
    pods = {"replicas": lambda: deployments(3, 4, "ob"),
            "empty_names": lambda: named(["", "a", ""]),
            "after_old_wire_pods": lambda: replicas(4, "new")}[case]()
    req = on_the_wire(pods=pods, provisioners=[], instance_types=[])
    extra = replicas(2, "old") if case == "after_old_wire_pods" else []
    req.pods.extend(codec.encode_pod(p) for p in extra)
    codec.pods_one_by_one(req)
    assert not (req.pod_shapes or req.pod_names or req.pod_shape)
    assert list(req.pods) == [codec.encode_pod(p) for p in pods + extra]
