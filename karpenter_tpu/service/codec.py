"""proto <-> model conversion for the solver service."""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..models.instancetype import InstanceType, Offering, Overhead
from ..models.machine import Machine
from ..models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from ..models.provisioner import KubeletConfiguration, Provisioner
from ..models.requirements import Requirement, Requirements
from ..solver.types import SimNode, SolveResult
from . import solver_pb2 as pb

# ---------------------------------------------------------------------------
# encode (model -> proto)
# ---------------------------------------------------------------------------


def _q(resource: str, value: float) -> pb.Quantity:
    return pb.Quantity(resource=resource, value=value)


def _quantities(d) -> List[pb.Quantity]:
    return [_q(k, v) for k, v in sorted(d.items())]


def _req(r: Requirement) -> pb.Requirement:
    return pb.Requirement(key=r.key, op=r.operator, values=list(r.values))


def _selector(s: LabelSelector) -> pb.LabelSelector:
    out = pb.LabelSelector()
    for k, v in s.match_labels:
        out.match_labels[k] = v
    out.match_expressions.extend(_req(r) for r in s.match_expressions)
    return out


def encode_pod(p: PodSpec) -> pb.Pod:
    out = pb.Pod(
        name=p.name, namespace=p.namespace, priority=p.priority,
        deletion_cost=p.deletion_cost, owner=p.owner_key,
        gang_id=p.gang_id, gang_size=p.gang_size,
    )
    for k, v in p.labels.items():
        out.labels[k] = v
    out.requests.extend(_quantities(p.requests))
    for k, v in p.node_selector.items():
        out.node_selector[k] = v
    for term in p.required_affinity_terms:
        out.required_affinity.append(pb.RequirementTerm(requirements=[_req(r) for r in term]))
    out.tolerations.extend(
        pb.Toleration(key=t.key, op=t.operator, value=t.value, effect=t.effect)
        for t in p.tolerations
    )
    out.spread.extend(
        pb.TopologySpread(max_skew=t.max_skew, topology_key=t.topology_key,
                          hard=t.hard, selector=_selector(t.label_selector))
        for t in p.topology_spread
    )
    out.affinity.extend(
        pb.AffinityTerm(selector=_selector(t.label_selector),
                        topology_key=t.topology_key, anti=t.anti)
        for t in p.affinity_terms
    )
    out.volume_zone_requirements.extend(_req(r) for r in p.volume_zone_requirements)
    return out


def _shape_key(p: PodSpec) -> tuple:
    """The value of every field :func:`encode_pod` reads except ``name``,
    as far apart as the wire holds them: a dict in its own order (a map's
    bytes follow it) and a zero by its ``repr`` (``-0.0 == 0.0`` in Python,
    not in a ``pb.Pod``).  Hashing it raises ``TypeError`` where a value
    does not hash."""
    cost, requests = p.deletion_cost, p.requests
    return (
        p.namespace, tuple(p.labels.items()),
        repr(requests) if 0 in requests.values() else tuple(requests.items()),
        tuple(p.node_selector.items()),
        tuple(map(tuple, p.required_affinity_terms)),
        tuple(p.tolerations), tuple(p.topology_spread),
        tuple(p.affinity_terms), p.priority, cost or repr(cost), p.owner_key,
        tuple(p.volume_zone_requirements), p.gang_id, p.gang_size,
    )


def _nameless(p: PodSpec) -> pb.Pod:
    """:func:`encode_pod` of ``p`` with the name left off: its shape."""
    out = encode_pod(p)
    out.name = ""
    return out


class PodShapes:
    """The pod shapes of ONE request on its way out: a ``pb.Pod`` is built
    once per distinct :func:`_shape_key`, not once per pod — the client's
    half of what :class:`PodTemplates` does at the sidecar's door.

    The request's own pods go out by shape (:meth:`index`): every shape
    once in ``pod_shapes``, nameless, and every pod as its name in
    ``pod_names`` beside its shape's index in ``pod_shape``, in the
    caller's order.  The pods of ``existing_nodes`` and ``daemonsets`` stay
    one ``pb.Pod`` each (:meth:`extend`): a copy of their shape's message
    with their own name.  Either way :func:`encode_pod` stays the only code
    that turns a ``PodSpec``'s fields into a ``pb.Pod``'s.

    The table watches its own hit share as ``PodTemplates`` does: fewer
    than half hits ``PROBE`` pods into the request and every later pod is a
    shape of its own, so a request of all-distinct pods costs what the
    plain build costs, and one index a pod.  One table serves one request —
    ``pods``, the pods of ``existing_nodes``, ``daemonsets`` — and is
    dropped with it; nothing is kept between requests and nothing is
    memoised on a pod."""

    #: pods a request encodes before the table judges its hit share
    PROBE = 512

    def __init__(self) -> None:
        self._index: Dict[tuple, int] = {}
        #: every shape met, nameless, in the order met: what ``pod_shape``
        #: indexes
        self._built: List[pb.Pod] = []
        self._seen = 0
        self._live = True
        #: pods written from a shape met before / pods that went through
        #: encode_pod
        self.templated_pods = 0
        self.plain_pods = 0

    @property
    def shapes(self) -> int:
        """Distinct shapes the table holds (it stops adding to them once
        it has given up on the request)."""
        return len(self._index)

    def _shape_of(self, pods: Sequence[PodSpec]) -> List[int]:
        """Each pod's index into the shapes, in order; a shape not met
        before is built and added."""
        index, built, key_of = self._index, self._built, _shape_key
        seen, hits = self._seen, 0
        at: List[int] = []
        it = iter(pods)
        if self._live:
            for p in it:
                try:
                    key = key_of(p)
                    i = index.get(key)
                except TypeError:
                    # a value that does not hash: a shape of its own
                    key = i = None
                if i is None:
                    i = len(built)
                    built.append(_nameless(p))
                    if key is not None:
                        index[key] = i
                else:
                    hits += 1
                at.append(i)
                seen += 1
                if seen == self.PROBE and 2 * (self.templated_pods + hits) < seen:
                    self._live = False
                    break
            self._seen = seen
        for p in it:
            at.append(len(built))
            built.append(_nameless(p))
        self.templated_pods += hits
        self.plain_pods += len(at) - hits
        return at

    def index(self, req: pb.SolveRequest, pods: Sequence[PodSpec]) -> None:
        """The pods of ``req``, by shape: what ``req.pods.extend(encode_pod(p)
        for p in pods)`` would carry, in ``pod_shapes`` / ``pod_names`` /
        ``pod_shape``."""
        at = self._shape_of(pods)
        req.pod_shapes.extend(self._built)
        req.pod_names.extend([p.name for p in pods])
        req.pod_shape.extend(at)

    def extend(self, msg, field: str, pods: Sequence[PodSpec]) -> None:
        """``getattr(msg, field).extend(encode_pod(p) for p in pods)``,
        one copy of a shape's message a pod."""
        out, built = getattr(msg, field), self._built
        for p, i in zip(pods, self._shape_of(pods)):
            pod = out.add()
            pod.CopyFrom(built[i])
            pod.name = p.name


def pods_one_by_one(req: pb.SolveRequest) -> None:
    """Moves ``req``'s pods by shape onto ``pods``, one message a pod, in
    the order the sidecar decodes them: the wire of a sidecar older than
    ``pod_shapes``, which every sidecar reads."""
    shapes, rest = req.pod_shapes, list(req.pods)
    req.ClearField("pods")
    for name, i in zip(req.pod_names, req.pod_shape):
        pod = req.pods.add()
        pod.CopyFrom(shapes[i])
        pod.name = name
    req.pods.extend(rest)
    for field in ("pod_shapes", "pod_names", "pod_shape"):
        req.ClearField(field)


def encode_instance_type(it: InstanceType) -> pb.InstanceType:
    out = pb.InstanceType(name=it.name)
    out.requirements.extend(_req(r) for r in it.requirements.to_list())
    out.offerings.extend(
        pb.Offering(zone=o.zone, capacity_type=o.capacity_type,
                    price=o.price, available=o.available)
        for o in it.offerings
    )
    out.capacity.extend(_quantities(it.capacity))
    out.overhead.extend(_quantities(it.overhead.total()))  # legacy decoders
    out.overhead_kube.extend(_quantities(it.overhead.kube_reserved))
    out.overhead_system.extend(_quantities(it.overhead.system_reserved))
    out.overhead_eviction.extend(_quantities(it.overhead.eviction_threshold))
    out.has_overhead_components = True
    return out


def encode_provisioner(p: Provisioner) -> pb.Provisioner:
    out = pb.Provisioner(
        name=p.name, weight=p.weight, consolidation_enabled=p.consolidation_enabled,
    )
    out.requirements.extend(_req(r) for r in p.requirements)
    out.taints.extend(pb.Taint(key=t.key, value=t.value, effect=t.effect) for t in p.taints)
    out.startup_taints.extend(
        pb.Taint(key=t.key, value=t.value, effect=t.effect) for t in p.startup_taints
    )
    for k, v in p.labels.items():
        out.labels[k] = v
    out.limits.extend(_quantities(p.limits))
    if p.kubelet is not None:
        kc = p.kubelet
        out.kubelet.CopyFrom(pb.KubeletConfiguration(
            has_max_pods=kc.max_pods is not None,
            max_pods=kc.max_pods or 0,
            has_pods_per_core=kc.pods_per_core is not None,
            pods_per_core=kc.pods_per_core or 0,
        ))
        out.kubelet.system_reserved.extend(_quantities(kc.system_reserved))
        out.kubelet.kube_reserved.extend(_quantities(kc.kube_reserved))
        for k, v in kc.eviction_hard.items():
            out.kubelet.eviction_hard[k] = v
        for k, v in kc.eviction_soft.items():
            out.kubelet.eviction_soft[k] = v
    return out


def encode_node(n: SimNode,
                shapes: Optional[PodShapes] = None) -> pb.ExistingNode:
    out = pb.ExistingNode(
        name=n.name, instance_type=n.instance_type, provisioner=n.provisioner,
        zone=n.zone, capacity_type=n.capacity_type, price=n.price,
    )
    out.allocatable.extend(_quantities(n.allocatable))
    for k, v in n.labels.items():
        out.labels[k] = v
    out.taints.extend(pb.Taint(key=t.key, value=t.value, effect=t.effect) for t in n.taints)
    (PodShapes() if shapes is None else shapes).extend(out, "pods", n.pods)
    return out


def encode_request(
    pods: Sequence[PodSpec],
    provisioners: Sequence[Provisioner],
    instance_types: Sequence[InstanceType],
    existing_nodes: Sequence[SimNode] = (),
    daemonsets: Sequence[PodSpec] = (),
    unavailable: Optional[Set[tuple]] = None,
    allow_new_nodes: bool = True,
    max_new_nodes: Optional[int] = None,
    backend: str = "",
    priority: str = "",
    deadline_ms: Optional[float] = None,
    session_id: str = "",
    base_epoch: int = 0,
    delta: bool = False,
    removed_pods: Sequence[str] = (),
    reclaimed_nodes: Sequence[str] = (),
    catalog_epoch: int = 0,
    trace_id: str = "",
    parent_span: str = "",
    session_nonce: str = "",
    shapes: Optional[PodShapes] = None,
    catalog_digest: str = "",
) -> pb.SolveRequest:
    """``shapes``: the caller's table for THIS request, handed in only so
    that its counts can be read afterwards (``RemoteScheduler.solve``).
    ``catalog_digest``: the sidecar's own name for ``instance_types``
    (``SolveResponse.catalog_digest``); it goes in the list's place."""
    # admission fields (docs/ADMISSION.md): "" / 0 are the backward-
    # compatible wire defaults — the server folds them into its configured
    # default class / deadline, so an old client is indistinguishable from
    # one that sent nothing.  The delta-session fields (ARCHITECTURE.md
    # round 14) default the same way: an empty session_id is a classic
    # full solve; delta=True reuses the request's pods for the ADDED pods
    # and `unavailable` for the newly ICE'd offerings.  The trace context
    # (ISSUE 15) defaults to "no context": the server roots locally.
    req = pb.SolveRequest(allow_new_nodes=allow_new_nodes, backend=backend,
                          priority_class=priority or "",
                          deadline_ms=float(deadline_ms or 0.0),
                          session_id=session_id or "",
                          base_epoch=int(base_epoch or 0),
                          delta=bool(delta),
                          catalog_epoch=int(catalog_epoch or 0),
                          trace_id=trace_id or "",
                          parent_span=parent_span or "",
                          session_nonce=session_nonce or "",
                          catalog_digest=catalog_digest or "")
    req.removed_pods.extend(removed_pods)
    req.reclaimed_nodes.extend(reclaimed_nodes)
    if shapes is None:
        shapes = PodShapes()
    shapes.index(req, pods)
    req.provisioners.extend(encode_provisioner(p) for p in provisioners)
    if not catalog_digest:
        req.instance_types.extend(
            encode_instance_type(t) for t in instance_types)
    req.existing_nodes.extend(encode_node(n, shapes) for n in existing_nodes)
    shapes.extend(req, "daemonsets", daemonsets)
    for (t, z, c) in sorted(unavailable or ()):
        req.unavailable.append(pb.UnavailableOffering(instance_type=t, zone=z, capacity_type=c))
    if max_new_nodes is not None:
        req.has_max_new_nodes = True
        req.max_new_nodes = max_new_nodes
    return req


def encode_warm_request(
    provisioners: Sequence[Provisioner],
    instance_types: Sequence[InstanceType],
    daemonsets: Sequence[PodSpec] = (),
    existing_nodes: Sequence[SimNode] = (),
    backend: str = "",
) -> pb.WarmRequest:
    req = pb.WarmRequest(backend=backend)
    shapes = PodShapes()
    req.provisioners.extend(encode_provisioner(p) for p in provisioners)
    req.instance_types.extend(encode_instance_type(t) for t in instance_types)
    shapes.extend(req, "daemonsets", daemonsets)
    req.existing_nodes.extend(encode_node(n, shapes) for n in existing_nodes)
    return req


def encode_response(result: SolveResult) -> pb.SolveResponse:
    out = pb.SolveResponse(solve_ms=result.solve_ms)
    for n in result.nodes:
        out.nodes.append(pb.NewNode(
            name=n.name, instance_type=n.instance_type, provisioner=n.provisioner,
            zone=n.zone, capacity_type=n.capacity_type, price=n.price,
            pod_names=[p.name for p in n.pods],
        ))
    for k, v in result.assignments.items():
        out.assignments[k] = v
    for k, v in result.infeasible.items():
        out.infeasible[k] = v
    return out


# ---------------------------------------------------------------------------
# decode (proto -> model)
# ---------------------------------------------------------------------------


def _qdict(qs) -> Dict[str, float]:
    return {q.resource: q.value for q in qs}


def _dreq(r: pb.Requirement) -> Requirement:
    return Requirement(r.key, r.op, list(r.values))


def _dselector(s: pb.LabelSelector) -> LabelSelector:
    return LabelSelector(
        tuple(sorted(s.match_labels.items())),
        tuple(_dreq(r) for r in s.match_expressions),
    )


def decode_pod(p: pb.Pod) -> PodSpec:
    return PodSpec(
        name=p.name,
        namespace=p.namespace or "default",
        labels=dict(p.labels),
        requests=_qdict(p.requests),
        node_selector=dict(p.node_selector),
        required_affinity_terms=[[_dreq(r) for r in t.requirements] for t in p.required_affinity],
        tolerations=[Toleration(t.key, t.op or "Equal", t.value, t.effect) for t in p.tolerations],
        topology_spread=[
            TopologySpreadConstraint(
                t.max_skew, t.topology_key,
                "DoNotSchedule" if t.hard else "ScheduleAnyway",
                _dselector(t.selector),
            )
            for t in p.spread
        ],
        affinity_terms=[
            PodAffinityTerm(_dselector(t.selector), t.topology_key, t.anti)
            for t in p.affinity
        ],
        priority=p.priority,
        deletion_cost=p.deletion_cost or 1.0,
        owner_key=p.owner,
        volume_zone_requirements=[_dreq(r) for r in p.volume_zone_requirements],
        # old wire bytes carry no gang tags and decode to ""/0 = ungrouped
        gang_id=p.gang_id,
        gang_size=p.gang_size,
    )


class MalformedRequest(ValueError):
    """A request whose pods by shape do not add up: ``pod_names`` and
    ``pod_shape`` of unequal length, or an index past ``pod_shapes``."""


class PodTemplates:
    """The pod shapes of ONE request: a ``PodSpec`` is built once per
    distinct shape, not once per pod.

    A Solve carries the pods of a few deployments (20 in a 50,000-pod
    batch), and pods of one deployment differ in ``name`` only.  A client
    that says so sends each shape once (:meth:`stamp`).  On the old wire,
    one ``pb.Pod`` a pod (:meth:`decode`), a pod's shape key is its own
    serialized bytes after the leading ``name`` field: equal keys mean
    equal bytes, so equal fields.  Either way the first pod of a shape goes
    through :func:`decode_pod`; every later one is stamped from that pod's
    field values (``PodSpec.like``) with its own name and the next uid, and
    SHARES the first one's containers — the copy-on-write convention stated
    at ``PodSpec``.  On the old wire a key that differs for two equal pods
    (map order) costs a plain decode, never a wrong answer.

    On the old wire the table watches its own hit share: if, ``PROBE`` pods
    into the request, fewer than half were hits, the rest decode plainly (a
    batch of all-distinct pods would only pay for the keys).  One table
    serves one request — its pods by shape, ``pods``, the pods of
    ``existing_nodes``, ``daemonsets`` — and is dropped with it; nothing is
    kept between requests."""

    #: pods a request decodes before the table judges its hit share
    PROBE = 512

    def __init__(self) -> None:
        self._fields: Dict[bytes, dict] = {}
        self._seen = 0
        self._hits = 0
        self._live = True
        #: shapes decoded by :meth:`stamp`
        self._stamped = 0
        #: pods stamped from a template / pods that went through decode_pod
        #: (the first pod of each shape, by shape or not)
        self.templated_pods = 0
        self.plain_pods = 0

    @property
    def templates(self) -> int:
        """Distinct shapes the table holds (on the old wire it stops adding
        to them once it has given up on the request)."""
        return len(self._fields) + self._stamped

    def stamp(self, shapes, names, index) -> List[PodSpec]:
        """The pods of ``pod_shapes`` / ``pod_names`` / ``pod_shape``, in
        order: ``[decode_pod(p) for p in pods]`` over the plain wire, field
        for field and uid for uid.  A shape is decoded when its first pod
        comes; an empty name takes the constructor's rule (``pod-<uid>``)."""
        if len(names) != len(index):
            raise MalformedRequest(
                f"{len(names)} pod_names against {len(index)} pod_shape")
        fields: List[Optional[dict]] = [None] * len(shapes)
        like = PodSpec.like
        out: List[PodSpec] = []
        decoded = 0
        for name, i in zip(names, index):
            try:
                shape = fields[i]
            except IndexError:
                raise MalformedRequest(
                    f"pod_shape {i} past the {len(shapes)} pod_shapes"
                ) from None
            if shape is None:
                pod = decode_pod(shapes[i])
                pod.name = name or f"pod-{pod.uid}"
                fields[i] = pod.template()
                decoded += 1
            else:
                pod = like(shape, name)
                if not name:
                    pod.name = f"pod-{pod.uid}"
            out.append(pod)
        self._stamped += decoded
        self.templated_pods += len(out) - decoded
        self.plain_pods += decoded
        return out

    @staticmethod
    def _key(p: pb.Pod, name: str) -> Optional[bytes]:
        """``p``'s bytes after its ``name`` field, or None where they do
        not start with exactly that field (tag 0x0a, varint length, the
        name): an empty name (proto3 leaves the field out, and the
        constructor then names the pod by its uid), a name of 16 KiB."""
        data = p.SerializeToString()
        raw = name.encode()
        n = len(raw)
        if 0 < n < 0x80:
            at = 2
            ok = data[1] == n
        elif 0x80 <= n < 0x4000:
            at = 3
            ok = data[1] == (n & 0x7F) | 0x80 and data[2] == n >> 7
        else:
            return None
        if ok and data[0] == 0x0A and data.startswith(raw, at):
            return data[at + n:]
        return None

    def decode(self, pods) -> List[PodSpec]:
        """``[decode_pod(p) for p in pods]``, field for field and uid for
        uid, by template."""
        if not self._live:
            self.plain_pods += len(pods)
            return [decode_pod(p) for p in pods]
        fields, key_of, like = self._fields, self._key, PodSpec.like
        seen, hits = self._seen, 0
        out: List[PodSpec] = []
        it = iter(pods)
        for p in it:
            name = p.name
            key = key_of(p, name)
            shape = None if key is None else fields.get(key)
            if shape is not None:
                out.append(like(shape, name))
                hits += 1
            else:
                pod = decode_pod(p)
                if key is not None:
                    fields[key] = pod.template()
                out.append(pod)
            seen += 1
            if seen == self.PROBE and 2 * (self._hits + hits) < seen:
                self._live = False
                out.extend(decode_pod(q) for q in it)
                break
        self._seen = seen
        self._hits += hits
        self.templated_pods += hits
        self.plain_pods += len(out) - hits
        return out


def decode_instance_type(it: pb.InstanceType) -> InstanceType:
    return InstanceType(
        name=it.name,
        requirements=Requirements([_dreq(r) for r in it.requirements]),
        offerings=[
            Offering(o.zone, o.capacity_type, o.price, o.available) for o in it.offerings
        ],
        capacity=_qdict(it.capacity),
        overhead=(
            Overhead(
                kube_reserved=_qdict(it.overhead_kube),
                system_reserved=_qdict(it.overhead_system),
                eviction_threshold=_qdict(it.overhead_eviction),
            )
            if it.has_overhead_components
            # older encoders: field 5 carries either the pre-summed total
            # (original wire format; fields 6/7 empty) or kube-reserved with
            # system/eviction in 6/7 — reading 6/7 here is correct for both
            # (empty lists decode to {} for the original format)
            else Overhead(
                kube_reserved=_qdict(it.overhead),
                system_reserved=_qdict(it.overhead_system),
                eviction_threshold=_qdict(it.overhead_eviction),
            )
        ),
    )


def decode_provisioner(p: pb.Provisioner) -> Provisioner:
    kubelet = None
    if p.HasField("kubelet"):
        kc = p.kubelet
        kubelet = KubeletConfiguration(
            max_pods=kc.max_pods if kc.has_max_pods else None,
            pods_per_core=kc.pods_per_core if kc.has_pods_per_core else None,
            system_reserved=_qdict(kc.system_reserved),
            kube_reserved=_qdict(kc.kube_reserved),
            eviction_hard=dict(kc.eviction_hard),
            eviction_soft=dict(kc.eviction_soft),
        )
    return Provisioner(
        name=p.name,
        requirements=[_dreq(r) for r in p.requirements],
        taints=[Taint(t.key, t.effect, t.value) for t in p.taints],
        startup_taints=[Taint(t.key, t.effect, t.value) for t in p.startup_taints],
        labels=dict(p.labels),
        limits=_qdict(p.limits),
        weight=p.weight,
        consolidation_enabled=p.consolidation_enabled,
        kubelet=kubelet,
    )


def decode_node(n: pb.ExistingNode,
                templates: Optional[PodTemplates] = None) -> SimNode:
    return SimNode(
        instance_type=n.instance_type,
        provisioner=n.provisioner,
        zone=n.zone,
        capacity_type=n.capacity_type,
        price=n.price,
        allocatable=_qdict(n.allocatable),
        labels=dict(n.labels),
        taints=[Taint(t.key, t.effect, t.value) for t in n.taints],
        pods=(PodTemplates() if templates is None
              else templates).decode(n.pods),
        existing=True,
        name=n.name,
    )


def catalog_digest(instance_types: Sequence[pb.InstanceType]) -> str:
    """The sidecar's name for a list of wire instance types: equal lists
    (same types, same order) get equal names.  Only the sidecar computes
    one, so no two parties can disagree on what a digest means."""
    h = hashlib.blake2b(digest_size=16)
    for t in instance_types:
        data = t.SerializeToString(deterministic=True)
        h.update(len(data).to_bytes(4, "big"))
        h.update(data)
    return h.hexdigest()


def decode_request(req: pb.SolveRequest,
                   templates: Optional[PodTemplates] = None,
                   catalog: Optional[List[InstanceType]] = None):
    """``templates``: the caller's table for THIS request, handed in only
    so that its counts can be read afterwards (``SolverService.Solve``).
    ``catalog``: the list the sidecar kept under the request's
    ``catalog_digest``; it stands in for the ``instance_types`` the
    request left out."""
    if templates is None:
        templates = PodTemplates()
    # the pods by shape, in the client's order, then an old client's
    pods = templates.stamp(req.pod_shapes, req.pod_names, req.pod_shape)
    pods += templates.decode(req.pods)
    return dict(
        pods=pods,
        provisioners=[decode_provisioner(p) for p in req.provisioners],
        instance_types=(catalog if catalog is not None else
                        [decode_instance_type(t) for t in req.instance_types]),
        existing_nodes=[decode_node(n, templates) for n in req.existing_nodes],
        daemonsets=templates.decode(req.daemonsets),
        unavailable={(u.instance_type, u.zone, u.capacity_type) for u in req.unavailable},
        allow_new_nodes=req.allow_new_nodes,
        max_new_nodes=req.max_new_nodes if req.has_max_new_nodes else None,
    )


def decode_trace_fields(req: pb.SolveRequest) -> "Tuple[str, str]":
    """The wire trace context of a SolveRequest: ``(trace_id,
    parent_span)``.  ``("", "")`` — old clients, unsampled origins —
    means "no remote parent"; every server entry that reads this must
    open its trace through ``Tracer.start_remote`` (ktlint KT019), which
    maps the empty context to a plain local start."""
    return (getattr(req, "trace_id", "") or "",
            getattr(req, "parent_span", "") or "")


def decode_delta_fields(req: pb.SolveRequest) -> Optional[dict]:
    """The delta-session envelope of a SolveRequest, or None for a classic
    (sessionless) solve.  Kept OUT of :func:`decode_request`'s dict — that
    dict feeds ``scheduler.solve(**kwargs)`` verbatim, and an old decoder
    reading new-field defaults must keep behaving like a plain solve."""
    sid = getattr(req, "session_id", "")
    if not sid:
        return None
    return dict(
        session_id=sid,
        base_epoch=int(getattr(req, "base_epoch", 0)),
        delta=bool(getattr(req, "delta", False)),
        removed=list(getattr(req, "removed_pods", ())),
        reclaimed=list(getattr(req, "reclaimed_nodes", ())),
        catalog_epoch=int(getattr(req, "catalog_epoch", 0)),
        # chain-identity nonce (ISSUE 17 divergence fix): "" from an old
        # client is the legacy wildcard — the server's nonce check only
        # fires when BOTH sides carry one
        nonce=str(getattr(req, "session_nonce", "") or ""),
    )


def encode_delta_reply(reply) -> pb.SolveResponse:
    """service/delta.DeltaReply -> wire.  Incremental replies carry only
    the step's changes; ``session_state``/``session_epoch``/``delta_mode``
    tell the client how to merge (service/client.DeltaSession)."""
    out = pb.SolveResponse(
        solve_ms=reply.solve_ms,
        session_epoch=int(reply.epoch),
        session_state=reply.state,
        delta_mode=reply.mode,
        session_nonce=getattr(reply, "nonce", "") or "",
    )
    for n in reply.nodes:
        out.nodes.append(pb.NewNode(
            name=n.name, instance_type=n.instance_type,
            provisioner=n.provisioner, zone=n.zone,
            capacity_type=n.capacity_type, price=n.price,
            pod_names=[p.name for p in n.pods],
        ))
    for k, v in reply.assignments.items():
        out.assignments[k] = v
    for k, v in reply.infeasible.items():
        out.infeasible[k] = v
    out.removed_nodes.extend(reply.removed_nodes)
    return out


#: delta_mode values whose reply carries the WHOLE solution (the client
#: replaces its ledger wholesale instead of merging the step's changes)
FULL_REPLY_MODES = ("establish", "reseed", "full", "")


def decode_delta_reply(resp: pb.SolveResponse):
    """wire -> service/delta.DeltaReply (node pods are name-stub PodSpecs,
    like :func:`decode_response`; DeltaSession re-attaches its ledger's
    real objects)."""
    from .delta import DeltaReply

    nodes = []
    for n in resp.nodes:
        node = SimNode(
            instance_type=n.instance_type, provisioner=n.provisioner,
            zone=n.zone, capacity_type=n.capacity_type, price=n.price,
            allocatable={}, name=n.name,
        )
        node.pods = [PodSpec(name=pn) for pn in n.pod_names]
        nodes.append(node)
    mode = getattr(resp, "delta_mode", "")
    return DeltaReply(
        state=getattr(resp, "session_state", ""),
        epoch=int(getattr(resp, "session_epoch", 0)),
        mode=mode,
        full=mode in FULL_REPLY_MODES,
        assignments=dict(resp.assignments),
        infeasible=dict(resp.infeasible),
        nodes=nodes,
        removed_nodes=list(getattr(resp, "removed_nodes", ())),
        solve_ms=resp.solve_ms,
        nonce=str(getattr(resp, "session_nonce", "") or ""),
    )


def decode_warm_request(req: pb.WarmRequest):
    templates = PodTemplates()
    return dict(
        provisioners=[decode_provisioner(p) for p in req.provisioners],
        instance_types=[decode_instance_type(t) for t in req.instance_types],
        daemonsets=templates.decode(req.daemonsets),
        existing_nodes=[decode_node(n, templates) for n in req.existing_nodes],
    )


def decode_response(resp: pb.SolveResponse,
                    pods: Optional[Sequence[PodSpec]] = None) -> SolveResult:
    """``pods``: the pods of the request this answers.  A node's ``pods``
    are then the caller's own objects, looked up by name (of two pods with
    one name, the later); a name the caller did not send, and every name
    without ``pods``, gets a name-stub ``PodSpec`` — the wire carries
    names only."""
    seat = {p.name: p for p in pods or ()}.get
    nodes = []
    for n in resp.nodes:
        node = SimNode(
            instance_type=n.instance_type, provisioner=n.provisioner, zone=n.zone,
            capacity_type=n.capacity_type, price=n.price, allocatable={},
            name=n.name,
        )
        node.pods = [seat(pn) or PodSpec(name=pn) for pn in n.pod_names]
        nodes.append(node)
    return SolveResult(
        nodes=nodes,
        assignments=dict(resp.assignments),
        infeasible=dict(resp.infeasible),
        solve_ms=resp.solve_ms,
    )
