"""The catalog crosses the wire once (ISSUE 33).

The contract under test: a sessionless ``Solve`` that carries instance types
is answered with the sidecar's own digest of them; ``RemoteScheduler`` then
sends the digest in their place for as long as the caller hands it the SAME
``InstanceType`` objects, and the sidecar solves on the list it kept — the
same objects every time.  A digest the sidecar does not hold costs exactly
one resend and never the degraded path; a sidecar that acknowledges nothing
is never sent a digest; a list on the request wins over a digest beside it;
``DeltaSession``, a forwarded slot and ``Warm`` carry what they carried.
"""

import copy
import pathlib
import subprocess
import sys

import grpc
import pytest
from test_codec_templates import default_prov, deployments

from karpenter_tpu.metrics import (
    FAULTS_RECOVERED,
    REMOTE_DEGRADED,
    REMOTE_FALLBACK_SOLVES,
    REQUEST_CATALOG,
    REQUEST_CATALOG_HOW,
    REQUEST_CATALOG_SENT,
    REQUEST_CATALOG_SENT_HOW,
    Registry,
)
from karpenter_tpu.models import tensorize as tz
from karpenter_tpu.obs.recorder import FlightRecorder
from karpenter_tpu.obs.trace import Tracer
from karpenter_tpu.parallel.forward import ResultForwarder, SlotNotOwned
from karpenter_tpu.service import codec
from karpenter_tpu.service import solver_pb2 as pb
from karpenter_tpu.service.client import DeltaSession, RemoteScheduler
from karpenter_tpu.service.server import (
    CATALOGS_KEPT,
    CatalogUnknown,
    KeptCatalogs,
    SolverService,
    make_server,
)
from karpenter_tpu.solver.scheduler import BatchScheduler

ROOT = pathlib.Path(__file__).resolve().parent.parent


class Tap:
    """Stands where a ``SolverClient`` stands: records every request as it
    left (a copy: ``_solve_rpc`` refills a request it sends again) and lets
    a test rewrite the reply, as a sidecar of another version would."""

    def __init__(self, inner, reply=None) -> None:
        self._inner, self._reply = inner, reply
        self.timeout = inner.timeout
        self.sent: list = []

    def solve_raw(self, req, timeout=None):
        mine = pb.SolveRequest()
        mine.CopyFrom(req)
        self.sent.append(mine)
        resp = self._inner.solve_raw(req, timeout=timeout)
        return resp if self._reply is None else self._reply(mine, resp)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Sidecar:
    """One in-process oracle sidecar and what a test reads off it."""

    def __init__(self) -> None:
        self.reg = Registry()
        self.flight = FlightRecorder(registry=self.reg)
        tracer = Tracer(registry=self.reg, flight=self.flight)
        self.sched = BatchScheduler(backend="oracle", registry=self.reg,
                                    tracer=tracer)
        self.svc = SolverService(self.sched, registry=self.reg)
        self.srv, self.port = make_server(self.svc, port=0)
        #: the instance-type lists the scheduler was handed, in order
        self.solved_on: list = []
        for entry in ("solve", "submit"):  # the direct path, the pipeline
            setattr(self.sched, entry, self._spy(getattr(self.sched, entry)))

    def _spy(self, real):
        def spy(pods, provisioners, instance_types, **kw):
            self.solved_on.append(instance_types)
            return real(pods, provisioners, instance_types, **kw)

        return spy

    def how(self, how: str) -> float:
        return self.reg.counter(REQUEST_CATALOG).get({"how": how})

    def client(self, reply=None):
        """A ``RemoteScheduler`` on its own registry with a :class:`Tap` in
        place of its transport: ``(remote, tap, sent)`` — ``sent(how)``
        reads its ``..._catalog_sent_total``."""
        reg = Registry()
        remote = RemoteScheduler(f"127.0.0.1:{self.port}", backend="oracle",
                                 registry=reg)
        tap = remote.client = Tap(remote.client, reply)
        return remote, tap, lambda how: reg.counter(
            REQUEST_CATALOG_SENT).get({"how": how})

    def restart(self) -> None:
        """What a restarted sidecar remembers of catalogs: nothing."""
        self.svc.catalogs = KeptCatalogs()

    def stop(self) -> None:
        self.srv.stop(grace=None)
        self.svc.close()


@pytest.fixture()
def sidecar():
    s = Sidecar()
    yield s
    s.stop()


def placed(result) -> tuple:
    """A result without its node names (a process-wide counter)."""
    return (sorted((n.instance_type, n.zone, n.capacity_type, round(n.price, 6),
                    tuple(sorted(p.name for p in n.pods)))
                   for n in result.nodes),
            sorted(result.infeasible.items()))


def wire_types(catalog) -> list:
    return [codec.encode_instance_type(t) for t in catalog]


# ---- the round trip -------------------------------------------------------


def test_full_send_then_the_digest_alone_and_the_same_answer(
        sidecar, small_catalog):
    remote, tap, sent = sidecar.client()
    other, _, _ = sidecar.client()  # never acknowledged: always in full
    pods, provs = deployments(4, 25, "rt"), [default_prov()]
    try:
        first = remote.solve(pods, provs, small_catalog)
        second = remote.solve(pods, provs, small_catalog)
        other._catalog_acked = None
        in_full = other.solve(pods, provs, small_catalog)
    finally:
        remote.close()
        other.close()
    one, two = tap.sent
    # the first went in full and named nothing
    assert list(one.instance_types) == wire_types(small_catalog)
    assert one.catalog_digest == ""
    # the sidecar named what it decoded; the client sent the name alone
    digest = codec.catalog_digest(one.instance_types)
    assert remote._catalog_acked[1] == digest
    assert len(two.instance_types) == 0 and two.catalog_digest == digest
    assert len(two.pods) == len(one.pods) == 100
    assert two.ByteSize() < one.ByteSize() - sum(
        t.ByteSize() for t in one.instance_types)
    assert (sent("full"), sent("digest"), sent("resent")) == (1, 1, 0)
    assert (sidecar.how("decoded"), sidecar.how("held"),
            sidecar.how("unknown")) == (2, 1, 0)
    # the answer on the kept list is the answer on the list sent in full
    assert len(first.assignments) == 100 and not first.infeasible
    assert placed(second) == placed(first) == placed(in_full)


def test_what_changes_between_refreshes_still_travels(sidecar, small_catalog):
    """ICE'd offerings are not part of the catalog: a digest-only request
    carries its own ``unavailable`` and the sidecar applies it to the kept
    list."""
    remote, tap, _ = sidecar.client()
    pods, provs = deployments(2, 10, "ice"), [default_prov()]
    try:
        free = remote.solve(pods, provs, small_catalog)
        bought = {(n.instance_type, n.zone, n.capacity_type)
                  for n in free.nodes}
        iced = remote.solve(pods, provs, small_catalog, unavailable=bought)
    finally:
        remote.close()
    assert tap.sent[1].catalog_digest and not tap.sent[1].instance_types
    assert len(tap.sent[1].unavailable) == len(bought)
    assert len(iced.assignments) == 20
    assert not bought & {(n.instance_type, n.zone, n.capacity_type)
                         for n in iced.nodes}


# ---- element identity, not list identity ----------------------------------


def _same_objects(cat):
    return list(cat)


def _one_replaced(cat):
    out = list(cat)
    out[3] = copy.copy(out[3])  # equal in every field, another object
    return out


def _shorter(cat):
    return list(cat)[:-1]


def _reordered(cat):
    out = list(cat)
    out[0], out[1] = out[1], out[0]
    return out


@pytest.mark.parametrize("again,how", [
    (_same_objects, "digest"),
    (tuple, "digest"),
    (_one_replaced, "full"),
    (_shorter, "full"),
    (_reordered, "full"),
], ids=["a_new_list_of_the_same_objects", "a_tuple_of_them",
        "one_object_replaced", "one_fewer", "two_swapped"])
def test_the_client_names_a_list_only_by_its_objects(
        sidecar, small_catalog, again, how):
    remote, tap, sent = sidecar.client()
    pods, provs = deployments(2, 5, "id"), [default_prov()]
    second = again(small_catalog)
    try:
        remote.solve(pods, provs, small_catalog)
        remote.solve(pods, provs, second)
        # whatever went in full replaced the entry: it goes by name now
        remote.solve(pods, provs, list(second))
    finally:
        remote.close()
    assert (sent("full"), sent("digest")) == (
        (1, 2) if how == "digest" else (2, 1))
    assert bool(tap.sent[1].catalog_digest) == (how == "digest")
    if how == "full":
        assert list(tap.sent[1].instance_types) == wire_types(second)
        assert tap.sent[1].catalog_digest == ""
        assert not tap.sent[2].instance_types
        assert tap.sent[2].catalog_digest == codec.catalog_digest(
            tap.sent[1].instance_types)
    assert sent("resent") == 0 and sidecar.how("unknown") == 0


# ---- a miss is typed and costs one resend ---------------------------------


def _degraded(remote) -> tuple:
    return (remote.degraded(),
            remote.registry.gauge(REMOTE_DEGRADED).get(),
            remote.registry.counter(REMOTE_FALLBACK_SOLVES).get())


def test_a_restarted_sidecar_costs_exactly_one_resend(sidecar, small_catalog):
    remote, tap, sent = sidecar.client()
    pods, provs = deployments(3, 10, "rs"), [default_prov()]
    try:
        before = remote.solve(pods, provs, small_catalog)
        sidecar.restart()
        after = remote.solve(pods, provs, small_catalog)
        then = remote.solve(pods, provs, small_catalog)
    finally:
        remote.close()
    # full | digest (refused), the list again | digest
    assert [(bool(r.catalog_digest), len(r.instance_types) > 0)
            for r in tap.sent] == [(False, True), (True, False),
                                   (False, True), (True, False)]
    assert (sent("full"), sent("digest"), sent("resent")) == (1, 2, 1)
    assert (sidecar.how("unknown"), sidecar.how("held"),
            sidecar.how("decoded")) == (1, 1, 2)
    # the resent request is the refused one with its list put back
    refused, resent = tap.sent[1], tap.sent[2]
    assert resent.pods == refused.pods and resent.trace_id == refused.trace_id
    # never the degraded path, never the local solve; one recovery counted
    assert _degraded(remote) == (False, 0, 0)
    assert remote.registry.counter(FAULTS_RECOVERED).get(
        {"site": "transport", "outcome": "retried"}) == 1
    assert placed(after) == placed(before) == placed(then)


def test_an_evicted_entry_costs_one_resend_and_the_lru_keeps_its_bound(
        sidecar, small_catalog):
    remote, _, sent = sidecar.client()
    crowd, _, crowd_sent = sidecar.client()
    pods, provs = deployments(1, 4, "ev"), [default_prov()]
    cat = list(small_catalog)
    try:
        remote.solve(pods, provs, cat)
        mine = remote._catalog_acked[1]
        for k in range(CATALOGS_KEPT):
            crowd.solve(pods, provs, cat[:len(cat) - 1 - k])
            assert len(sidecar.svc.catalogs) == min(k + 2, CATALOGS_KEPT)
        assert sidecar.svc.catalogs.get(mine) is None
        got = remote.solve(pods, provs, cat)
    finally:
        remote.close()
        crowd.close()
    assert crowd_sent("full") == CATALOGS_KEPT
    assert (sent("full"), sent("digest"), sent("resent")) == (1, 1, 1)
    assert sidecar.how("unknown") == 1 and len(got.assignments) == 4
    assert len(sidecar.svc.catalogs) == CATALOGS_KEPT
    assert sidecar.svc.catalogs.get(mine) is not None
    assert _degraded(remote) == (False, 0, 0)


def test_the_lru_evicts_the_list_used_longest_ago():
    kept = KeptCatalogs()
    for k in range(CATALOGS_KEPT):
        kept.keep(f"d{k}", [k])
    assert kept.get("d0") == (0,)      # used: now the newest
    kept.keep("new", ["n"])
    assert kept.get("d1") is None and kept.get("d0") == (0,)
    assert len(kept) == CATALOGS_KEPT
    # a digest already held keeps the objects it was first given
    first = kept.get("d2")
    kept.keep("d2", [2])
    assert kept.get("d2") is first


def test_a_second_unknown_is_an_error_like_any_other(sidecar, small_catalog):
    """Only a request that NAMED its catalog is sent again; the failure of
    the resend reaches the caller's handling as any RPC error does (here:
    served locally, not latched)."""
    def refuse(req, resp):
        err = grpc.RpcError()
        err.code = lambda: grpc.StatusCode.FAILED_PRECONDITION
        err.details = lambda: "CATALOG_UNKNOWN: injected"
        raise err

    remote, tap, sent = sidecar.client()
    pods, provs = deployments(1, 6, "un"), [default_prov()]
    try:
        remote.solve(pods, provs, small_catalog)
        tap._reply = refuse
        got = remote.solve(pods, provs, small_catalog)
    finally:
        remote.close()
    assert len(tap.sent) == 3 and sent("resent") == 1
    assert remote._catalog_acked is None
    assert len(got.assignments) == 6               # the local fallback's
    assert remote.degraded() is False
    assert remote.registry.counter(REMOTE_FALLBACK_SOLVES).get() == 1


def test_a_direct_caller_gets_the_typed_error(sidecar, small_catalog):
    req = codec.encode_request(deployments(1, 2, "dc"), [default_prov()],
                               small_catalog, catalog_digest="feedbeef")
    assert not req.instance_types and req.catalog_digest == "feedbeef"
    with pytest.raises(CatalogUnknown, match="^CATALOG_UNKNOWN"):
        sidecar.svc.Solve(req, None)
    assert sidecar.how("unknown") == 1 and not sidecar.solved_on


def test_over_grpc_the_miss_is_failed_precondition(sidecar, small_catalog):
    remote, _, _ = sidecar.client()
    req = codec.encode_request(deployments(1, 2, "fp"), [default_prov()],
                               small_catalog, catalog_digest="feedbeef")
    try:
        with pytest.raises(grpc.RpcError) as err:
            remote.client.solve_raw(req)
    finally:
        remote.close()
    assert err.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    assert err.value.details().startswith("CATALOG_UNKNOWN")


# ---- mixed versions -------------------------------------------------------


def test_a_sidecar_that_acknowledges_nothing_is_never_sent_a_digest(
        sidecar, small_catalog):
    def old_sidecar(req, resp):
        resp.catalog_digest = ""   # field 11 does not exist there
        return resp

    remote, tap, sent = sidecar.client(old_sidecar)
    pods, provs = deployments(2, 5, "old"), [default_prov()]
    try:
        for _ in range(3):
            remote.solve(pods, provs, small_catalog)
    finally:
        remote.close()
    assert all(r.instance_types and not r.catalog_digest for r in tap.sent)
    assert (sent("full"), sent("digest"), sent("resent")) == (3, 0, 0)
    assert remote._catalog_acked is None


def test_a_sidecar_rolled_back_under_the_client_gets_the_list_again(
        sidecar, small_catalog):
    """An old sidecar skips field 22 and takes a digest-only request for
    one with no instance types: its reply names no catalog, and the client
    sends the list instead of handing that answer on."""
    rolled_back = []

    def maybe_old(req, resp):
        if rolled_back:
            resp.catalog_digest = ""
        return resp

    remote, tap, sent = sidecar.client(maybe_old)
    pods, provs = deployments(2, 5, "rb"), [default_prov()]
    try:
        remote.solve(pods, provs, small_catalog)
        rolled_back.append(True)
        got = remote.solve(pods, provs, small_catalog)
        remote.solve(pods, provs, small_catalog)
    finally:
        remote.close()
    assert [bool(r.instance_types) for r in tap.sent] == [
        True, False, True, True]
    assert (sent("full"), sent("digest"), sent("resent")) == (2, 1, 1)
    assert len(got.assignments) == 10


def test_a_client_that_sets_no_digest_is_decoded_in_full(
        sidecar, small_catalog):
    """The old client's request: field 22 absent.  Byte for byte what
    ``encode_request`` wrote before it had the argument."""
    req = codec.encode_request(deployments(2, 5, "oc"), [default_prov()],
                               small_catalog)
    assert "catalog_digest" not in {f.name for f, _ in req.ListFields()}
    for _ in range(2):
        resp = sidecar.svc.Solve(pb.SolveRequest.FromString(
            req.SerializeToString()), None)
        assert len(resp.assignments) == 10
    assert (sidecar.how("decoded"), sidecar.how("held")) == (2, 0)
    assert resp.catalog_digest == codec.catalog_digest(req.instance_types)
    assert len(sidecar.svc.catalogs) == 1


def test_a_list_on_the_request_wins_over_a_digest_beside_it(
        sidecar, small_catalog):
    cat = list(small_catalog)
    pods, provs = deployments(1, 3, "lw"), [default_prov()]
    first = sidecar.svc.Solve(codec.encode_request(pods, provs, cat), None)
    both = codec.encode_request(pods, provs, cat[:10])
    both.catalog_digest = first.catalog_digest
    resp = sidecar.svc.Solve(both, None)
    assert [t.name for t in sidecar.solved_on[-1]] == [
        t.name for t in cat[:10]]
    assert resp.catalog_digest == codec.catalog_digest(both.instance_types)
    assert resp.catalog_digest != first.catalog_digest
    assert (sidecar.how("decoded"), sidecar.how("held")) == (2, 0)


def test_neither_a_list_nor_a_digest_behaves_as_before(sidecar):
    resp = sidecar.svc.Solve(codec.encode_request(
        deployments(1, 3, "no"), [default_prov()], []), None)
    assert len(resp.infeasible) == 3 and resp.catalog_digest == ""
    assert sidecar.how("decoded") == 1 and len(sidecar.svc.catalogs) == 0


# ---- the sidecar solves on what it kept -----------------------------------


def test_the_kept_objects_are_the_same_objects_every_time(
        sidecar, small_catalog):
    remote, _, _ = sidecar.client()
    provs = [default_prov()]
    try:
        for k in range(3):
            remote.solve(deployments(2, 5, f"ko{k}"), provs, small_catalog)
    finally:
        remote.close()
    decoded, held_a, held_b = sidecar.solved_on
    assert len(decoded) == len(small_catalog)
    assert held_a is not held_b and held_a is not decoded  # a list a request
    assert all(a is b and b is c
               for a, b, c in zip(decoded, held_a, held_b))
    # so the identity memo of the structural signature hits: signing the
    # kept list a second time computes no signature anew
    sig = tz.context_signature(provs, held_a, ())
    memo = dict(tz._IT_SIG_MEMO)
    assert all(memo[id(t)][0] is t for t in held_b)
    assert tz.context_signature(provs, held_b, ()) == sig
    assert tz._IT_SIG_MEMO == memo


def test_the_spans_and_the_counters_say_how_the_catalog_went(
        sidecar, small_catalog):
    for reg, family, hows in (
            (sidecar.reg, REQUEST_CATALOG, REQUEST_CATALOG_HOW),
            (sidecar.client()[0].registry, REQUEST_CATALOG_SENT,
             REQUEST_CATALOG_SENT_HOW)):
        c = reg.counter(family)
        assert all(c.has({"how": h}) and c.get({"how": h}) == 0 for h in hows)
    creg = Registry()
    ctracer = Tracer(registry=creg, flight=FlightRecorder(registry=creg))
    remote, _, _ = sidecar.client()
    seen = []
    try:
        for k in range(2):
            with ctracer.start("provision") as trace:
                remote.solve(deployments(1, 4, f"sp{k}"), [default_prov()],
                             small_catalog, trace=trace)
            seen.append(next(sp.attrs["catalog"] for sp in trace.spans()
                             if sp.name == "encode"))
    finally:
        remote.close()
    assert seen == ["full", "digest"]
    doors = [sp.attrs["catalog"] for t in sidecar.flight.traces()
             for sp in t.spans() if sp.name == "request_decode"]
    assert sorted(doors) == ["decoded", "held"]


# ---- who else encodes a request: what they carried, they carry ------------


def test_a_delta_session_carries_its_catalog_as_before(sidecar, small_catalog):
    sess = DeltaSession(f"127.0.0.1:{sidecar.port}", backend="oracle",
                        registry=Registry())
    tap = sess.client = Tap(sess.client)
    replies = []
    tap._reply = lambda req, resp: replies.append(resp) or resp
    pods, provs = deployments(2, 6, "ds"), [default_prov()]
    try:
        sess.solve(pods, provs, small_catalog)
        sess.solve_delta(added=deployments(1, 2, "ds-more"))
    finally:
        sess.close()
    establish, step = tap.sent
    assert list(establish.instance_types) == wire_types(small_catalog)
    assert not step.instance_types            # its own epoch protocol
    for req in tap.sent:
        assert "catalog_digest" not in {f.name for f, _ in req.ListFields()}
    # the sidecar names and keeps nothing for a session
    assert all(r.catalog_digest == "" for r in replies)
    assert len(sidecar.svc.catalogs) == 0 and sidecar.how("held") == 0


def test_a_session_request_is_never_served_from_the_kept_list(
        sidecar, small_catalog):
    """The digest is the sessionless Solve's: on a session request it is
    not looked up (and not refused either)."""
    pods, provs = deployments(1, 3, "sx"), [default_prov()]
    first = sidecar.svc.Solve(
        codec.encode_request(pods, provs, small_catalog), None)
    req = codec.encode_request(pods, provs, small_catalog, session_id="s-1",
                               catalog_digest=first.catalog_digest)
    assert not req.instance_types
    resp = sidecar.svc.Solve(req, None)
    assert len(resp.infeasible) == 3          # no instance types: as sent
    assert sidecar.how("held") == 0 and sidecar.how("unknown") == 0


def test_a_forwarded_slot_goes_in_full(sidecar, small_catalog):
    """``parallel/forward.py`` re-encodes from decoded kwargs — also when
    those came off the kept list — and keeps no digest."""
    fwd = ResultForwarder(peers=[f"127.0.0.1:{sidecar.port}"],
                          registry=Registry())
    sent = []

    class Peer:
        def solve_raw(self, req):
            sent.append(req)
            return sidecar.svc.Solve(req, None)

    fwd._client = lambda endpoint: Peer()
    kwargs = {"pods": deployments(1, 5, "fw"),
              "provisioners": [default_prov()],
              "instance_types": list(small_catalog)}
    for _ in range(2):
        got = fwd.forward(kwargs, SlotNotOwned(1, owner=0))
        assert len(got.assignments) == 5
    assert fwd.enabled() and len(sent) == 2
    for req in sent:
        assert list(req.instance_types) == wire_types(small_catalog)
        assert req.catalog_digest == ""
    assert sent[0].SerializeToString() == codec.encode_request(
        kwargs["pods"], kwargs["provisioners"], kwargs["instance_types"],
    ).SerializeToString()


def test_warm_always_carries_the_catalog(sidecar, small_catalog):
    remote, _, _ = sidecar.client()
    warmed = []
    remote.client.warm_raw = lambda req: (warmed.append(req)
                                          or pb.WarmResponse(started=0))
    try:
        remote.solve(deployments(1, 3, "wm"), [default_prov()], small_catalog)
        assert remote._catalog_acked is not None
        remote.warm_startup([default_prov()], small_catalog)
    finally:
        remote.close()
    assert list(warmed[0].instance_types) == wire_types(small_catalog)


# ---- the wire's schema -----------------------------------------------------


def test_the_two_fields_are_on_the_wire_where_the_proto_says():
    req = pb.SolveRequest.DESCRIPTOR.fields_by_name["catalog_digest"]
    resp = pb.SolveResponse.DESCRIPTOR.fields_by_name["catalog_digest"]
    assert (req.number, resp.number) == (22, 11)
    proto = (ROOT / "karpenter_tpu" / "service" / "solver.proto").read_text()
    assert "string catalog_digest = 22;" in proto
    assert "string catalog_digest = 11;" in proto


def test_gen_proto_check_is_clean():
    done = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "gen_proto.py"), "--check"],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_equal_lists_get_equal_names_and_order_counts(small_catalog):
    a, b = wire_types(small_catalog), wire_types(small_catalog)
    assert codec.catalog_digest(a) == codec.catalog_digest(b)
    assert codec.catalog_digest(a[::-1]) != codec.catalog_digest(a)
    assert codec.catalog_digest(a[:-1]) != codec.catalog_digest(a)
    assert len(codec.catalog_digest(a)) == 32
