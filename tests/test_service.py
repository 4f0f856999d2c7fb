"""Solver service: codec round-trips + live gRPC server/client."""

import pytest

from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodAffinityTerm,
    PodSpec,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.requirements import IN, Requirement
from karpenter_tpu.service import codec
from karpenter_tpu.service.client import RemoteScheduler, SolverClient
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver import reference
from karpenter_tpu.solver.scheduler import BatchScheduler


@pytest.fixture(scope="module")
def server():
    service = SolverService(BatchScheduler(backend="oracle"))
    srv, port = make_server(service, port=0)
    yield port
    srv.stop(grace=None)


def rich_pod():
    return PodSpec(
        name="rich", namespace="ns1", labels={"app": "x"},
        requests={"cpu": 1.5, "memory": 2.0 * 2**30},
        node_selector={L.ZONE: "zone-1a"},
        required_affinity_terms=[[Requirement(L.ARCH, IN, ["amd64"])]],
        tolerations=[Toleration(key="team", operator="Equal", value="a", effect="NoSchedule")],
        topology_spread=[TopologySpreadConstraint(
            1, L.ZONE, "DoNotSchedule", LabelSelector.of({"app": "x"}))],
        affinity_terms=[PodAffinityTerm(LabelSelector.of({"app": "x"}), L.HOSTNAME, anti=True)],
        priority=5, deletion_cost=2.5, owner_key="deploy-x",
    )


class TestCodec:
    def test_pod_roundtrip(self):
        p = rich_pod()
        back = codec.decode_pod(codec.encode_pod(p))
        assert back.name == p.name and back.namespace == "ns1"
        assert back.requests == p.requests
        assert back.node_selector == p.node_selector
        assert back.required_affinity_terms[0][0].key == L.ARCH
        assert back.tolerations == p.tolerations
        assert back.topology_spread[0].max_skew == 1
        assert back.topology_spread[0].hard
        assert back.affinity_terms[0].anti
        assert back.priority == 5 and back.deletion_cost == 2.5

    def test_instance_type_roundtrip(self, small_catalog):
        it = small_catalog[0]
        back = codec.decode_instance_type(codec.encode_instance_type(it))
        assert back.name == it.name
        assert back.capacity == it.capacity
        assert len(back.offerings) == len(it.offerings)
        # overhead total must survive (summed form)
        assert back.allocatable == pytest.approx(it.allocatable)

    def test_provisioner_roundtrip(self):
        p = Provisioner(
            name="p", weight=7, consolidation_enabled=True,
            requirements=[Requirement(L.CAPACITY_TYPE, IN, ["spot"])],
            taints=[Taint("k", "NoSchedule", "v")],
            labels={"team": "a"}, limits={"cpu": 100.0},
        )
        back = codec.decode_provisioner(codec.encode_provisioner(p))
        assert back.name == "p" and back.weight == 7 and back.consolidation_enabled
        assert back.taints == p.taints and back.limits == p.limits


class TestGrpc:
    def test_health(self, server):
        client = SolverClient(f"127.0.0.1:{server}")
        h = client.health()
        assert h.ok and h.devices >= 1
        client.close()

    def test_remote_solve_matches_local(self, server, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="d") for i in range(20)]
        prov = Provisioner(name="default").with_defaults()
        local = reference.solve(pods, [prov], small_catalog)

        remote = RemoteScheduler(f"127.0.0.1:{server}")
        result = remote.solve(pods, [prov], small_catalog)
        assert result.infeasible == {}
        assert result.n_scheduled == 20
        assert result.new_node_cost == pytest.approx(local.new_node_cost)
        # nodes carry the real pod objects back
        assert all(isinstance(p, PodSpec) and p.requests for n in result.nodes for p in n.pods)

    def test_concurrent_clients(self, server, small_catalog):
        """The sidecar serves concurrent solves correctly — the production
        concurrency surface (reconciler replicas + consolidation what-ifs
        hitting one solver)."""
        import threading

        prov = Provisioner(name="default").with_defaults()
        out = [None] * 6

        def solve(i):
            pods = [PodSpec(name=f"c{i}-p{j}", requests={"cpu": 0.5 + 0.5 * (i % 3)},
                            owner_key=f"c{i}") for j in range(10)]
            remote = RemoteScheduler(f"127.0.0.1:{server}")
            try:
                out[i] = remote.solve(pods, [prov], small_catalog)
            finally:
                remote.client.close()

        threads = [threading.Thread(target=solve, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, res in enumerate(out):
            assert res is not None and res.infeasible == {}
            assert res.n_scheduled == 10
            # each client's result contains ONLY its own pods (no cross-talk)
            names = {p.name for n in res.nodes for p in n.pods}
            assert names == {f"c{i}-p{j}" for j in range(10)}

    def test_warm_rpc_forwards_cluster_shape(self, small_catalog):
        """Warm ships provisioners/catalog/cluster snapshots to the sidecar
        and returns how many compiles it accepted — the wire analog of
        warm_startup, so the operator's compile-behind works split."""
        calls = {}

        class RecordingScheduler(BatchScheduler):
            def warm_startup(self, provisioners, instance_types,
                             daemonsets=(), existing_nodes=(), profiles=None):
                calls["provisioners"] = [p.name for p in provisioners]
                calls["n_types"] = len(instance_types)
                calls["n_existing"] = len(existing_nodes)
                return 3

        service = SolverService(RecordingScheduler(backend="oracle"))
        srv, port = make_server(service, port=0)
        try:
            from karpenter_tpu.solver.types import SimNode

            remote = RemoteScheduler(f"127.0.0.1:{port}")
            existing = [SimNode(
                instance_type=small_catalog[0].name, provisioner="default",
                zone="zone-1a", capacity_type="on-demand", price=1.0,
                allocatable=dict(small_catalog[0].allocatable), existing=True,
                name="n-0",
            )]
            started = remote.warm_startup(
                [Provisioner(name="default").with_defaults()], small_catalog,
                existing_nodes=existing,
            )
            assert started == 3
            assert calls == {"provisioners": ["default"],
                             "n_types": len(small_catalog), "n_existing": 1}
            remote.close()
        finally:
            srv.stop(grace=None)

    def test_remote_respects_unavailable(self, server, small_catalog):
        pods = [PodSpec(name="p", requests={"cpu": 1.0, "memory": 2**30})]
        prov = Provisioner(name="default").with_defaults()
        base = reference.solve(pods, [prov], small_catalog)
        ice = {(base.nodes[0].instance_type, z, "on-demand")
               for z in ("zone-1a", "zone-1b", "zone-1c")}
        remote = RemoteScheduler(f"127.0.0.1:{server}")
        result = remote.solve(pods, [prov], small_catalog, unavailable=ice)
        assert result.infeasible == {}
        assert result.nodes[0].instance_type != base.nodes[0].instance_type


class TestFacadeContract:
    """RemoteScheduler must stay a drop-in for BatchScheduler: the operator
    swaps one for the other on --solver-address, so any signature drift
    between them is a production crash.  This test IS the contract."""

    SURFACE = ("solve", "warm_startup", "stop_warms")

    def test_signatures_match(self):
        import inspect

        for name in self.SURFACE:
            local = inspect.signature(getattr(BatchScheduler, name))
            remote = inspect.signature(getattr(RemoteScheduler, name))
            assert list(local.parameters) == list(remote.parameters), (
                f"{name}: parameter drift between BatchScheduler and "
                f"RemoteScheduler"
            )
            for p in local.parameters.values():
                q = remote.parameters[p.name]
                assert p.kind == q.kind, f"{name}({p.name}): kind drift"
                assert p.default == q.default, f"{name}({p.name}): default drift"

    def test_shared_attributes(self, server):
        remote = RemoteScheduler(f"127.0.0.1:{server}")
        local = BatchScheduler(backend="oracle")
        # the attributes the operator and controllers actually read
        for attr in ("backend", "mesh", "registry"):
            assert hasattr(remote, attr) and hasattr(local, attr), attr
        remote.close()


class TestFallback:
    def _pods(self, n=8):
        return [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="d")
                for i in range(n)]

    def test_solve_falls_back_when_unreachable(self, small_catalog):
        from karpenter_tpu.metrics import Registry
        from karpenter_tpu.service.client import REMOTE_FALLBACK_SOLVES

        reg = Registry()
        # nothing listens on port 1; keep the probe interval long so the
        # second solve skips straight to the fallback without re-probing
        remote = RemoteScheduler("127.0.0.1:1", timeout=2.0,
                                 reconnect_interval=600.0, registry=reg)
        prov = Provisioner(name="default").with_defaults()
        result = remote.solve(self._pods(), [prov], small_catalog)
        assert result.infeasible == {} and result.n_scheduled == 8
        assert remote.degraded()
        assert reg.counter(REMOTE_FALLBACK_SOLVES).get() == 1
        # degraded warm_startup is a cheap no-op, not an RPC deadline wait
        assert remote.warm_startup([prov], small_catalog) == 0
        remote.solve(self._pods(), [prov], small_catalog)
        assert reg.counter(REMOTE_FALLBACK_SOLVES).get() == 2
        remote.close()

    def test_health_gated_reconnect(self, server, small_catalog):
        remote = RemoteScheduler(f"127.0.0.1:{server}", reconnect_interval=0.0)
        prov = Provisioner(name="default").with_defaults()
        # simulate a past outage: degraded, but the sidecar is healthy now
        remote._mark_degraded(RuntimeError("injected outage"))
        assert remote.degraded()
        result = remote.solve(self._pods(), [prov], small_catalog)
        assert result.infeasible == {} and result.n_scheduled == 8
        assert not remote.degraded()  # probe succeeded -> remote path resumed
        remote.close()

    def test_warm_unimplemented_does_not_degrade(self, small_catalog):
        """Rolling upgrade: a pre-Warm sidecar answers UNIMPLEMENTED to Warm.
        Warmup is best-effort — the Solve path must stay remote."""
        from concurrent import futures

        import grpc

        from karpenter_tpu.service import solver_pb2 as pb
        from karpenter_tpu.service.server import SERVICE

        service = SolverService(BatchScheduler(backend="oracle"))
        handlers = {  # Solve + Health only: no Warm handler registered
            "Solve": grpc.unary_unary_rpc_method_handler(
                service.Solve,
                request_deserializer=pb.SolveRequest.FromString,
                response_serializer=pb.SolveResponse.SerializeToString,
            ),
            "Health": grpc.unary_unary_rpc_method_handler(
                service.Health,
                request_deserializer=pb.HealthRequest.FromString,
                response_serializer=pb.HealthResponse.SerializeToString,
            ),
        }
        srv = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        srv.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE, handlers),))
        port = srv.add_insecure_port("127.0.0.1:0")
        srv.start()
        try:
            remote = RemoteScheduler(f"127.0.0.1:{port}")
            prov = Provisioner(name="default").with_defaults()
            assert remote.warm_startup([prov], small_catalog) == 0
            assert not remote.degraded()  # UNIMPLEMENTED is not an outage
            result = remote.solve(self._pods(), [prov], small_catalog)
            assert result.infeasible == {} and result.n_scheduled == 8
            assert not remote.degraded()
            remote.close()
        finally:
            srv.stop(grace=None)


class TestPipelineWedgedStop:
    def test_stop_fails_request_wedged_inside_submit(self):
        """A dispatcher wedged INSIDE scheduler.submit (an H2D dispatch that
        never returns — before the request reaches the inflight queue) must
        not strand its RPC thread: stop() fails everything in the
        dispatcher's _in_hand ledger, not just the queued/inflight entries
        (review finding on the ISSUE 2 round)."""
        import threading

        from karpenter_tpu.service.server import SolvePipeline

        wedged = threading.Event()

        class WedgingScheduler:
            backend = "oracle"

            def submit(self, *a, **kw):
                wedged.set()
                threading.Event().wait()  # never returns

        pipe = SolvePipeline(WedgingScheduler())
        outcome = {}

        def rpc():
            try:
                outcome["val"] = pipe.solve(
                    dict(pods=[], provisioners=[], instance_types=[]))
            except RuntimeError as e:
                outcome["err"] = str(e)

        t = threading.Thread(target=rpc)
        t.start()
        assert wedged.wait(5)
        pipe.stop()  # join times out (5s), then drains the in-hand ledger
        t.join(5)
        assert not t.is_alive(), "RPC thread stranded on a wedged submit"
        assert "stopped" in outcome.get("err", "")


class TestSidecarStartupRefusals:
    """ISSUE 21: a device-backend sidecar serves from a TPU or not at all,
    and ``--warmup`` fails start-up when a warm-up compile failed — neither
    may end up answering from the host tiers for ever without complaint."""

    def test_device_backend_refuses_a_cpu(self, capsys):
        from karpenter_tpu.service import server

        rc = server.main(["--host", "unix:/nonexistent/never-bound.sock",
                          "--backend", "auto"])
        assert rc == 2
        assert "needs a TPU" in capsys.readouterr().err

    def test_warmup_failure_fails_startup(self, monkeypatch, capsys):
        import types

        import jax

        from karpenter_tpu.service import server
        from karpenter_tpu.solver.scheduler import BatchScheduler, WarmupFailed

        fake = types.SimpleNamespace(platform="tpu", device_kind="fake v0")
        monkeypatch.setattr(jax, "devices", lambda *a: [fake])

        def failed(self, *a, **k):
            raise WarmupFailed("1 of 9 bucket compiles failed: Mosaic says no")

        monkeypatch.setattr(BatchScheduler, "precompile_buckets", failed)
        bound = []
        monkeypatch.setattr(server, "make_server",
                            lambda *a, **k: bound.append(1))
        rc = server.main(["--host", "unix:/nonexistent/never-bound.sock",
                          "--backend", "auto", "--warmup", "--small"])
        out = capsys.readouterr()
        assert rc == 1 and not bound
        assert "warmup FAILED, not serving" in out.err
        assert "Mosaic says no" in out.err
        # the device line was printed before the warm-up started
        assert "platform=tpu, device_kind='fake v0', devices=1" in out.out
        assert "serving" not in out.out.replace("not serving", "")
