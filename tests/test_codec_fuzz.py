"""Wire-codec property fuzz: solve-equivalence through the proto boundary.

The gRPC split topology (service/solver.proto) is only as trustworthy as the
codec: any field dropped or coerced in encode/decode silently changes what
the sidecar solves.  These tests round-trip seeded random scenarios through
``encode_request -> SerializeToString -> FromString -> decode_request`` and
assert the ORACLE solves the decoded objects to the same answer as the
originals — the strongest equivalence the wire can claim (SURVEY.md §2.3
protobuf schema slot; hardens the operator's --solver-address path).
"""

import numpy as np
import pytest

from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.service import codec
from karpenter_tpu.service import solver_pb2 as pb
from karpenter_tpu.solver import reference
from tests.test_fuzz_parity import random_existing_nodes, random_scenario


def _roundtrip(req: pb.SolveRequest) -> dict:
    wire = req.SerializeToString()
    return codec.decode_request(pb.SolveRequest.FromString(wire))


def _canonical(res):
    """Packing shape independent of node-name counters."""
    return (
        res.n_scheduled,
        round(res.new_node_cost, 9),
        sorted(res.infeasible),
        sorted((n.instance_type, n.zone, n.capacity_type,
                tuple(sorted(p.name for p in n.pods)))
               for n in res.nodes),
    )


class TestCodecFuzz:
    @pytest.mark.parametrize("seed", range(12))
    def test_solve_equivalence_through_the_wire(self, seed, small_catalog):
        """oracle(original objects) == oracle(decode(encode(objects))) over
        the full constraint surface the fuzz generator produces (spreads,
        anti-affinity, taints, selectors, limits, weights, ICE'd offerings,
        partially-filled existing nodes)."""
        pods, provs, unavailable = random_scenario(seed, small_catalog)
        existing = random_existing_nodes(seed, small_catalog, provs)

        req = codec.encode_request(
            pods, provs, small_catalog,
            existing_nodes=existing, unavailable=unavailable,
        )
        back = _roundtrip(req)

        local = reference.solve(pods, provs, small_catalog,
                                existing_nodes=existing, unavailable=unavailable)
        wired = reference.solve(
            back["pods"], back["provisioners"], back["instance_types"],
            existing_nodes=back["existing_nodes"],
            unavailable=back["unavailable"],
            allow_new_nodes=back["allow_new_nodes"],
            max_new_nodes=back["max_new_nodes"],
        )
        assert _canonical(local) == _canonical(wired), (
            f"seed {seed}: wire round-trip changed the solve"
        )

    def test_unicode_labels_and_zero_resource_pods(self, small_catalog):
        pods = [
            PodSpec(name="zero", requests={}),  # no resources at all
            PodSpec(name="uni-é中文", namespace="tést",
                    labels={"app☃": "snöwman", "plain": "v"},
                    requests={"cpu": 0.5},
                    node_selector={L.ZONE: "zone-1a"}),
        ]
        provs = [Provisioner(name="défault",
                             labels={"tëäm": "ünit"}).with_defaults()]
        back = _roundtrip(codec.encode_request(pods, provs, small_catalog))
        assert back["pods"][0].name == "zero"
        assert back["pods"][0].requests == {}
        assert back["pods"][1].name == "uni-é中文"
        assert back["pods"][1].namespace == "tést"
        assert back["pods"][1].labels["app☃"] == "snöwman"
        assert back["provisioners"][0].name == "défault"
        assert back["provisioners"][0].labels["tëäm"] == "ünit"

    def test_warm_request_roundtrip(self, small_catalog):
        pods, provs, _un = random_scenario(7, small_catalog)
        existing = random_existing_nodes(7, small_catalog, provs)
        req = codec.encode_warm_request(
            provs, small_catalog, daemonsets=pods[:2], existing_nodes=existing,
            backend="tpu",
        )
        wire = req.SerializeToString()
        back = codec.decode_warm_request(pb.WarmRequest.FromString(wire))
        assert [p.name for p in back["provisioners"]] == [p.name for p in provs]
        assert len(back["instance_types"]) == len(small_catalog)
        assert [p.name for p in back["daemonsets"]] == [p.name for p in pods[:2]]
        assert len(back["existing_nodes"]) == len(existing)
        # existing-node free capacity survives (remaining(), not allocatable)
        for orig, got in zip(existing, back["existing_nodes"]):
            assert got.allocatable == pytest.approx(orig.allocatable)
            assert len(got.pods) == len(orig.pods)

    def test_50k_full_catalog_roundtrip(self, full_catalog):
        """The north-star batch size survives one wire round-trip intact."""
        rng = np.random.default_rng(0)
        pods = [
            PodSpec(name=f"p{i}",
                    requests={"cpu": float(rng.choice([0.25, 0.5, 1.0, 2.0])),
                              "memory": float(rng.choice([1, 2, 4])) * 2**30},
                    owner_key=f"d{i % 20}")
            for i in range(50_000)
        ]
        provs = [Provisioner(name="default").with_defaults()]
        req = codec.encode_request(pods, provs, full_catalog)
        wire = req.SerializeToString()
        assert len(wire) < 256 * 1024 * 1024  # inside the channel limits
        back = codec.decode_request(pb.SolveRequest.FromString(wire))
        assert len(back["pods"]) == 50_000
        assert back["pods"][0].requests == pods[0].requests
        assert back["pods"][-1].name == "p49999"
        assert len(back["instance_types"]) == len(full_catalog)

    def test_50k_by_shape_roundtrip(self, full_catalog):
        """The north-star batch crosses as its shapes plus names: each
        distinct pod once, every pod back field for field and in order, as
        ``decode_pod`` over one ``pb.Pod`` a pod gives it."""
        rng = np.random.default_rng(1)
        pods = [
            PodSpec(name=f"p{i}",
                    requests={"cpu": float(rng.choice([0.25, 0.5, 1.0, 2.0])),
                              "memory": float(rng.choice([1, 2, 4])) * 2**30},
                    owner_key=f"d{i % 20}")
            for i in range(50_000)
        ]
        req = codec.encode_request(pods, [], full_catalog)
        back = pb.SolveRequest.FromString(req.SerializeToString())
        assert not back.pods and len(back.pod_names) == 50_000
        assert len(back.pod_shapes) == len({
            (p.owner_key, tuple(p.requests.items())) for p in pods})
        plain = pb.SolveRequest()
        plain.pods.extend(codec.encode_pod(p) for p in pods)
        # the pods alone cross in under a quarter of the plain build's bytes
        alone = pb.SolveRequest()
        alone.CopyFrom(back)
        alone.ClearField("instance_types")
        alone.ClearField("provisioners")
        assert 4 * len(alone.SerializeToString()) < len(
            plain.SerializeToString())
        got = codec.decode_request(back)["pods"]
        want = [codec.decode_pod(p) for p in plain.pods]
        assert [p.name for p in got] == [p.name for p in pods]
        assert all(vars(a).keys() == vars(b).keys() for a, b in zip(got, want))
        drop = ("uid",)
        assert [{k: v for k, v in vars(a).items() if k not in drop}
                for a in got] == [
            {k: v for k, v in vars(b).items() if k not in drop}
            for b in want]
        uids = [p.uid for p in got]
        assert uids == list(range(uids[0], uids[0] + 50_000))
