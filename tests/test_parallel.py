"""Device-mesh sharding: the multi-chip solve path exercised every test run.

Runs over the 8-device virtual CPU mesh from conftest (XLA's forced
host-platform device count) — the same GSPMD-partitioned programs a real
(pods x types) TPU mesh runs (SURVEY.md §2.3 "device mesh + sharding layout").
"""

import jax
import pytest

from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.tensorize import tensorize
from karpenter_tpu.parallel.distributed import multiprocess_cpu_support
from karpenter_tpu.parallel.mesh import POD_AXIS, TYPE_AXIS, make_mesh
from karpenter_tpu.solver.tpu import TpuSolver

# precise capability probe (NOT a blanket skip): the 2-real-process phases
# need jaxlib's gloo CPU collectives backend; hosts whose jaxlib lacks the
# config can't run multi-process CPU programs at all
_MP_UNSUPPORTED = multiprocess_cpu_support()


def _pods(n):
    return [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key=f"d{i % 3}")
            for i in range(n)]


def _prov():
    return [Provisioner(name="default").with_defaults()]


class TestMesh:
    def test_make_mesh_factorizes(self):
        mesh = make_mesh(8)
        assert mesh.devices.size == 8
        assert mesh.axis_names == (POD_AXIS, TYPE_AXIS)
        assert mesh.devices.shape == (4, 2)

    def test_host_major_multi_host_layout(self):
        """Multi-host: pods axis spans hosts (DCN), types axis stays within
        a host (ICI) — the chatty candidate-axis collectives ride the fast
        fabric."""
        from karpenter_tpu.parallel.mesh import _host_major

        class Dev:
            def __init__(self, pid, i):
                self.process_index = pid
                self.id = i

            def __repr__(self):
                return f"d{self.process_index}.{self.id}"

        devs = [Dev(pid, i) for pid in range(2) for i in range(4)]  # 2 hosts x 4 chips
        arr = _host_major(devs)
        assert arr.shape == (2, 4)  # pods=hosts, types=chips-per-host
        for row in arr:
            assert len({d.process_index for d in row}) == 1  # one host per row

    def test_host_major_single_host_factorizes(self):
        from karpenter_tpu.parallel.mesh import _host_major

        class Dev:
            process_index = 0

        arr = _host_major([Dev() for _ in range(8)])
        assert arr.shape == (4, 2)

    def test_make_mesh_two_devices(self):
        mesh = make_mesh(2)
        assert mesh.devices.size == 2
        assert mesh.devices.shape == (2, 1)


def test_make_mesh_raises_rather_than_substituting_devices():
    """ISSUE 21: asked for more devices than the default platform has,
    make_mesh raises — it never quietly builds the mesh out of another
    platform's (CPU) devices, which would measure the host under a chip's
    name.  (conftest pins the CPU with 8 virtual devices.)"""
    assert len(jax.devices()) == 8
    with pytest.raises(ValueError, match="only 8 device"):
        make_mesh(16)


class TestShardedSolve:
    @pytest.mark.parametrize("n_devices", [2, 8])
    def test_sharded_matches_unsharded(self, small_catalog, n_devices):
        """The sharded solve must produce the identical packing to the
        single-device solve — sharding is a layout choice, not a semantic."""
        pods = _pods(40)
        provs = _prov()
        st = tensorize(pods, provs, small_catalog)
        solo = TpuSolver().solve(st).result
        mesh = make_mesh(n_devices)
        sharded = TpuSolver().solve(st, mesh=mesh).result

        assert sharded.n_scheduled == solo.n_scheduled == 40
        assert sharded.infeasible == {}
        assert abs(sharded.new_node_cost - solo.new_node_cost) < 1e-6
        assert sorted((n.instance_type, n.zone, n.capacity_type) for n in sharded.nodes) \
            == sorted((n.instance_type, n.zone, n.capacity_type) for n in solo.nodes)

    @pytest.mark.skipif(_MP_UNSUPPORTED is not None,
                        reason=_MP_UNSUPPORTED or "")
    def test_dryrun_entrypoint(self):
        """The driver's exact multi-chip validation path (in-process 8-device
        mesh + the 2-process phase)."""
        import __graft_entry__ as g

        g.dryrun_multichip(8)


class TestMultiProcess:
    @pytest.mark.skipif(_MP_UNSUPPORTED is not None,
                        reason=_MP_UNSUPPORTED or "")
    def test_two_process_sharded_solve(self):
        """2 REAL processes x 2 virtual devices via jax.distributed: the
        GSPMD-sharded solve executes across processes (Gloo collectives over
        the coordination service — the DCN stand-in) and the host-major
        layout is asserted against real process_indexes inside each worker
        (parallel/distributed.py assert_host_major), not mock Dev objects."""
        from karpenter_tpu.parallel.distributed import launch_dryrun

        outs = launch_dryrun(2, 2)
        assert len(outs) == 2
        for o in outs:
            assert "OK" in o and "2 processes x 2 devices" in o


class TestBenchScaleSharded:
    @pytest.mark.skipif("not __import__('os').environ.get('KT_SLOW_MESH')",
                        reason="bench-scale mesh compile is minutes on CPU; "
                               "opt in with KT_SLOW_MESH=1 (the driver's "
                               "dryrun_multichip runs this shape every round)")
    def test_bench_scale_sharded_matches_unsharded(self):
        """10k pods / full catalog over the 8-device mesh: identical
        cost/nodes to the single-device solve at real rung sizes (NR=2048,
        C>=512) — the padding/uneven-axis paths the 50k solve rides."""
        import __graft_entry__ as g
        from karpenter_tpu.solver.tpu import solve_dims

        st = g._bench_scenario()
        dims = solve_dims(st, NE=0, node_budget=2048, a=4, b=2)
        assert dims["NR"] >= 2048 and dims["C"] >= 512, dims

        solo = TpuSolver().solve(st, max_nodes=2048,
                                 track_assignments=False).result
        mesh = make_mesh(8)
        sharded = TpuSolver().solve(st, max_nodes=2048, mesh=mesh,
                                    track_assignments=False).result
        assert sharded.infeasible == {} and solo.infeasible == {}
        assert abs(sharded.new_node_cost - solo.new_node_cost) < 1e-4
        assert len(sharded.nodes) == len(solo.nodes)
        assert sorted((n.instance_type, n.zone, n.capacity_type)
                      for n in sharded.nodes) \
            == sorted((n.instance_type, n.zone, n.capacity_type)
                      for n in solo.nodes)
