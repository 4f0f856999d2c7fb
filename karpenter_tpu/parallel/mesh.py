"""Device mesh + sharding layout for the solver.

The reference scales with goroutines on one process (SURVEY.md §2.3); the
TPU-native answer is a ``jax.sharding.Mesh`` with named axes and GSPMD
partitioning (SURVEY.md §5 "long-context" slot):

- ``pods``  — shards the pod-group axis (G) of the requirement masks and the
  node-slot axis (NR) of the packing state; the analog of data parallelism.
- ``types`` — shards the candidate axis (C) of the catalog tensors; the
  analog of tensor/model parallelism.

Feasibility (``F[G, C]``) is computed fully sharded on both axes — this is
the O(G*C*K) hot tensor contraction.  The packing scan's per-step vector math
shards over node slots; XLA inserts the prefix-sum collectives.  Consolidation
what-if evaluation (solver/consolidation.py) shards candidate subsets over
``pods`` x ``types`` jointly — embarrassingly parallel batched solves.

- ``slots`` — the megabatch request-slot axis (:func:`slot_mesh`): a 1-D
  re-view of the SAME devices, one independent solve request per chip.  The
  cross-request megabatch (solver/tpu.py ``_run_scan_many``) shards its
  leading slot axis here — per-slot feasibility+scan stay fully local, so
  the whole mesh serves one coalesced flush with zero collectives.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POD_AXIS = "pods"
TYPE_AXIS = "types"
#: the megabatch request-slot axis: a 1-D re-view of the SAME devices the
#: (pods, types) mesh spans — see :func:`slot_mesh`
SLOT_AXIS = "slots"


def _host_major(devices: Sequence) -> np.ndarray:
    """Arrange devices as a (pods, types) array with ICI/DCN awareness.

    Multi-host (devices spanning >1 process): the pods axis runs ACROSS
    hosts and the types axis WITHIN a host, so the hot candidate-axis
    collectives (the O(G*C*K) feasibility contraction's gathers/reductions
    along C) ride ICI between a host's own chips, and only the
    embarrassingly-parallel pod-group axis crosses DCN — the scaling-book
    recipe of keeping the chatty axis on the fast fabric.

    Single host: largest factor pair (a, b), a >= b, so both axes shard.
    """
    by_proc: dict = {}
    for d in devices:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    n = len(devices)
    n_proc = len(by_proc)
    if n_proc > 1 and n % n_proc == 0:
        per_host = n // n_proc
        rows = [by_proc[p][:per_host] for p in sorted(by_proc)]
        if all(len(r) == per_host for r in rows):
            return np.array(rows)  # (hosts=pods over DCN, chips=types on ICI)
    b = int(np.floor(np.sqrt(n)))
    while n % b:
        b -= 1
    return np.array(list(devices)).reshape(n // b, b)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Build a (pods, types) mesh over the available devices.

    Prefers a 2D factorization (e.g. 8 -> 4x2) so both the group axis and the
    candidate axis shard; degenerates gracefully to 1D.  On multi-host
    topologies the pods axis maps to hosts (DCN) and the types axis to each
    host's chips (ICI) — see ``_host_major``.
    """
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            # never substitute another platform's devices: a mesh that was
            # asked for on the chip and built on the host would measure the
            # host.  Dryruns and tests pin JAX_PLATFORMS=cpu with
            # --xla_force_host_platform_device_count and ask for those.
            raise ValueError(
                f"make_mesh({n_devices}): the default platform "
                f"{devices[0].platform!r} has only {len(devices)} device(s)")
        devices = devices[:n_devices]
    return Mesh(_host_major(devices), (POD_AXIS, TYPE_AXIS))


def feasibility_shardings(mesh: Mesh):
    """(in_shardings, out_shardings) for solver.tpu.compute_feasibility."""
    s = lambda *names: NamedSharding(mesh, P(*names))
    ins = dict(
        pm=s(POD_AXIS),            # [G, K, W]
        requests=s(POD_AXIS),      # [G, R]
        gp_ok=s(POD_AXIS),         # [G, P]
        cand_vw=s(TYPE_AXIS),      # [C, K]
        cand_vb=s(TYPE_AXIS),
        cand_alloc=s(TYPE_AXIS),
        cand_prov=s(TYPE_AXIS),
        key_check=s(),
        dom_vw=s(),
        dom_vb=s(),
    )
    outs = (s(POD_AXIS, TYPE_AXIS), s(POD_AXIS))  # F[G,C], dom_ok[G,D]
    return ins, outs


def replicate(mesh: Mesh, tree):
    """Place a pytree fully replicated on the mesh."""
    return jax.device_put(tree, axis_sharding(mesh))


# ---------------------------------------------------------------------------
# cached sharding construction (the KT011 discipline)
# ---------------------------------------------------------------------------
# Sharding objects (Mesh, NamedSharding) belong at program-build time: a
# NamedSharding constructed inside a per-flush serving function is rebuilt —
# and re-hashed into every device_put and jit-cache lookup — on every solve
# (the KT008 precedent, applied to layout objects).  These factories are the
# sanctioned construction sites; ``jax.sharding.Mesh`` is hashable, so the
# caches key on the mesh object itself and hit for the process-lifetime mesh
# every serving path holds.


@lru_cache(maxsize=64)
def slot_mesh(mesh: Mesh) -> Mesh:
    """1-D ``('slots',)`` re-view of a ``(pods, types)`` mesh's devices.

    The megabatch request-slot axis is data-parallel by construction (vmap
    introduces no cross-slot ops), so the highest-throughput layout puts one
    slot's whole program on one chip: flatten the 2-D mesh and shard the
    slot axis over ALL devices.  The flatten is row-major over the
    host-major ``(pods, types)`` array — on multi-host topologies the pods
    axis walks hosts in order (:func:`_host_major`), so each host's slots
    stay CONTIGUOUS: a slot never splits across DCN, and a multi-process
    flush places whole slots on one host's chips."""
    return Mesh(mesh.devices.reshape(-1), (SLOT_AXIS,))


@lru_cache(maxsize=64)
def slot_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (dim 0 = request slot) sharding over :func:`slot_mesh`;
    trailing axes replicated — i.e. fully local per slot."""
    return NamedSharding(slot_mesh(mesh), P(SLOT_AXIS))


@lru_cache(maxsize=256)
def axis_sharding(mesh: Mesh, *names: str) -> NamedSharding:
    """Cached ``NamedSharding(mesh, P(*names))`` — no names = replicated."""
    return NamedSharding(mesh, P(*names))


# ---------------------------------------------------------------------------
# host-ownership map (ISSUE 14: per-host fences over addressable shards)
# ---------------------------------------------------------------------------
# The slot axis shards over :func:`slot_mesh`'s flattened, HOST-MAJOR device
# order, so a padded B_pad-slot megabatch splits into n_dev contiguous
# blocks of B_pad/n_dev slots and every host's slots form ONE contiguous
# range.  These pure-host helpers derive who owns what, so each serving
# process can fence and demux exactly its own slots
# (solver/tpu.py PendingMegaSolve.results) instead of paying DCN latency to
# read the whole batch back.


def _owner_blocks(proc_of_dev: Sequence[int], n_slots: int) -> tuple:
    """Owner process index per slot, given the flattened (host-major)
    per-device process indexes.  ``n_slots`` must divide evenly over the
    devices (the sharded rung ladder guarantees it — ``_mega_rung`` floors
    at the device count and doubles)."""
    n_dev = len(proc_of_dev)
    if n_slots % n_dev:
        raise ValueError(
            f"{n_slots} slots do not divide over {n_dev} devices: the "
            "sharded rung ladder should have padded to a multiple")
    per_dev = n_slots // n_dev
    return tuple(proc_of_dev[s // per_dev] for s in range(n_slots))


def multihost(mesh: Optional[Mesh]) -> bool:
    """True when ``mesh`` spans more than one process — the regime where a
    whole-batch fence pays DCN for slots this host does not own."""
    if mesh is None:
        return False
    procs = {getattr(d, "process_index", 0)
             for d in mesh.devices.reshape(-1)}
    return len(procs) > 1


def slot_hosts(mesh: Mesh, n_slots: int) -> tuple:
    """Owner process index for each of ``n_slots`` padded request slots of
    a megabatch sharded over :func:`slot_mesh` — host-major contiguous by
    construction (each host's slots are one contiguous block)."""
    flat = mesh.devices.reshape(-1)
    return _owner_blocks(
        [getattr(d, "process_index", 0) for d in flat], n_slots)


def local_slot_range(
    mesh: Mesh, n_slots: int, process_index: Optional[int] = None,
) -> Tuple[int, int]:
    """The contiguous ``[start, stop)`` slot range this process owns in a
    ``n_slots``-padded megabatch (empty range when the process holds no
    device of the mesh).  Defaults to ``jax.process_index()``."""
    if process_index is None:
        process_index = jax.process_index()
    owners = slot_hosts(mesh, n_slots)
    mine = [s for s, p in enumerate(owners) if p == process_index]
    if not mine:
        return (0, 0)
    lo, hi = mine[0], mine[-1] + 1
    # host-major contiguity is a layout INVARIANT (slot_mesh's flatten);
    # a hole would mean the ownership map and the sharding disagree
    assert hi - lo == len(mine), (
        f"process {process_index} owns non-contiguous slots {mine}")
    return (lo, hi)


def mesh_signature(mesh: Optional[Mesh]) -> tuple:
    """Hashable (axis, size) fingerprint of a mesh for compile-bucket keys:
    two schedulers over different meshes run different partitioned programs,
    so their megabatch bucket keys must never collide (``()`` for None)."""
    if mesh is None:
        return ()
    return tuple(
        (str(a), int(s)) for a, s in zip(mesh.axis_names, mesh.devices.shape)
    )
