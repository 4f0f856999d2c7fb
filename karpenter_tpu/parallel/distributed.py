"""Multi-process execution: ``jax.distributed`` over the host-major mesh.

The reference scales out with a single leader process and goroutines
(SURVEY.md §2.3); the TPU-native scale-out story is SPMD across processes —
each host runs this worker, ``jax.distributed.initialize`` wires the
coordination service (the DCN control plane), and the (pods, types) mesh of
``parallel/mesh.py`` spans every process's devices: the pods axis crosses
hosts (DCN) while the types axis stays on each host's own chips (ICI).

Two entry points:

- ``worker_main`` — one distributed process: initialize, build the global
  mesh, assert the host-major layout against REAL process indexes, run the
  fully-sharded solve, and cross-check the result on every process.
- ``launch_dryrun`` — spawn N worker processes on this machine over virtual
  CPU devices (the way multi-host is validated without N real hosts) and
  collect their verdicts.  ``__graft_entry__.dryrun_multichip`` and
  ``tests/test_parallel.py`` both ride this.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Optional


def multiprocess_cpu_support() -> Optional[str]:
    """None when multi-process execution over virtual CPU devices is
    available in this jaxlib, else the precise missing capability (tests use
    it as a skip reason — a capability probe, not a blanket skip).

    CPU multi-process programs need an explicit cross-process collectives
    backend: without one, the first sharded computation raises
    INVALID_ARGUMENT "Multiprocess computations aren't implemented on the
    CPU backend".  jaxlib exposes that backend through the
    ``jax_cpu_collectives_implementation`` config (gloo); a build without
    the option cannot run the 2-process dryrun at all."""
    import jax

    if "jax_cpu_collectives_implementation" not in jax.config.values:
        return ("this jaxlib has no jax_cpu_collectives_implementation "
                "config (no gloo CPU collectives): multi-process CPU "
                "computations are unimplemented")
    return None


def _enable_cpu_collectives() -> None:
    """Select the gloo cross-process collectives backend for CPU workers.
    Must run before ``jax.distributed.initialize``."""
    import jax

    if "jax_cpu_collectives_implementation" in jax.config.values:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def put_sharded(value, sharding):
    """Place a host value under ``sharding``, multi-process safe.

    Single process: plain ``device_put``.  Multi process: every process holds
    the full value (the solve tensors are built deterministically on each
    host), so each contributes its addressable shards via
    ``make_array_from_callback`` — ``device_put`` cannot target
    non-addressable devices."""
    import jax
    import numpy as np

    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def replicate_for_host(mesh, value):
    """Re-place a (possibly non-addressable) global array fully replicated so
    every process can read it with plain numpy."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # ktlint: allow[KT008] dryrun-validation helper, two calls per worker
    # process lifetime: the per-call wrapper is deliberate (out_shardings
    # closes over the worker's mesh), and no serving path reaches it
    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))(value)


def assert_host_major(mesh) -> None:
    """The layout contract of parallel/mesh.py:_host_major on real process
    indexes: with >1 process, every types-axis row lives inside ONE process
    (candidate-axis collectives ride ICI) and the pods axis walks processes
    in order (only the embarrassingly-parallel axis crosses DCN)."""
    import jax

    if jax.process_count() == 1:
        return
    rows = mesh.devices  # (pods, types)
    row_procs = []
    for row in rows:
        procs = {d.process_index for d in row}
        assert len(procs) == 1, (
            f"types axis spans processes {procs}: candidate-axis collectives "
            "would cross DCN"
        )
        row_procs.append(procs.pop())
    assert row_procs == sorted(row_procs), (
        f"pods axis does not walk hosts in order: {row_procs}"
    )
    assert len(set(row_procs)) == jax.process_count(), (
        f"pods axis covers {len(set(row_procs))} of {jax.process_count()} hosts"
    )


def worker_main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=2)
    args = ap.parse_args(argv)

    # the launcher exported XLA_FLAGS/JAX_PLATFORMS=cpu for this process
    import jax

    _enable_cpu_collectives()
    jax.distributed.initialize(
        args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id,
    )
    assert jax.process_count() == args.num_processes
    assert len(jax.local_devices()) == args.local_devices

    from ..parallel.mesh import make_mesh
    from ..solver.tpu import TpuSolver

    n_global = args.num_processes * args.local_devices
    mesh = make_mesh(n_global)
    assert mesh.devices.size == n_global
    assert_host_major(mesh)

    # deterministic scenario: every process builds identical tensors
    import __graft_entry__ as graft

    st = graft._scenario()
    run, init, _ne = TpuSolver().prepare(st, track_assignments=False, mesh=mesh)
    carry, _ys, _steps = run(init)
    infeasible = int(
        __import__("numpy").asarray(replicate_for_host(mesh, carry[-1])).sum()
    )
    n_used = int(__import__("numpy").asarray(replicate_for_host(mesh, carry[7])))
    assert n_used > 0, "distributed sharded solve created no nodes"
    assert infeasible == 0, f"distributed solve left {infeasible} pods unplaced"
    print(
        f"worker {args.process_id}/{args.num_processes} OK: "
        f"{jax.process_count()} processes x {args.local_devices} devices, "
        f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
        f"{n_used} nodes, 0 infeasible",
        flush=True,
    )
    return 0


def launch_dryrun(
    n_processes: int = 2,
    local_devices: int = 2,
    timeout: float = 600.0,
    port: int = 0,
    retries: int = 2,
) -> List[str]:
    """Spawn ``n_processes`` distributed workers on this machine (virtual
    CPU devices) and return their stdout tails; raises on any failure."""
    return launch_workers(
        [sys.executable, "-m", "karpenter_tpu.parallel.distributed"],
        n_processes, local_devices, timeout=timeout, port=port,
        retries=retries)


def launch_workers(
    worker_cmd: List[str],
    n_processes: int = 2,
    local_devices: int = 2,
    *,
    timeout: float = 600.0,
    port: int = 0,
    retries: int = 2,
) -> List[str]:
    """Spawn ``n_processes`` copies of ``worker_cmd`` wired into one
    ``jax.distributed`` job over virtual CPU devices (the way multi-host
    is validated without N real hosts) and return their stdout tails;
    raises on any failure.  Each worker receives the standard coordination
    flags (``--coordinator/--num-processes/--process-id/--local-devices``)
    appended to ``worker_cmd`` — the multihost dryrun
    (scripts/dryrun_multihost.py) and the plain distributed worker both
    ride this one launcher.

    The coordinator port is picked by bind-and-release, which is racy
    (another process can grab it before worker 0 binds), so a launch that
    failed with a bind/connect-shaped error retries with a fresh port up to
    ``retries`` times — but only when the port was auto-picked; explicit
    ports and deterministic worker failures (assertion errors, bad solves)
    surface immediately."""
    last_err: Optional[Exception] = None
    attempts = 1 + (max(0, retries) if port == 0 else 0)
    for _ in range(attempts):
        try:
            return _launch_once(worker_cmd, n_processes, local_devices,
                                timeout, port)
        except RuntimeError as e:
            last_err = e
            msg = str(e).lower()
            if not any(s in msg for s in
                       ("bind", "address already in use", "connect",
                        "unavailable", "deadline", "timed out")):
                raise
    raise last_err


def _launch_once(
    worker_cmd: List[str], n_processes: int, local_devices: int,
    timeout: float, port: int,
) -> List[str]:
    import socket

    if port == 0:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={local_devices}"
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    for pid in range(n_processes):
        procs.append(subprocess.Popen(
            list(worker_cmd) + [
                "--coordinator", coordinator,
                "--num-processes", str(n_processes),
                "--process-id", str(pid),
                "--local-devices", str(local_devices)],
            env=env, cwd=repo_root,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    failures = []
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            failures.append(f"worker {pid} timed out after {timeout}s")
        if p.returncode != 0:
            failures.append(f"worker {pid} rc={p.returncode}: {out.strip()[-500:]}")
        outs.append(out.strip())
    if failures:
        raise RuntimeError("; ".join(failures))
    return outs


if __name__ == "__main__":
    raise SystemExit(worker_main())
