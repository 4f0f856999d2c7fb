#!/usr/bin/env python
"""Multi-host megabatch dryrun (ISSUE 14): per-host fences proven on real
processes.

Launches N real ``jax.distributed`` processes on this machine (gloo CPU
collectives over virtual devices — the same harness as
``tests/test_parallel.py``), serves one coalesced megabatch SPMD across
them, and asserts the whole per-host serving contract per process:

- **ownership**: each process's owned slot range matches the host-major
  ownership map (``parallel/mesh.slot_hosts``) and is contiguous;
- **addressable-only fences**: the bytes each process read back are
  EXACTLY 1/N of the whole-batch readback (the per-host fence never
  touches a foreign shard);
- **demux**: foreign slots resolve to typed ``SlotNotOwned`` carrying the
  true owner; owned slots extract locally;
- **byte parity**: every owned slot's result is identical to a
  single-process, single-device serial solve of the same request;
- **flush wall**: the steady sharded flush is timed per process.

Modes:

    python scripts/dryrun_multihost.py                  # launcher (2 x 4)
    python scripts/dryrun_multihost.py --processes 2 --local-devices 4
    python scripts/dryrun_multihost.py --lone-ab        # single-process A/B:
        # per-host fence (KT_MULTIHOST=1) vs whole-batch readback (=0)
        # on a lone 1-slot meshed flush — the latency-tax gate's input

``make multihost-dryrun`` runs the launcher in CI; the A/B's latency tax
is unmeasured on the chip (four chips: nothing run, PERF.md §7).  Machine-readable verdicts: one ``MHOSTW {...}`` JSON line
per worker, one ``MHOST {...}`` summary from the launcher, one
``LONE_AB {...}`` from the A/B mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: slots per flush in the default 2x4 topology: one per chip
DEFAULT_PROCESSES = 2
DEFAULT_LOCAL_DEVICES = 4


def _plan(res):
    """Node-plan fingerprint for byte-parity checks (the
    dryrun_megabatch_sharded idiom: node names are counter-assigned, so
    parity is judged on everything BUT the name)."""
    return sorted(
        (n.instance_type, n.zone, n.capacity_type, round(n.price, 6),
         tuple(sorted(q.name for q in n.pods)))
        for n in res.nodes
    )


def _scenario(n_slots: int):
    import __graft_entry__ as graft
    from karpenter_tpu.models.tensorize import tensorize

    parts = [graft._scenario_parts(48, tenant=f"mh{i}")
             for i in range(n_slots)]
    provs, catalog = parts[0][1], parts[0][2]
    return [tensorize(p, provs, catalog) for p, _pv, _c in parts]


def worker(args) -> int:
    import jax

    from karpenter_tpu.parallel.distributed import (
        _enable_cpu_collectives,
        assert_host_major,
    )

    _enable_cpu_collectives()
    jax.distributed.initialize(
        args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id)
    pid = jax.process_index()
    n_global = args.num_processes * args.local_devices

    from karpenter_tpu.parallel.forward import SlotNotOwned
    from karpenter_tpu.parallel.mesh import (
        local_slot_range,
        make_mesh,
        multihost,
        slot_hosts,
    )
    from karpenter_tpu.solver.tpu import TpuSolver

    mesh = make_mesh(n_global)
    assert mesh.devices.size == n_global
    assert_host_major(mesh)
    assert multihost(mesh), "dryrun mesh must span >1 process"

    n_slots = args.slots or n_global
    sts = _scenario(n_slots)
    solver = TpuSolver()
    reqs = [dict(st=st) for st in sts]

    # cold dispatch compiles the sharded slot-rung program (SPMD: every
    # process runs the identical dispatch)
    handle = solver.solve_many_async(reqs, min_slots=n_slots, mesh=mesh)
    outs = handle.results()

    owners = slot_hosts(mesh, handle.B_pad)
    lo, hi = local_slot_range(mesh, handle.B_pad)
    exp = [s for s, p in enumerate(owners) if p == pid]
    assert (lo, hi) == (exp[0], exp[-1] + 1), (
        f"owned range {(lo, hi)} != host-major ownership map {exp}")
    assert handle.owned_slots == (lo, hi)

    # addressable-only fence: bytes read are EXACTLY the 1/N share
    assert handle.fence_bytes_read * args.num_processes == \
        handle.fence_bytes_total, (
        f"per-host fence read {handle.fence_bytes_read} of "
        f"{handle.fence_bytes_total} bytes — not the 1/"
        f"{args.num_processes} addressable share")

    # demux: foreign slots are typed with the true owner, owned slots
    # extracted locally and byte-identical to single-device serial solves
    n_foreign = 0
    for i, out in enumerate(outs):
        if lo <= i < hi:
            assert not isinstance(out, Exception), (i, out)
            solo = solver.solve(sts[i])
            assert _plan(out.result) == _plan(solo.result), (
                f"slot {i} diverged from the single-process serial solve")
            assert set(out.result.assignments) == \
                set(solo.result.assignments), i
            assert out.result.infeasible == solo.result.infeasible, i
        else:
            assert isinstance(out, SlotNotOwned), (i, out)
            assert out.owner == owners[i], (i, out.owner, owners[i])
            n_foreign += 1

    # steady flush wall (median of 3): dispatch + per-host fence
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        h = solver.solve_many_async(reqs, min_slots=n_slots, mesh=mesh)
        h.results()
        walls.append((time.perf_counter() - t0) * 1000.0)
    flush_ms = sorted(walls)[1]

    print("MHOSTW " + json.dumps(dict(
        pid=pid, ok=True, owned=[lo, hi], slots=int(handle.B_pad),
        foreign=n_foreign, read=int(handle.fence_bytes_read),
        total=int(handle.fence_bytes_total),
        frac=handle.fence_bytes_read / max(1, handle.fence_bytes_total),
        flush_ms=round(flush_ms, 2))), flush=True)
    return 0


def lone_ab(devices: int = 8, pairs: int = 5) -> int:
    """Single-process A/B: lone 1-slot meshed flush with the per-host
    fence (addressable-shard reads) vs the legacy whole-batch readback —
    the machinery must not tax the lone request (gate <= 1.10x)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    from karpenter_tpu.parallel.mesh import make_mesh
    from karpenter_tpu.solver.tpu import TpuSolver

    mesh = make_mesh(devices)
    st = _scenario(1)[0]
    solver = TpuSolver()
    solver.solve_many([dict(st=st)], mesh=mesh)  # compile

    def flush(flag: str) -> float:
        os.environ["KT_MULTIHOST"] = flag
        t0 = time.perf_counter()
        h = solver.solve_many_async([dict(st=st)], mesh=mesh)
        h.results()
        return (time.perf_counter() - t0) * 1000.0

    flush("1"), flush("0")  # warm both readback paths
    on, off = [], []
    for k in range(pairs):
        # paired, alternating within-pair order (the repo's estimator
        # idiom): monotone host drift biases half the pairs each way
        if k % 2 == 0:
            on.append(flush("1"))
            off.append(flush("0"))
        else:
            off.append(flush("0"))
            on.append(flush("1"))
    os.environ.pop("KT_MULTIHOST", None)
    on_ms = sorted(on)[len(on) // 2]
    off_ms = sorted(off)[len(off) // 2]
    print("LONE_AB " + json.dumps(dict(
        on_ms=round(on_ms, 2), off_ms=round(off_ms, 2),
        ratio=round(on_ms / max(off_ms, 1e-9), 3))), flush=True)
    return 0


def run(n_processes: int, local_devices: int, slots=None,
        timeout: float = 900.0) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from karpenter_tpu.parallel.distributed import (
        launch_workers,
        multiprocess_cpu_support,
    )

    reason = multiprocess_cpu_support()
    if reason is not None:
        # capability probe, not a failure: this jaxlib cannot run
        # multi-process CPU programs at all (the test-suite skip reason)
        print("MHOST " + json.dumps(dict(skipped=reason)), flush=True)
        return 0
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    if slots:
        cmd += ["--slots", str(slots)]
    outs = launch_workers(cmd, n_processes, local_devices, timeout=timeout)
    records = []
    for out in outs:
        print(out, flush=True)
        for ln in out.splitlines():
            if ln.startswith("MHOSTW "):
                records.append(json.loads(ln[len("MHOSTW "):]))
    assert len(records) == n_processes, (
        f"{len(records)} worker verdicts for {n_processes} processes")
    assert all(r["ok"] for r in records)
    summary = dict(
        processes=n_processes, local_devices=local_devices,
        slots=records[0]["slots"],
        fence_frac=max(r["frac"] for r in records),
        flush_ms=max(r["flush_ms"] for r in records),
        parity=True,
    )
    print("MHOST " + json.dumps(summary), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--lone-ab", action="store_true")
    ap.add_argument("--processes", type=int, default=DEFAULT_PROCESSES)
    ap.add_argument("--local-devices", type=int,
                    default=DEFAULT_LOCAL_DEVICES)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count for --lone-ab")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=900.0)
    # launcher-appended coordination flags (worker mode)
    ap.add_argument("--coordinator")
    ap.add_argument("--num-processes", type=int)
    ap.add_argument("--process-id", type=int)
    args = ap.parse_args(argv)
    if args.lone_ab:
        return lone_ab(args.devices)
    if args.worker:
        return worker(args)
    return run(args.processes, args.local_devices, args.slots or None,
               args.timeout)


if __name__ == "__main__":
    raise SystemExit(main())
