#!/usr/bin/env python
"""Profile the config-2 solve on the chip — where do the milliseconds go?

Produces the breakdown SURVEY §5 (tracing) and §7.3 (Pallas slot) ask for:
host tensorize vs the D2H fence vs pure device compute, the top device
kernels by self time, and the XLA cost analysis (flops / bytes) of the
compiled program.  Results feed PERF.md.

    python scripts/profile_solve.py [--pods 50000] [--trace-dir /tmp/kt-trace]

Kernel extraction: the image has no tensorflow/tensorboard, so the captured
``*.xplane.pb`` is read with a generic protobuf wire-format walker (varint +
length-delimited framing only — no schema compile needed).  XPlane layout
(tensorflow/core/profiler/protobuf/xplane.proto):

    XSpace.planes = 1              XPlane.name = 2
    XPlane.lines = 3               XLine.events = 4 / name = 2
    XEvent.metadata_id = 1         XEvent.duration_ps = 3
    XPlane.event_metadata = 4 (map<int64, XEventMetadata{id=1, name=2}>)
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict


# ---------------------------------------------------------------------------
# generic protobuf wire-format walker
# ---------------------------------------------------------------------------


def _read_varint(buf: bytes, i: int):
    val = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        fnum, wtype = tag >> 3, tag & 7
        if wtype == 0:
            val, i = _read_varint(buf, i)
        elif wtype == 1:
            val, i = buf[i:i + 8], i + 8
        elif wtype == 2:
            ln, i = _read_varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wtype == 5:
            val, i = buf[i:i + 4], i + 4
        else:  # groups (3/4): not used by xplane
            raise ValueError(f"unsupported wire type {wtype}")
        yield fnum, wtype, val


def top_kernels(xplane_path: str, k: int = 10):
    """[(kernel name, total self us, calls)] for the device plane(s)."""
    raw = open(xplane_path, "rb").read()
    totals = defaultdict(float)
    calls = defaultdict(int)
    for fnum, _wt, plane in fields(raw):
        if fnum != 1:  # XSpace.planes
            continue
        name = b""
        meta = {}
        lines = []
        for pf, _pw, pv in fields(plane):
            if pf == 2:
                name = pv
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:  # event_metadata map entry {key=1, value=2}
                key = None
                mname = b""
                for mf, _mw, mv in fields(pv):
                    if mf == 1:
                        key = mv
                    elif mf == 2:
                        for ef, _ew, ev in fields(mv):
                            if ef == 2:
                                mname = ev
                if key is not None:
                    meta[key] = mname.decode(errors="replace")
        if b"TPU" not in name and b"/device" not in name.lower():
            continue
        for line in lines:
            for lf, _lw, lv in fields(line):
                if lf != 4:  # XLine.events
                    continue
                mid = dur = 0
                for ef, ew, ev in fields(lv):
                    if ef == 1 and ew == 0:
                        mid = ev
                    elif ef == 3 and ew == 0:
                        dur = ev
                kname = meta.get(mid, f"metadata:{mid}")
                totals[kname] += dur / 1e6  # ps -> us
                calls[kname] += 1
    ranked = sorted(totals.items(), key=lambda t: -t[1])[:k]
    return [(n, round(us, 1), calls[n]) for n, us in ranked]


# ---------------------------------------------------------------------------
# hierarchical host-stage profile (numpy only — never imports jax)
# ---------------------------------------------------------------------------

#: measured dev-host entry-build rate (ms per group): the per-block entry
#: construction (counts mask + zone-share suffix projection) is group-
#: count-bound numpy work, but building it needs the solver's jax-backed
#: base arrays — this script stays jax-free, so it projects from a rate
#: once read on a CPU dev host (21.7 ms / 400 groups)
_ENTRIES_MS_PER_GROUP = 0.055


def _profile_hier() -> int:
    """Host-stage ladder for the ISSUE-16 decomposition.  Everything here
    is numpy: scenario build, tensorize, constraint-reachability
    partition, LPT block packing, and the scale-model wall projection.
    The entry build and the block wave need jax (they are projected from
    measured rates instead); nothing measures the hierarchical path end to
    end (no cell of BENCHMARK.json reaches it).  Asserts jax was never
    imported."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import DEFAULT_ZONES, generate_catalog
    from karpenter_tpu.models.pod import (LabelSelector, PodSpec,
                                          TopologySpreadConstraint)
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver.hierarchy import (block_budgets,
                                                coupling_components,
                                                partition_blocks,
                                                scale_model)

    GIB = 1024 ** 3
    catalog = generate_catalog(full=False)
    provs = [Provisioner(name="default").with_defaults()]
    out = {"jax_imported": None, "rungs": []}
    for n_target in (100_000, 500_000, 1_000_000):
        # the real deployment shape at this rung (one group per 2500-pod
        # deployment), carried by 25-pod proxies: every host stage below
        # is group-count-bound, so the timings ARE the rung's timings
        nd = max(2, n_target // 2500)
        pods = []
        for d in range(nd):
            sel = LabelSelector.of({"app": f"hp{d}"})
            pods.extend(
                PodSpec(
                    name=f"hp{d}-{i}",
                    labels={"app": f"hp{d}"},
                    requests={"cpu": 0.25 * (1 + d % 8),
                              "memory": (0.5 + (d % 6)) * GIB},
                    topology_spread=[TopologySpreadConstraint(
                        1, L.ZONE, "DoNotSchedule", sel)],
                    owner_key=f"hp{d}",
                )
                for i in range(25)
            )
        t0 = time.perf_counter()
        st = tensorize(pods, provs, catalog)
        tensorize_ms = (time.perf_counter() - t0) * 1000.0
        t1 = time.perf_counter()
        comps = coupling_components(st)
        masks = partition_blocks(st, comps, 32)
        budgets = block_budgets(st, masks)
        partition_ms = (time.perf_counter() - t1) * 1000.0
        # block budgets scale with REAL pod counts, not the 25-pod proxy
        scale = n_target / max(1, len(pods))
        entries_ms = _ENTRIES_MS_PER_GROUP * st.G
        model = scale_model(
            {"n_pods": n_target, "blocks": len(masks), "waves": 1,
             "partition_ms": partition_ms, "entries_ms": entries_ms},
            n_target)
        out["rungs"].append({
            "n_pods": n_target, "groups": st.G,
            "components": len(comps), "blocks": len(masks),
            "max_block_budget": int(round(max(budgets) * scale)),
            "tensorize_ms": round(tensorize_ms, 2),
            "partition_ms": round(partition_ms, 2),
            "entries_ms_est": round(entries_ms, 2),
            "model": model,
        })
    out["jax_imported"] = "jax" in sys.modules
    print(json.dumps(out, indent=2))
    return 1 if out["jax_imported"] else 0


# ---------------------------------------------------------------------------
# the measured solve
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=50_000)
    ap.add_argument("--trace-dir", default="/tmp/kt-trace")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--lint-surface", action="store_true",
                    help="dump the KT014 compile-surface audit as JSON — "
                         "the runtime-constructible signature vocabulary "
                         "(solve_dims keys, megabatch rungs per device "
                         "floor) next to the precompile grid — for human "
                         "diffing when the ladder changes; pure stdlib, "
                         "no jax, exits immediately")
    ap.add_argument("--hier", action="store_true",
                    help="per-stage timings of the hierarchical "
                         "decomposition's HOST stages (tensorize, "
                         "partition, LPT block packing) at the 100k/500k/"
                         "1M-pod group shapes, plus the dev-host scale-"
                         "model's host stages (the device wave is 'not "
                         "measured' off the chip) — numpy only, never "
                         "imports jax")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    if args.lint_surface:
        from karpenter_tpu.analysis.ktlint import collect_package_files
        from karpenter_tpu.analysis.rules.kt014 import surface

        print(json.dumps(surface(collect_package_files()), indent=2))
        return 0

    if args.hier:
        return _profile_hier()

    from karpenter_tpu.models.scenarios import config2_scenario

    import jax
    import jax.numpy as jnp
    import numpy as np

    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver.tpu import TpuSolver

    out = {"backend": jax.default_backend(), "n_devices": len(jax.devices())}

    # 1. the bare fence: a tiny dispatch + 4-byte D2H read, the floor under
    # every fenced timing below
    one = jnp.float32(1.0)
    x = jnp.zeros((), jnp.float32)
    np.asarray(x + one)  # compile the add, warm the path
    fences = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(x + one)
        fences.append((time.perf_counter() - t0) * 1000.0)
    out["d2h_fence_ms"] = {"min": round(min(fences), 3),
                           "median": round(sorted(fences)[len(fences) // 2], 3)}

    # 2. host tensorize: from-scratch, then through the incremental cache
    # (steady state = identity tier: the provisioning loop re-offering the
    # same pending set; shape tier = fresh pod objects, same shapes)
    from karpenter_tpu.models.tensorize import TensorizeCache

    pods, provs, catalog = config2_scenario()
    if args.pods != 50_000:
        pods = pods[:args.pods]
    t0 = time.perf_counter()
    st = tensorize(pods, provs, catalog)
    out["tensorize_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
    cache = TensorizeCache()
    t0 = time.perf_counter()
    cache.tensorize(pods, provs, catalog)
    out["tensorize_cold_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
    t0 = time.perf_counter()
    _st2, tier = cache.tensorize(pods, provs, catalog)
    out["tensorize_steady_ms"] = round((time.perf_counter() - t0) * 1000.0, 2)
    out["tensorize_steady_tier"] = tier
    pods_fresh = config2_scenario()[0]
    if args.pods != 50_000:
        pods_fresh = pods_fresh[:args.pods]
    t0 = time.perf_counter()
    _st3, tier3 = cache.tensorize(pods_fresh, provs, catalog)
    out["tensorize_shape_hit_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
    out["tensorize_shape_tier"] = tier3

    # 3. compile + fenced steady-state timings
    solver = TpuSolver()
    run, init, _ne = solver.prepare(st, track_assignments=False)
    t0 = time.perf_counter()
    carry, _ys, _steps = run(init)
    np.asarray(carry[7])
    out["first_call_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
    times = []
    for r in range(args.repeats):
        t0 = time.perf_counter()
        c2, _, _ = run(init)
        np.asarray(c2[7])
        times.append((time.perf_counter() - t0) * 1000.0)
    out["solve_ms"] = {"min": round(min(times), 1),
                       "median": round(sorted(times)[len(times) // 2], 1),
                       "all": [round(t, 1) for t in times]}

    # 4. XLA cost analysis of the compiled program
    try:
        lowered = jax.jit(lambda i: run(i)).lower(init)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        out["cost_analysis"] = {
            "gflops": round(float(cost.get("flops", 0.0)) / 1e9, 3),
            "gbytes_accessed": round(
                float(cost.get("bytes accessed", 0.0)) / 1e9, 3),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        }
    except Exception as err:  # cost analysis is best-effort per backend
        out["cost_analysis"] = {"error": str(err)[:200]}

    # 5. profiler trace of one solve
    os.makedirs(args.trace_dir, exist_ok=True)
    with jax.profiler.trace(args.trace_dir):
        c3, _, _ = run(init)
        np.asarray(c3[7])
    paths = sorted(glob.glob(
        os.path.join(args.trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if paths:
        try:
            out["top_kernels"] = top_kernels(paths[-1])
            out["trace_file"] = paths[-1]
        except Exception as err:
            out["top_kernels"] = [("parse-error", str(err)[:200], 0)]
    else:
        gz = sorted(glob.glob(os.path.join(args.trace_dir, "**", "*.json.gz"),
                              recursive=True), key=os.path.getmtime)
        out["trace_file"] = gz[-1] if gz else None

    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
