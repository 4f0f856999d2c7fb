#!/usr/bin/env python
"""The million-pod hierarchical walk (`make hier-demo`, ISSUE 16).

Three acts, all on the dev host (JAX_PLATFORMS=cpu):

1. Partition the REAL 1M-pod group shape (400 deployments): constraint-
   reachability components -> LPT-packed megabatch blocks.
2. A real hierarchical solve on a CPU-sized overlapping batch — one
   vmapped block wave, the dual price loop (a provisioner limit is set
   tight enough to contend across blocks), warm-start repair and the
   cross-block tail repack — printing the stats
   ``tests/test_hierarchy.py`` asserts on.
3. The scale model seeded with the measured HOST stats.  The device wave
   is "not measured" here by construction: only a run on the chip
   supplies the per-pod device rate the 1M wall and its 250 ms budget are
   judged by, and no cell of BENCHMARK.json reaches this path yet.

The full 1M batch never dispatches here — a CPU host neither holds the
32-slot carry nor finishes the wave in demo time.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from karpenter_tpu.models import labels as L  # noqa: E402
from karpenter_tpu.models.catalog import generate_catalog  # noqa: E402
from karpenter_tpu.models.pod import (LabelSelector, PodSpec,  # noqa: E402
                                      TopologySpreadConstraint)
from karpenter_tpu.models.provisioner import Provisioner  # noqa: E402
from karpenter_tpu.models.tensorize import tensorize  # noqa: E402
from karpenter_tpu.solver import hierarchy as hier  # noqa: E402
from karpenter_tpu.solver.scheduler import BatchScheduler  # noqa: E402

GIB = 1024 ** 3
HIER_BUDGET_MS = 250.0


def deployments(nd: int, per: int, tag: str = "hd"):
    pods = []
    for d in range(nd):
        sel = LabelSelector.of({"app": f"{tag}{d}"})
        pods.extend(
            PodSpec(
                name=f"{tag}{d}-{i}",
                labels={"app": f"{tag}{d}"},
                requests={"cpu": 0.25 * (1 + d % 8),
                          "memory": (0.5 + (d % 6)) * GIB},
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"{tag}{d}",
            )
            for i in range(per)
        )
    return pods


def main() -> int:
    catalog = generate_catalog(full=False)
    print("== 1M-pod hierarchical walk (dev host) ==")

    # ---- act 1: partition the real 1M group shape (host stages are
    # group-count-bound, so 25-pod proxies carry the true shape) --------
    provs = [Provisioner(name="default").with_defaults()]
    proxy = deployments(400, 25)
    st = tensorize(proxy, provs, catalog)
    t0 = time.perf_counter()
    comps = hier.coupling_components(st)
    masks = hier.partition_blocks(st, comps, 32)
    budgets = hier.block_budgets(st, masks)
    part_ms = (time.perf_counter() - t0) * 1000.0
    scale = 1_000_000 / len(proxy)
    print(f"partition: {st.G} groups -> {len(comps)} components -> "
          f"{len(masks)} blocks (max budget "
          f"{int(round(max(budgets) * scale))}) in {part_ms:.1f} ms")

    # ---- act 2: a real hierarchical solve, CPU-sized ------------------
    pods = deployments(8, 1250, tag="hw")
    sched = BatchScheduler(backend="tpu", compile_behind=False)
    # first run pays the XLA compiles (block wave + repair shapes); the
    # second run's stats are the steady state the model projects from
    hier.solve_hierarchical(sched, pods, provs, catalog, stats={})
    stats_free: dict = {}
    free = hier.solve_hierarchical(sched, pods, provs, catalog,
                                   stats=stats_free)
    if free is None:
        print("hierarchical solve fell back to flat — demo aborted")
        return 1
    # a provisioner limit just under the unconstrained buy makes the
    # blocks contend for shared capacity, so the dual price loop runs
    bought = sum(
        float(sched._tensorize(pods, provs, catalog, (), ())[0]
              .capacity_row(n.instance_type, n.allocatable)[0])
        for n in free.nodes)
    lim = Provisioner(name="default").with_defaults()
    lim.limits = {"cpu": round(bought * 0.99, 1)}
    print(f"unconstrained buy: {len(free.nodes)} nodes, "
          f"{bought:.0f} cpu capacity; limiting cpu to "
          f"{lim.limits['cpu']:.0f} to force cross-block contention")
    stats: dict = {}
    res = hier.solve_hierarchical(sched, pods, [lim], catalog, stats=stats)
    if res is None:
        print("hierarchical solve fell back to flat — demo aborted")
        return 1
    print(f"measured {len(pods)}-pod contended solve: "
          f"{stats['blocks']} blocks, {stats['waves']} wave(s) "
          f"({stats['dispatches']} dispatches, 1 per wave), "
          f"{stats['price_iters']} price iteration(s), "
          f"{stats['repair_pods']} repaired, "
          f"{stats['tail_repack_pods']} tail-repacked, "
          f"{stats['total_ms']:.0f} ms wall "
          f"({len(res.nodes)} nodes, {len(res.infeasible)} infeasible)")

    # ---- act 3: the dev-host 1M projection ----------------------------
    # seeded from the UNCONTENDED measured stats (that scenario carries no
    # binding provisioner limit; the contended run above is the price-
    # loop showcase, and its capacity-shortage repair is not a property
    # of the 1M shape)
    model = hier.scale_model(
        {"n_pods": 1_000_000, "blocks": len(masks),
         "waves": stats_free["waves"], "partition_ms": part_ms,
         "entries_ms": stats_free["entries_ms"]
         * (st.G / max(1, len(masks))),
         "repair_ms": stats_free["repair_ms"]},
        1_000_000)
    print(f"modeled 1M wall: host {model['host_ms']:.1f} ms + "
          f"{model['waves']} wave(s) x {model['wave_ms']} + "
          f"repair {model['repair_ms']:.1f} ms -> {model['total_ms']}  "
          f"[budget {HIER_BUDGET_MS:.0f} ms: judged on the chip only]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
