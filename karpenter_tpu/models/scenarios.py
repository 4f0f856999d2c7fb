"""Named provisioning scenarios, built from nothing but their arguments.

The one place the on-chip smoke (``chip_smoke.py``), the profiling scripts
and the tests get their pod sets from, so "config 2" means the same 50,000
pods everywhere (``benchmarks/gen.py`` keeps its own copy of the c2/c3
shapes: the benchmark imports nothing of the program it measures).  Pure model code: no
jax, no solver import.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from . import labels as L
from .catalog import generate_catalog
from .instancetype import GIB, InstanceType
from .pod import LabelSelector, PodSpec, TopologySpreadConstraint
from .provisioner import Provisioner


def spread_deployments(nd: int, per: int, tag: str = "h",
                       zones: Optional[Sequence[str]] = None) -> List[PodSpec]:
    """``nd`` deployment-shaped groups of ``per`` pods (per-deployment
    zone-spread selector + owner key — each deployment is one coupling
    component).  ``zones`` pins deployment ``d`` to ``zones[d % len]`` via
    nodeSelector: with distinct zones AND distinct selectors the flat
    program has no channel left to couple blocks (no shared zone for the
    suffix backfill, no co-residency across zone pins) — the
    block-disjoint byte-parity construction."""
    pods = []
    for d in range(nd):
        sel = LabelSelector.of({"app": f"{tag}{d}"})
        node_sel = {L.ZONE: zones[d % len(zones)]} if zones else {}
        for i in range(per):
            pods.append(PodSpec(
                name=f"{tag}{d}-{i}", labels={"app": f"{tag}{d}"},
                requests={"cpu": 0.25 * (1 + d % 8),
                          "memory": (0.5 + (d % 6)) * GIB},
                node_selector=dict(node_sel),
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"{tag}{d}"))
    return pods


def config2_scenario() -> Tuple[List[PodSpec], List[Provisioner],
                                List[InstanceType]]:
    """BASELINE config 2 at full size: 50,000 pods as 20 deployments x
    2,500 with a 3-AZ ``DoNotSchedule`` spread each, the full catalog, one
    default provisioner.  Returns ``(pods, provisioners, catalog)``."""
    return (spread_deployments(20, 2500, tag="d"),
            [Provisioner(name="default").with_defaults()],
            generate_catalog(full=True))


def unconstrained_pods(n: int, tag: str) -> List[PodSpec]:
    """Unconstrained steady-state serving pods: 6 deployment shapes, no
    topology — the classic microservice churn the warm-start host path and
    the relax rung are built for."""
    out = []
    for i in range(n):
        g = i % 6
        out.append(PodSpec(
            name=f"{tag}-{i}", labels={"app": f"ws{g}"},
            requests={"cpu": 0.25 * (1 + g % 3),
                      "memory": (0.5 + g % 4) * 2**30},
            owner_key=f"ws{g}",
        ))
    return out
