"""Convex-relaxation refinement rung — better-than-FFD node cost, on device.

The vectorized scan (solver/tpu.py) IS sequential first-fit-decreasing, so
its node cost is locked at ~0.99x the FFD oracle no matter how fast it
runs (ROADMAP item 4).  The gap to a globally-optimized packing is
structural: the scan commits each pod GROUP to the locally-cheapest
$/pod candidate at that group's step, so it can never discover that a
cpu-heavy group and a memory-heavy group sharing one balanced node type is
cheaper than each buying its own density-optimal fleet — the
backfill-aware scoring estimates later demand, it never re-decides an
earlier group's type.  CvxCluster (PAPERS.md) solves exactly this class of
large granular allocation problems via per-agent decomposable convex
relaxations, the shape that jits and vmaps; "Priority Matters" (PAPERS.md)
shows constraint-based packing beating greedy heuristics on real clusters.

This module is that rung, built to the repo's serving discipline:

- **The relaxation is a fixed-iteration, fixed-shape device program.**
  Variables ``x[g, c]`` — fractional pods of group ``g`` on candidate
  ``c`` — minimize the fractional node-cost objective
  ``sum_c price_c * max_r(load_cr / alloc_cr)`` (the LP node count of a
  candidate is its bottleneck-resource utilization) by entropic mirror
  descent on the per-group scaled simplexes: multiplicative weights with a
  row-normalized subgradient, the ``max_r`` smoothed by a sharp softmax,
  best-true-cost iterate tracked through the ``lax.scan``.  Shapes pad to
  the SAME ``solve_dims`` G/C rungs the scan compiles at (``relax_dims``
  delegates — never invents a key), and the iteration count buckets onto
  ``RELAX_ITER_RUNGS``, so the program precompiles onto a bounded ladder
  exactly like every other XLA program here (KT008/KT014).  Chosen over a
  host-side LP solver deliberately: scipy's simplex would be exact but is
  a serial host dependency with data-dependent runtime; the mirror-descent
  rung is ~1 ms of dense [G, C] arithmetic with a hard iteration bound,
  and the min-cost select below makes exactness unnecessary for
  correctness — only for win-rate.
- **Rounding reaches integrality on the host, repair seeds the scan.**
  Largest-remainder integerization per group, then a per-candidate
  first-fit (groups descending by the solvers' shared FFD magnitude) into
  whole nodes of the chosen type, provisioner limits and the pods-resource
  row enforced from the same tensors the scan packs with.  Pods the
  rounding strands (integrality slack, a limit binding) first-fit into the
  open capacity of the rounded fleet — the vectorized prefix-allocation
  pattern of the PR-6 warm-start host tier — and any remainder re-solves
  through the caller's ``repair_solve`` hook: the existing scan, SEEDED
  from the rounded solution as its existing-node state (the PR-6
  machinery), so repair composes spread/affinity-exactly with everything
  already placed.
- **Never worse by construction.**  Only *unconstrained* pod groups are
  eligible (no spread/affinity/hostname caps, no zone/capacity-type
  pinning, nothing watching them through a constraint selector, fully
  placed on solver-proposed nodes whose every pod is itself eligible) —
  constraint-bearing pods keep their scan seats as fixed boundary
  conditions.  The rung re-packs the eligible pods, self-validates the
  rounded fleet (capacity, exactly-once assignment), and the solver ships
  whichever of {scan, relax+round} costs strictly less:
  ``karpenter_solver_relax_total{outcome=improved|tied|fallback|skipped}``
  partitions every evaluation.

Knobs: ``KT_RELAX`` (default on) gates the rung, ``KT_RELAX_ITERS``
(default 64, bucketed up to RELAX_ITER_RUNGS) sets the descent budget,
``KT_RELAX_DELTA`` (default off) opts delta-chain full-solve boundaries in
(solver/scheduler.py routes; delta scan steps and megabatch slots always
skip — the rung buys $ at latency, the wrong trade on those paths).
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import (
    RELAX_DURATION,
    RELAX_IMPROVEMENT,
    RELAX_OUTCOMES,
    RELAX_TOTAL,
    Registry,
    registry as default_registry,
)
from ..gang import gang_fixed
from ..models import labels as L
from ..obs.trace import NULL_TRACE
from .types import SimNode, SolveResult

logger = logging.getLogger(__name__)

#: iteration-count compile rungs: KT_RELAX_ITERS buckets UP onto this
#: ladder (smallest rung >= the ask; the top rung caps it), so the relax
#: program's compile signatures stay log-bounded and precompilable exactly
#: like the tensor-axis rungs (KT014 audits the ladder's health)
RELAX_ITER_RUNGS = (32, 64, 128, 256)
DEFAULT_RELAX_ITERS = 64

#: softmax sharpness smoothing the per-candidate max_r bottleneck (the
#: objective's only non-smooth piece); the best-TRUE-cost iterate tracking
#: makes the smoothing a descent aid, never a correctness input
_TAU = 64.0
#: mirror-descent step on the range-normalized subgradient
_ETA = 1.0


def mirror_eta(t):
    """Step size η/√(1+t/8) of the mirror-descent ladder at iteration ``t``
    (float — pass ``t.astype(jnp.float32)`` from traced code).  One source
    for the schedule: the relax rung's multiplicative-weights loop and the
    hierarchical price ascent (solver/hierarchy.py) share it so the two
    rungs decay in lockstep."""
    return _ETA / jnp.sqrt(1.0 + t / 8.0)


def relax_enabled() -> bool:
    return os.environ.get("KT_RELAX", "1") != "0"


def relax_delta_enabled() -> bool:
    """Whether delta-chain FULL-solve boundaries run the rung (default
    off: a delta chain is the latency path; KT_RELAX_DELTA=1 opts in)."""
    return os.environ.get("KT_RELAX_DELTA", "0") == "1"


def configured_iters() -> int:
    """The live iteration budget, read through the knob registry
    (ISSUE 19): a tuned override wins, else the registry falls back to
    ``KT_RELAX_ITERS``/the default at call time — env workflows are
    untouched until the controller actually moves the knob."""
    from ..tuning.knobs import global_knobs

    try:
        # ktlint: allow[KT014] registry knob NAME, not a key tail
        return int(global_knobs().get("relax_iters"))
    except (TypeError, ValueError):
        return DEFAULT_RELAX_ITERS


def iter_rung(n: int) -> int:
    """Bucket an iteration ask UP onto RELAX_ITER_RUNGS (top rung caps)."""
    for r in RELAX_ITER_RUNGS:
        if n <= r:
            return r
    return RELAX_ITER_RUNGS[-1]


def _relax_key_tail(relax_iters: int) -> tuple:
    """The relax program's compile-key suffix — the SINGLE source of this
    format; ``relax_signature`` and the KT014 audit both anchor on it."""
    return (("relax_iters", relax_iters),)


def relax_dims(st) -> dict:
    """The relax program's padded dims: the G/C rungs of the scan's own
    ``solve_dims`` bucketing (delegated — the single source of the
    bucketing math; an invented key would be a compile axis no rung ladder
    bounds, KT014) plus the resource width."""
    from .tpu import solve_dims

    # NE/node_budget only shape the NR axis, which the relax program does
    # not carry; the minimal budget keeps the delegate's estimate cheap
    dims = solve_dims(st, NE=0, node_budget=1)
    return dict(G=dims["G"], C=dims["C"], R=dims["R"])


def relax_signature(st, relax_iters: Optional[int] = None) -> tuple:
    """Compile signature of the relax program for this tensor shape — the
    key TpuSolver readiness/warm bookkeeping tracks for it."""
    from .tpu import _dims_key

    iters = iter_rung(configured_iters() if relax_iters is None
                      else relax_iters)
    return (("relax", True),) + _dims_key(relax_dims(st)) \
        + _relax_key_tail(iters)


def zero_init_metrics(registry: Registry) -> None:
    """Register the relax series at 0 so rate()/increase() never lose the
    first evaluation (KT003)."""
    for outcome in RELAX_OUTCOMES:
        if not registry.counter(RELAX_TOTAL).has({"outcome": outcome}):
            registry.counter(RELAX_TOTAL).inc({"outcome": outcome},
                                              value=0.0)
    registry.histogram(RELAX_DURATION)
    if not registry.gauge(RELAX_IMPROVEMENT).has():
        # 1.0 = parity (no comparison yet): the series exists from
        # construction without claiming an improvement that never ran
        registry.gauge(RELAX_IMPROVEMENT).set(1.0)


def record_outcome(registry: Registry, outcome: str,
                   seconds: Optional[float] = None,
                   ratio: Optional[float] = None) -> None:
    registry.counter(RELAX_TOTAL).inc({"outcome": outcome})
    if seconds is not None:
        registry.histogram(RELAX_DURATION).observe(seconds)
    if ratio is not None:
        registry.gauge(RELAX_IMPROVEMENT).set(ratio)


# ---------------------------------------------------------------------------
# the device program
# ---------------------------------------------------------------------------


def _relax_program(req, counts, feas, alloc_inv, price, x0,
                   relax_iters: int):
    """Entropic mirror descent on the fractional allocation relaxation.

    ``req[G, R]`` per-pod requests, ``counts[G]`` pods per group (0 for
    ineligible/padding rows), ``feas[G, C]`` bool feasibility,
    ``alloc_inv[C, R]`` reciprocal candidate allocatable (0 where the
    candidate lacks the resource), ``price[C]`` effective $/hr (cheapest
    available offering), ``x0[G, C]`` warm start (the scan's own
    solution).  Objective ``f(x) = sum_c price_c * max_r(load_cr *
    alloc_inv_cr)`` — convex (max of linears); minimized over the product
    of per-group scaled simplexes by multiplicative-weights updates.
    Returns ``(best_x, best_cost)`` — the best TRUE-objective iterate, so
    the softmax smoothing inside the gradient can never degrade the
    reported solution below the warm start."""
    feas_f = feas.astype(jnp.float32)

    def renorm(x):
        x = x * feas_f
        s = jnp.sum(x, axis=1, keepdims=True)
        return jnp.where(s > 1e-30, x / jnp.maximum(s, 1e-30), 0.0) \
            * counts[:, None]

    def util(x):
        return (x.T @ req) * alloc_inv            # [C, R]

    def cost(x):
        return jnp.sum(price * jnp.max(util(x), axis=1))

    def grad(x):
        w = jax.nn.softmax(_TAU * util(x), axis=1)  # [C, R] bottleneck mix
        return req @ (price[:, None] * w * alloc_inv).T  # [G, C]

    x_init = renorm(x0)

    def step(carry, t):
        x, bx, bf = carry
        g = grad(x)
        gmin = jnp.min(jnp.where(feas, g, jnp.inf), axis=1, keepdims=True)
        gmax = jnp.max(jnp.where(feas, g, -jnp.inf), axis=1, keepdims=True)
        spread = jnp.maximum(gmax - gmin, 1e-12)
        eta = mirror_eta(t.astype(jnp.float32))
        x = renorm(x * jnp.exp(-eta * (g - gmin) / spread))
        f = cost(x)
        better = f < bf
        bx = jnp.where(better, x, bx)
        bf = jnp.where(better, f, bf)
        return (x, bx, bf), jnp.int32(0)

    (x, bx, bf), _ = jax.lax.scan(
        step, (x_init, x_init, cost(x_init)),
        jnp.arange(relax_iters, dtype=jnp.int32))
    return bx, bf


#: module-level jitted program (KT008: the wrapper is created once; the
#: iteration rung is the only static axis beyond the padded shapes)
relax_jit = partial(jax.jit, static_argnames=("relax_iters",))(
    _relax_program
)


# ktlint: fence the relax rung's one D2H read — the refinement program's
# result comes back here, strictly after the main solve already fenced
def _run_relax(req, counts, feas, alloc_inv, price, x0, relax_iters: int,
               guard=None) -> Tuple[np.ndarray, float]:
    def call():
        return relax_jit(req, counts, feas, alloc_inv, price, x0,
                         relax_iters=relax_iters)

    bx, bf = guard.run(call) if guard is not None else call()
    return np.asarray(bx), float(np.asarray(bf))


# ktlint: fence the warm thunk's D2H read is the deliberate compile+fence
# of the background relax-program warm (discarded results, warm thread)
def warm_relax(solver, st, relax_iters: Optional[int] = None,
               on_done=None) -> bool:
    """Background-compile the relax program for this tensor shape on the
    solver's warm machinery (concurrency cap, bounded queue, failure
    backoff) — the compile-behind contract: the serving path skips the
    rung while its program is cold and never stalls on XLA.
    ``on_done(sig, seconds, error)`` fires when the warm ends, as for
    ``TpuSolver.warm_async`` — the scheduler passes its ``_warm_done`` so a
    failed relax compile is logged, counted and fails ``--warmup``."""
    iters = iter_rung(configured_iters() if relax_iters is None
                      else relax_iters)
    sig = relax_signature(st, iters)
    dims = relax_dims(st)
    Gp, Cp, R = dims["G"], dims["C"], dims["R"]

    def thunk():
        req = np.zeros((Gp, R), dtype=np.float32)
        req[:, :1] = 1.0
        counts = np.ones(Gp, dtype=np.float32)
        feas = np.ones((Gp, Cp), dtype=bool)
        alloc_inv = np.ones((Cp, R), dtype=np.float32)
        price = np.ones(Cp, dtype=np.float32)
        x0 = np.ones((Gp, Cp), dtype=np.float32)
        bx, _bf = relax_jit(req, counts, feas, alloc_inv, price, x0,
                            relax_iters=iters)
        np.asarray(bx)  # fence: the compile has landed
        solver._mark_ready(sig)

    return solver.warm_custom(sig, thunk, on_done=on_done)


# ---------------------------------------------------------------------------
# host-side eligibility + feasibility
# ---------------------------------------------------------------------------


def host_feasibility(st) -> np.ndarray:
    """Numpy mirror of the device feasibility (labels & fit & provisioner)
    — byte-identical semantics to ops/feasibility's gather path.  The label
    and provisioner part is ``coalesce.label_feasibility``, which the
    extraction of the same tensors has already computed and cached on
    them."""
    from .coalesce import label_feasibility

    G, C = st.G, st.C
    if G == 0 or C == 0:
        return np.zeros((G, C), dtype=bool)
    req = np.asarray(st.requests, dtype=np.float32)  # [G, R]
    alloc = np.asarray(st.cand_alloc, dtype=np.float32)
    fit = np.all((req[:, None, :] <= alloc[None, :, :] + 1e-6)
                 | (req[:, None, :] <= 0), axis=2)
    return label_feasibility(st) & fit


def _host_dom_ok(st) -> np.ndarray:
    """Numpy mirror of the device per-group domain allowance [G, D]."""
    zone_key = st.vocab.key_id[L.ZONE]
    ct_key = st.vocab.key_id[L.CAPACITY_TYPE]
    pm = np.asarray(st.pm)
    dom_vw = np.asarray(st.dom_vw)
    dom_vb = np.asarray(st.dom_vb).astype(np.uint32)
    zw = pm[:, zone_key, :][:, dom_vw[:, 0]]         # [G, D]
    zok = ((zw >> dom_vb[None, :, 0]) & np.uint32(1)).astype(bool)
    cw = pm[:, ct_key, :][:, dom_vw[:, 1]]
    cok = ((cw >> dom_vb[None, :, 1]) & np.uint32(1)).astype(bool)
    return zok & cok


def eligible_partition(st, result: SolveResult):
    """Partition the solved batch for the rung.

    Returns ``(elig, freed, lifted, seats)``: the group indexes with
    lifted pods, the freed solver-proposed node names the rung may
    re-pack, ``lifted[gi] -> [pods]`` — exactly the pods the rung
    re-seats — and ``seats[node] -> {gi: pods}`` over the freed nodes
    (the scan-solution warm start ``x0`` derives from it).

    A group is STATICALLY eligible iff it is unconstrained (no spread /
    hostname cap / (anti-)affinity slots, no volume or daemonset
    coupling, every available zone+capacity-type domain allowed — no
    pinning) and UNWATCHED (no constraint selector of any group matches
    its pods — the PR-6 coupling-guard condition: re-seating a watched
    pod silently changes someone else's spread count).  A node is freed
    iff EVERY pod seated on it belongs to a statically-eligible group (a
    mixed node stays whole — its constrained pods are boundary conditions
    and lifting only its unconstrained pods would strand slack the cost
    compare can't win back).  The rung lifts exactly the pods on freed
    nodes: eligible pods backfilled onto constrained or existing nodes
    keep their seats, so constraint-bearing placements are never
    disturbed and partial lifts stay sound by construction."""
    G = st.G
    pod_group: Dict[str, int] = {}
    for gi, g in enumerate(st.groups):
        for p in g.pods:
            pod_group[p.name] = gi

    watched = (np.asarray(st.g_sel_match).any(axis=0)
               if st.S else np.zeros(G, dtype=bool))
    dom_ok = _host_dom_ok(st)
    avail_dom = np.asarray(st.cand_avail).any(axis=0)  # [D]

    static_ok = np.zeros(G, dtype=bool)
    for gi, g in enumerate(st.groups):
        rep = g.pods[0]
        if (st.g_zone_spread[gi] >= 0 or st.g_host_spread[gi] >= 0
                or st.g_zone_anti[gi] >= 0 or st.g_zone_paff[gi] >= 0
                or st.g_host_paff[gi] >= 0 or bool(watched[gi])):
            continue
        if rep.volume_claims or rep.volume_zone_requirements or rep.is_daemon:
            continue
        if gang_fixed(rep):
            # gang members are relax-INELIGIBLE (ISSUE 20): their scan
            # seats are fixed boundary conditions the gang epilogue audits
            # and packs — the rung must not move them out from under it
            continue
        if not bool(np.all(dom_ok[gi] | ~avail_dom)):
            continue  # zone/ct pinning: the node's domain choice couples
        static_ok[gi] = True

    freed: Set[str] = set()
    lifted: Dict[int, List] = {}
    seats: Dict[str, Dict[int, int]] = {}  # freed node -> {gi: pods}
    for n in result.nodes:
        gis = []
        ok = True
        for q in n.pods:
            gi = pod_group.get(q.name)
            if gi is None or not static_ok[gi]:
                ok = False  # carve-out or constrained pod pins the node
                break
            gis.append(gi)
        if not ok:
            continue
        freed.add(n.name)
        cnt: Dict[int, int] = {}
        for gi, q in zip(gis, n.pods):
            lifted.setdefault(gi, []).append(q)
            cnt[gi] = cnt.get(gi, 0) + 1
        seats[n.name] = cnt
    return set(lifted), freed, lifted, seats


# ---------------------------------------------------------------------------
# rounding + repair
# ---------------------------------------------------------------------------


def _largest_remainder(row: np.ndarray, total: int) -> np.ndarray:
    """Integerize a non-negative row to the exact total, largest
    fractional parts first."""
    base = np.floor(row).astype(np.int64)
    delta = total - int(base.sum())
    if delta > 0:
        frac = row - base
        for i in np.argsort(-frac)[:delta]:
            base[i] += 1
    elif delta < 0:
        frac = row - base
        order = [i for i in np.argsort(frac) if base[i] > 0]
        for i in order[: -delta]:
            base[i] -= 1
    return base


def _prefix_fit(res_mat: np.ndarray, req: np.ndarray, k: int):
    """First-fit ``k`` identical pods with request ``req`` into the node
    residual rows ``res_mat`` in order (the PR-6 warm-start host tier's
    vectorized prefix allocation).  Returns (takes[N], placed)."""
    if not len(res_mat) or k <= 0:
        return np.zeros(len(res_mat), dtype=np.int64), 0
    pos = req > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        cap = np.floor(np.min(
            np.where(pos[None, :],
                     (res_mat + 1e-9) / np.maximum(req[None, :], 1e-12),
                     np.inf),
            axis=1))
    cap = np.where(np.isfinite(cap), np.maximum(cap, 0.0), float(k))
    before = np.cumsum(cap) - cap
    takes = np.clip(k - before, 0.0, cap).astype(np.int64)
    return takes, int(takes.sum())


class _Rounding:
    """Mutable state of the integral build: the open node fleet (one
    residual row per node), assignments, and provisioner-limit usage."""

    def __init__(self, st, prov_used: np.ndarray) -> None:
        self.st = st
        self.prov_used = prov_used                  # [P, R] mutable
        self.node_cand: List[int] = []              # candidate per node
        self.node_res: List[np.ndarray] = []        # residual per node
        self.takes: List[Tuple[int, int, int]] = []  # (gi, node_idx, k)
        self.cost = 0.0

    def limit_headroom(self, ci: int) -> int:
        p = int(self.st.cand_prov[ci])
        cap_row = np.asarray(self.st.cand_cap[ci], dtype=np.float64)
        head = np.asarray(self.st.prov_limits[p], dtype=np.float64) \
            - self.prov_used[p]
        with np.errstate(divide="ignore", invalid="ignore"):
            per = np.where(cap_row > 0,
                           np.floor((head + 1e-6) / np.maximum(cap_row, 1e-12)),
                           np.inf)
        n = np.min(per)
        return int(n) if np.isfinite(n) else (1 << 30)

    def buy(self, ci: int, n: int, price: float) -> List[int]:
        p = int(self.st.cand_prov[ci])
        self.prov_used[p] += np.asarray(self.st.cand_cap[ci],
                                        dtype=np.float64) * n
        idxs = []
        alloc = np.asarray(self.st.cand_alloc[ci], dtype=np.float64)
        for _ in range(n):
            idxs.append(len(self.node_res))
            self.node_cand.append(ci)
            self.node_res.append(alloc.copy())
        self.cost += price * n
        return idxs

    def fill(self, gi: int, node_idxs: Sequence[int], k: int) -> int:
        """First-fit k pods of group gi into the given nodes; returns the
        number placed."""
        if not node_idxs or k <= 0:
            return 0
        req = np.asarray(self.st.requests[gi], dtype=np.float64)
        res_mat = np.stack([self.node_res[i] for i in node_idxs])
        takes, placed = _prefix_fit(res_mat, req, k)
        for j, ni in enumerate(node_idxs):
            if takes[j] > 0:
                self.node_res[ni] = res_mat[j] - req * takes[j]
                self.takes.append((gi, ni, int(takes[j])))
        return placed


def _sparsify(x: np.ndarray, counts: np.ndarray, feas: np.ndarray,
              req: np.ndarray, alloc_inv: np.ndarray,
              frac: float = 0.05, rounds: int = 3) -> np.ndarray:
    """Concentrate the descent's interior point before integerizing.

    Entropic mirror descent converges to interior points that smear a few
    percent of every group across many near-optimal candidates; rounded
    literally, every touched candidate pays a partial last node and the
    integral cost explodes.  Two alternating prunes, renormalizing after
    each: (a) per GROUP, drop allocations under ``frac`` of the group
    (keeping its largest), (b) per CANDIDATE, drop candidates carrying
    less than ~one node's worth of total bottleneck load.  Each prune can
    only move mass onto candidates the descent already ranked higher, and
    the never-worse select downstream makes aggressiveness safe."""
    x = x.copy()
    for _ in range(rounds):
        keep = x >= frac * np.maximum(counts[:, None], 1.0)
        amax = x.argmax(axis=1)
        keep[np.arange(len(x)), amax] = True
        x = np.where(keep & feas, x, 0.0)
        y = ((x.T @ req) * alloc_inv).max(axis=1)    # fractional node count
        col_keep = y >= 0.9
        col_keep[x.argmax(axis=1)] = True            # every row keeps a home
        x = np.where(col_keep[None, :], x, 0.0)
        s = x.sum(axis=1, keepdims=True)
        x = np.where(s > 0, x / np.maximum(s, 1e-30), 0.0) * counts[:, None]
    return x


def _round_solution(st, x: np.ndarray, lift_counts: Dict[int, int],
                    prov_used: np.ndarray, F: np.ndarray):
    """Integral build from the fractional solution.

    Per group: largest-remainder split over its candidates.  Per
    candidate: buy the integral bottleneck node count and fill each node
    with the PROPORTIONAL group mix — node ``j`` takes
    ``round((j+1)*n_gc/N) - round(j*n_gc/N)`` pods of group ``g`` — which
    is what realizes the relaxation's complementary-resource pairing
    (group-sequential first-fit would exhaust one resource before the
    complementary group arrives and re-fragment into per-group fleets).
    Per-node integer jitter that overflows capacity is re-fit within the
    candidate, then stranded pods backfill cross-candidate.  Returns
    ``(rounding, leftovers{gi: count})``; None when a group has no
    purchasable candidate at all."""
    G, C = st.G, st.C
    x = np.maximum(np.asarray(x[:G, :C], dtype=np.float64), 0.0)

    pr = np.where(np.asarray(st.cand_avail), np.asarray(st.cand_price),
                  np.inf)
    p_c = pr.min(axis=1)                             # effective $/node

    n_alloc = np.zeros((G, C), dtype=np.int64)
    for gi in sorted(lift_counts):
        row = np.where(F[gi] & np.isfinite(p_c), x[gi], 0.0)
        total = int(lift_counts[gi])
        s = row.sum()
        if s <= 0:
            # descent starved the row (all-infeasible numerics): fall back
            # to the cheapest-density feasible candidate for the group
            ok = F[gi] & np.isfinite(p_c)
            if not ok.any():
                return None, {gi: total}
            req = np.asarray(st.requests[gi], dtype=np.float64)
            alloc = np.asarray(st.cand_alloc, dtype=np.float64)
            with np.errstate(divide="ignore", invalid="ignore"):
                ppn = np.min(np.where(req[None, :] > 0,
                                      np.floor(alloc / np.maximum(req[None, :],
                                                                  1e-12)),
                                      np.inf), axis=1)
            dens = np.where(ok & (ppn >= 1), p_c / np.maximum(ppn, 1.0),
                            np.inf)
            row = np.zeros(C)
            row[int(np.argmin(dens))] = 1.0
            s = 1.0
        n_alloc[gi] = _largest_remainder(row * (total / s), total)

    rounding = _Rounding(st, prov_used)
    leftovers: Dict[int, int] = {}
    order = [int(g) for g in np.argsort(-np.asarray(st.magnitude))]
    requests = np.asarray(st.requests, dtype=np.float64)
    for ci in range(C):
        col = n_alloc[:, ci]
        if col.sum() == 0:
            continue
        if not np.isfinite(p_c[ci]):
            for gi in np.nonzero(col)[0]:
                leftovers[gi] = leftovers.get(gi, 0) + int(col[gi])
            continue
        alloc_c = np.asarray(st.cand_alloc[ci], dtype=np.float64)
        load = requests.T @ col                       # [R]
        with np.errstate(divide="ignore", invalid="ignore"):
            per_r = np.where(alloc_c > 1e-9,
                             load / np.maximum(alloc_c, 1e-9), np.inf)
            per_r = np.where(load > 1e-9, per_r, 0.0)
        bottleneck = float(np.max(per_r))
        if not np.isfinite(bottleneck):
            for gi in np.nonzero(col)[0]:
                leftovers[gi] = leftovers.get(gi, 0) + int(col[gi])
            continue
        n_nodes = max(int(np.ceil(bottleneck)), 1)
        buy = min(n_nodes, rounding.limit_headroom(ci))
        cand_nodes = rounding.buy(ci, buy, float(p_c[ci])) if buy else []
        overflow: Dict[int, int] = {}
        placed_col = np.zeros(G, dtype=np.int64)
        if buy:
            # vectorized proportional quotas: node j of the fleet takes
            # round((j+1)*n_g/buy) - round(j*n_g/buy) pods of group g —
            # telescopes to exactly n_g, never more than ±1 off the real-
            # valued per-node mix the bottleneck guarantees fits
            used_g = np.nonzero(col)[0]
            n_g = col[used_g].astype(np.float64)
            steps = np.arange(buy + 1, dtype=np.float64)[:, None]
            cum = np.rint(steps * n_g[None, :] / buy)
            quota = (cum[1:] - cum[:-1]).astype(np.int64)   # [buy, |used|]
            load = quota @ requests[used_g]                 # [buy, R]
            fits = np.all(load <= alloc_c[None, :] + 1e-9, axis=1)
            for j in np.nonzero(~fits)[0]:
                # integer jitter overflowed this node: sequential re-take
                # in FFD-magnitude order, overflow re-queued below
                res = alloc_c.copy()
                for oi in sorted(range(len(used_g)),
                                 key=lambda i: order.index(int(used_g[i]))):
                    t = int(quota[j, oi])
                    if t <= 0:
                        continue
                    req_g = requests[used_g[oi]]
                    pos = req_g > 0
                    with np.errstate(divide="ignore", invalid="ignore"):
                        cap = np.min(np.where(
                            pos, (res + 1e-9) / np.maximum(req_g, 1e-12),
                            np.inf))
                    take = int(min(t, max(int(cap), 0)))
                    quota[j, oi] = take
                    res -= req_g * take
                load[j] = quota[j] @ requests[used_g]
            for j, ni in enumerate(cand_nodes):
                rounding.node_res[ni] = alloc_c - load[j]
            nz_j, nz_i = np.nonzero(quota)
            for j, oi in zip(nz_j.tolist(), nz_i.tolist()):
                gi = int(used_g[oi])
                k = int(quota[j, oi])
                rounding.takes.append((gi, cand_nodes[j], k))
                placed_col[gi] += k
        for gi in np.nonzero(col)[0]:
            short = int(col[gi]) - int(placed_col[gi])
            if short > 0:
                overflow[int(gi)] = overflow.get(int(gi), 0) + short
        # re-fit integer jitter within the candidate's own fleet first,
        # then fund the straggler tail with extra whole nodes (the ceil
        # bottleneck is exact in aggregate; ±1-pod-per-group-per-node
        # jitter can exceed it by a node or two at scale)
        for gi in list(overflow):
            placed = rounding.fill(gi, cand_nodes, overflow[gi])
            overflow[gi] -= placed
            k = overflow[gi]
            if k > 0:
                req_g = requests[gi]
                pos = req_g > 0
                with np.errstate(divide="ignore", invalid="ignore"):
                    ppn = np.min(np.where(pos, np.floor(
                        (alloc_c + 1e-6) / np.maximum(req_g, 1e-12)),
                        np.inf))
                if np.isfinite(ppn) and ppn >= 1:
                    extra = min(int(np.ceil(k / ppn)),
                                rounding.limit_headroom(ci))
                    if extra > 0:
                        new_idxs = rounding.buy(ci, extra, float(p_c[ci]))
                        cand_nodes.extend(new_idxs)
                        k -= rounding.fill(gi, new_idxs, k)
            if k > 0:
                leftovers[gi] = leftovers.get(gi, 0) + k

    # cross-candidate backfill: stranded pods take any open rounded
    # capacity on a candidate their group is feasible for
    if leftovers and rounding.node_res:
        for gi in sorted(leftovers):
            ok_nodes = [i for i, ci in enumerate(rounding.node_cand)
                        if F[gi, ci]]
            placed = rounding.fill(gi, ok_nodes, leftovers[gi])
            leftovers[gi] -= placed
        leftovers = {gi: k for gi, k in leftovers.items() if k > 0}
    return rounding, leftovers


def _materialize(st, rounding: _Rounding,
                 lifted: Dict[int, List]) -> Tuple[List[SimNode],
                                                   Dict[str, str]]:
    """SimNodes + assignments from the rounded build (same construction
    as the scan's extraction, solver/tpu.py _extract).  Pods come from
    the partition's lifted pools — the exact pods taken off the freed
    nodes, never a group-mate that kept its seat."""
    pr = np.where(np.asarray(st.cand_avail), np.asarray(st.cand_price),
                  np.inf)
    d_c = pr.argmin(axis=1)
    n_ct = max(1, len(st.ct_names))
    nodes: List[SimNode] = []
    for ci in rounding.node_cand:
        prov_name, type_name = st.cand_names[ci]
        di = int(d_c[ci])
        zone = st.zone_names[int(st.dom_zone[di])] if st.zone_names else ""
        node = SimNode(
            instance_type=type_name,
            provisioner=prov_name,
            zone=zone,
            capacity_type=st.ct_names[di % n_ct] if st.ct_names else "",
            price=float(pr[ci, di]),
            allocatable={
                st.vocab.resources[r]: float(st.cand_alloc[ci, r])
                for r in range(st.cand_alloc.shape[1])
            },
            existing=False,
        )
        node.stamp_labels()
        nodes.append(node)

    per_group: Dict[int, List[Tuple[int, int]]] = {}
    for gi, ni, k in rounding.takes:
        per_group.setdefault(gi, []).append((ni, k))
    assignments: Dict[str, str] = {}
    for gi, picks in per_group.items():
        pods = lifted[gi]
        pos = 0
        for ni, k in picks:
            chunk = pods[pos:pos + k]
            pos += k
            name = nodes[ni].name
            nodes[ni].pods.extend(chunk)
            assignments.update((p.name, name) for p in chunk)
    return nodes, assignments


def _self_validate(st, lift_counts: Dict[int, int], rounding: _Rounding,
                   leftovers: Optional[Dict[int, int]] = None) -> bool:
    """Cheap integrality/capacity audit of the rounded fleet, at group
    granularity (no per-pod walk): every lifted pod placed exactly once
    OR accounted in ``leftovers`` (the repair hook's input), and every
    rounded node's take-derived load within its candidate allocatable.
    Runs BEFORE repair — an overloaded rounded node handed to the repair
    solve as a seed would ship (the scan sees negative residual and just
    places nothing more there).  A failed audit falls back to the scan —
    never ships."""
    G = st.G
    leftovers = leftovers or {}
    placed = np.zeros(G, dtype=np.int64)
    load = np.zeros((len(rounding.node_res), st.requests.shape[1]),
                    dtype=np.float64)
    requests = np.asarray(st.requests, dtype=np.float64)
    for gi, ni, k in rounding.takes:
        placed[gi] += k
        load[ni] += requests[gi] * k
    for gi in range(G):
        want = int(lift_counts.get(gi, 0)) - int(leftovers.get(gi, 0))
        if placed[gi] != want:
            return False
    alloc = np.asarray(st.cand_alloc, dtype=np.float64)
    for ni, ci in enumerate(rounding.node_cand):
        if np.any(load[ni] > alloc[ci] + 1e-6):
            return False
    return True


# ---------------------------------------------------------------------------
# the rung
# ---------------------------------------------------------------------------


def refine(
    result: SolveResult,
    st,
    *,
    registry: Optional[Registry] = None,
    guard=None,
    trace=None,
    repair_solve=None,
    relax_iters: Optional[int] = None,
) -> Tuple[SolveResult, str]:
    """Run the relaxation rung over a scan result and ship the cheaper of
    {scan, relax+round}.  Returns ``(result, outcome)`` with outcome in
    RELAX_OUTCOMES; on every outcome except "improved" the input result is
    returned unchanged.  ``repair_solve(pods, seed_nodes)`` (optional) is
    the integrality repair hook: a full scheduler re-solve of the stranded
    pods SEEDED with the rounded fleet as existing-node state.  The caller
    owns readiness (``relax_signature`` must be warm) and policy routing;
    this function owns the math and the never-worse select."""
    t0 = time.perf_counter()
    registry = registry or default_registry
    trace = trace or NULL_TRACE
    iters = iter_rung(configured_iters() if relax_iters is None
                      else relax_iters)
    with trace.span("relax") as span:
        try:
            out, outcome, ratio = _refine_inner(
                result, st, guard=guard, repair_solve=repair_solve,
                iters=iters, span=span)
        # ktlint: allow[KT005] the rung is an optimization layer: any
        # failure ships the proven scan solution and counts as fallback
        except Exception:
            logger.warning("relax rung failed; scan solution ships",
                           exc_info=True)
            out, outcome, ratio = result, "fallback", None
        span.annotate(outcome=outcome,
                      ratio=None if ratio is None else round(ratio, 4))
    record_outcome(registry, outcome,
                   seconds=time.perf_counter() - t0, ratio=ratio)
    return out, outcome


def _refine_inner(result: SolveResult, st, *, guard, repair_solve,
                  iters: int, span):
    elig, freed, lifted, seats = eligible_partition(st, result)
    # what the rung's device program runs over: the groups it may re-seat
    # and their pods on the nodes it may free
    span.annotate(groups=len(lifted),
                  eligible_pods=sum(len(pods) for pods in lifted.values()))
    if not elig or not freed:
        return result, "skipped", None

    F = host_feasibility(st)
    dims = relax_dims(st)
    Gp, Cp, R = dims["G"], dims["C"], dims["R"]
    G, C = st.G, st.C

    lift_counts = {gi: len(pods) for gi, pods in lifted.items()}
    req = np.zeros((Gp, R), dtype=np.float32)
    req[:G] = st.requests
    counts = np.zeros(Gp, dtype=np.float32)
    for gi, k in lift_counts.items():
        counts[gi] = float(k)
    elig_mask = counts[:G] > 0

    pr = np.where(np.asarray(st.cand_avail), np.asarray(st.cand_price),
                  np.inf)
    p_c = pr.min(axis=1)
    feas = np.zeros((Gp, Cp), dtype=bool)
    feas[:G, :C] = F & elig_mask[:, None] & np.isfinite(p_c)[None, :]
    price = np.zeros(Cp, dtype=np.float32)
    price[:C] = np.where(np.isfinite(p_c), p_c, 0.0).astype(np.float32)

    alloc = np.asarray(st.cand_alloc, dtype=np.float32)
    alloc_inv = np.zeros((Cp, R), dtype=np.float32)
    with np.errstate(divide="ignore"):
        alloc_inv[:C] = np.where(alloc > 1e-9, 1.0 / np.maximum(alloc, 1e-9),
                                 0.0)

    # warm start from the scan's own solution (the freed nodes' seated
    # counts from the partition pass; + a uniform escape term so the
    # descent can leave the scan's vertex)
    cand_index = {pair: ci for ci, pair in enumerate(st.cand_names)}
    node_cand = {n.name: cand_index.get((n.provisioner, n.instance_type))
                 for n in result.nodes if n.name in freed}
    x0 = np.zeros((Gp, Cp), dtype=np.float32)
    for name, cnt in seats.items():
        ci = node_cand.get(name)
        if ci is None:
            continue
        for gi, k in cnt.items():
            if feas[gi, ci]:
                x0[gi, ci] += float(k)
    uni = feas[:G].astype(np.float32)
    usum = uni.sum(axis=1, keepdims=True)
    uni = np.where(usum > 0, uni / np.maximum(usum, 1.0), 0.0) \
        * counts[:G, None]
    x0[:G] = 0.7 * x0[:G] + 0.3 * uni

    bx, _bf = _run_relax(req, counts, feas, alloc_inv, price, x0, iters,
                         guard=guard)
    bx = _sparsify(np.asarray(bx, dtype=np.float64),
                   counts.astype(np.float64), feas,
                   req.astype(np.float64), alloc_inv.astype(np.float64))

    # kept fleet + provisioner usage base (limits bind on raw capacity,
    # matching the scan and the ground-truth validator)
    kept_new = [n for n in result.nodes if n.name not in freed]
    freed_nodes = [n for n in result.nodes if n.name in freed]
    P = len(st.prov_names)
    prov_index = {n: i for i, n in enumerate(st.prov_names)}
    prov_used = np.zeros((P, st.prov_limits.shape[1]), dtype=np.float64)
    for node in list(result.existing_nodes) + kept_new:
        pi = prov_index.get(node.provisioner)
        if pi is not None:
            prov_used[pi] += st.capacity_row(node.instance_type,
                                             node.allocatable)

    rounding, leftovers = _round_solution(st, bx, lift_counts, prov_used, F)
    if rounding is None:
        return result, "fallback", None
    if not _self_validate(st, lift_counts, rounding, leftovers):
        return result, "fallback", None
    nodes_new, assignments_new = _materialize(st, rounding, lifted)

    scan_cost = sum(n.price for n in result.nodes)
    repair_nodes: List[SimNode] = []
    repair_existing: Optional[List[SimNode]] = None
    if leftovers:
        if repair_solve is None:
            return result, "fallback", None
        # integrality repair: re-solve the stranded pods through the
        # existing scan, SEEDED from the rounded solution (the PR-6
        # warm-start shape: rounded + kept nodes are the existing-node
        # state, so the repair packs against everything already placed)
        stranded: List = []
        assigned_names = set(assignments_new)
        for gi, k in leftovers.items():
            pool = [p for p in lifted[gi] if p.name not in assigned_names]
            stranded.extend(pool[:k])
        seeds = list(result.existing_nodes) + kept_new + nodes_new
        sub = repair_solve(stranded, seeds)
        if sub is None or sub.infeasible:
            return result, "fallback", None
        placed = list(sub.existing_nodes)
        ne = len(result.existing_nodes)
        nk = len(kept_new)
        repair_existing = placed[:ne]
        kept_new = placed[ne:ne + nk]
        nodes_new = placed[ne + nk:]
        repair_nodes = list(sub.nodes)
        assignments_new.update(sub.assignments)

    relax_cost = (sum(n.price for n in kept_new)
                  + sum(n.price for n in nodes_new)
                  + sum(n.price for n in repair_nodes))
    ratio = relax_cost / scan_cost if scan_cost > 0 else 1.0
    if relax_cost >= scan_cost - 1e-9:
        return result, ("tied" if relax_cost <= scan_cost + 1e-9
                        else "fallback"), ratio

    # adopt: the rung's fleet replaces the freed nodes
    if repair_existing is not None:
        result.existing_nodes = repair_existing
    result.nodes = kept_new + nodes_new + repair_nodes
    result.assignments.update(assignments_new)
    logger.info(
        "relax rung improved the solve: %d eligible pods re-packed, "
        "node cost %.4f -> %.4f (%.2f%%)",
        sum(lift_counts.values()), scan_cost, relax_cost,
        100.0 * (1.0 - ratio))
    return result, "improved", ratio
