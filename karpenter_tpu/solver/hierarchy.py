"""Million-pod hierarchical solving: block decomposition + dual reconciliation.

One flat (pods x types x domains) program holds 50k pods, but the next
order of magnitude does not fit one scan.  This module decomposes the batch the way CvxCluster decomposes its
clustering objective (PAPERS.md: "100-1000x faster via decomposition"):

1. **Partition** — union-find over the coupling guard's constraint
   reachability (the PR-6 warm-start index: a selector slot couples every
   group that CARRIES a hard constraint watching it with every group the
   selector MATCHES).  Namespace/selector-disjoint groups never share a
   component, so they can solve independently; a component is never split
   across blocks (fuzz-asserted).  Components are LPT-packed by pod count
   into at most ``MEGA_MAX_SLOTS`` blocks.

2. **Block solve** — every block is one slot of ONE vmapped megabatch
   dispatch (``solve_many_prepared``): the shared catalog tensors are built
   once (``_host_arrays`` base) and broadcast across slots by the
   dispatcher's ``_stack``; a block differs only by its masked counts
   vector, its suffix backfill projection, and its node budget.  One device
   round trip solves every block.

3. **Price loop** — blocks contend for shared capacity (provisioner
   limits).  A fixed-iteration dual ascent on the relax rung's
   mirror-descent schedule (``relax.mirror_eta``) prices over-subscribed
   provisioners up multiplicatively; contending blocks re-solve against the
   price-adjusted candidate costs — again ONE dispatch per wave — until
   either no limit is violated or the ``KT_HIER_PRICE_ITERS`` budget
   expires.  Fixed-iteration duals (not a global LP): every wave is the
   same compiled program at the same signature, the wall-clock budget is a
   hard constant, and an imperfect price equilibrium is repaired exactly in
   step 4 — an LP would give exact prices for a relaxation we round anyway.

4. **Repair** — the host enforces limits exactly (evicting the most
   expensive nodes of any still-over provisioner) and re-seats stragglers
   (evicted pods + block-infeasible pods) through the PR-6 warm-start path
   (``warmstart.delta_solve``): first-fit into the merged solution's
   residual capacity, flat re-solve against the kept nodes for the rest.
   A cross-block tail pass then evicts each block's most underfull node
   (every block rounds its own tail up to a whole node — the one cost flat
   pays nowhere) and re-seats those pods jointly through the same path;
   the cheaper of before/after ships, so repair is never-worse by select.

The per-wave hot path runs PACKED: feasibility as int8 and prices as bf16
(``models/tensorize.pack_feasibility``/``pack_scores`` — ~4x fewer HBM
bytes than the float32 layout the relax rung materializes), scored either
by a lax program or a hand-written Pallas kernel behind ``KT_PALLAS``
(Mosaic-lowered; tests pass ``interpret=True`` explicitly to run it on the
CPU) with byte-parity between the two.

Import-light by design: no jax at module import — the partition, the LPT
packer and the scale model are pure numpy/stdlib so
``scripts/profile_solve.py --hier`` can time them without a backend.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..metrics import (
    HIER_BLOCKS,
    HIER_DURATION,
    HIER_PATHS,
    HIER_PRICE_ITERATIONS,
    HIER_REPAIR_PODS,
    HIER_SOLVES,
    Registry,
)
from ..gang import gang_enabled
from ..obs.trace import NULL_TRACE
from .types import SimNode, SolveResult

logger = logging.getLogger(__name__)

#: infeasible-cost sentinel, shared with the scan program's padding value
_BIG = float(np.float32(3.0e38))

DEFAULT_HIER_THRESHOLD = 100_000
DEFAULT_PRICE_ITERS = 4

#: what the scale model reports for a device-dependent term when the run
#: that seeded it measured no device rate
NOT_MEASURED = "not measured"

def hier_threshold() -> int:
    """Pod count at/above which the scheduler routes hierarchically
    (default 100k; 0 disables the hierarchical path entirely).  Read
    through the knob registry (ISSUE 19): a tuned override wins, else
    the registry falls back to ``KT_HIER_THRESHOLD``/the default at
    call time — env workflows are untouched until something moves the
    knob."""
    from ..tuning.knobs import global_knobs

    try:
        return int(global_knobs().get("hier_threshold"))
    except (TypeError, ValueError):
        return DEFAULT_HIER_THRESHOLD


def hier_price_iters() -> int:
    """Fixed price-ascent wave budget (``KT_HIER_PRICE_ITERS``)."""
    try:
        return max(0, int(os.environ.get("KT_HIER_PRICE_ITERS",
                                         DEFAULT_PRICE_ITERS)))
    except ValueError:
        return DEFAULT_PRICE_ITERS


def pallas_enabled() -> bool:
    """Whether the packed score kernel runs the Pallas program
    (``KT_PALLAS=1``; default = the lax program, byte-identical)."""
    return os.environ.get("KT_PALLAS", "0") == "1"


def zero_init_hier_metrics(registry: Registry) -> None:
    """Register the hierarchical series at 0 (KT003)."""
    for path in HIER_PATHS:
        if not registry.counter(HIER_SOLVES).has({"path": path}):
            registry.counter(HIER_SOLVES).inc({"path": path}, value=0.0)
    registry.histogram(HIER_BLOCKS)
    registry.histogram(HIER_PRICE_ITERATIONS)
    registry.histogram(HIER_REPAIR_PODS)
    registry.histogram(HIER_DURATION)


# ---------------------------------------------------------------------------
# partition: constraint-reachability components -> LPT blocks
# ---------------------------------------------------------------------------


class _UnionFind:
    def __init__(self, n: int) -> None:
        self.parent = list(range(n))

    def find(self, x: int) -> int:
        p = self.parent
        while p[x] != x:
            p[x] = p[p[x]]
            x = p[x]
        return x

    def union(self, a: int, b: int) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[rb] = ra


def coupling_components(st) -> List[List[int]]:
    """Connected components of the group-coupling graph, in first-group
    order.  Two groups couple iff some selector slot reaches both: a slot
    ``sid`` connects every group whose hard constraint CARRIES it (zone/
    host spread, anti-affinity, zone/host pod affinity — the same slot-id
    tensors the scan consumes) with every group the selector MATCHES
    (``g_sel_match`` — the coupling guard's reachability, exactly what the
    PR-6 warm-start displacement index walks).  Groups in different
    components share no constraint that could observe each other's
    placements, so their solves commute."""
    G = st.G
    uf = _UnionFind(G)
    S = st.S
    if S:
        sel_match = np.asarray(st.g_sel_match)  # [S, G]
        reach: List[List[int]] = [[] for _ in range(S)]
        for arr in (st.g_zone_spread, st.g_host_spread, st.g_zone_anti,
                    st.g_zone_paff, st.g_host_paff):
            a = np.asarray(arr)
            for gi in np.nonzero(a >= 0)[0]:
                reach[int(a[gi])].append(int(gi))
        for sid in range(S):
            members = set(reach[sid])
            members.update(int(g) for g in np.nonzero(sel_match[sid])[0])
            it = iter(sorted(members))
            first = next(it, None)
            if first is None:
                continue
            for g in it:
                uf.union(first, g)
    # gang never-split (ISSUE 20): groups carrying the same gang tag join
    # one component — the partition must hand an entire gang to one block,
    # or the per-block solves could each place a legal-looking fragment
    # the all-or-nothing epilogue would then have to retract whole
    g_gang = np.asarray(getattr(st, "g_gang", np.zeros(0, dtype=np.int32)))
    if g_gang.size and gang_enabled():
        first_of: Dict[int, int] = {}
        for gi in np.nonzero(g_gang >= 0)[0]:
            tag = int(g_gang[gi])
            anchor = first_of.setdefault(tag, int(gi))
            if anchor != int(gi):
                uf.union(anchor, int(gi))
    comps: Dict[int, List[int]] = {}
    for gi in range(G):
        comps.setdefault(uf.find(gi), []).append(gi)
    return sorted(comps.values(), key=lambda c: c[0])


def partition_blocks(
    st, components: Sequence[Sequence[int]], max_blocks: int,
) -> List[np.ndarray]:
    """LPT-pack components (weight = pod count) into at most ``max_blocks``
    bins; returns one boolean group mask ``[G]`` per non-empty block.  A
    component is NEVER split — the invariant the fuzz harness asserts."""
    counts = np.asarray(st.counts)
    B = max(1, min(int(max_blocks), len(components)))
    weights = [(int(sum(counts[g] for g in comp)), ci)
               for ci, comp in enumerate(components)]
    weights.sort(key=lambda t: (-t[0], t[1]))
    loads = [0] * B
    bins: List[List[int]] = [[] for _ in range(B)]
    for w, ci in weights:
        b = min(range(B), key=lambda i: (loads[i], i))
        loads[b] += w
        bins[b].append(ci)
    masks: List[np.ndarray] = []
    for b in range(B):
        if not bins[b]:
            continue
        mask = np.zeros(st.G, dtype=bool)
        for ci in bins[b]:
            for gi in components[ci]:
                mask[gi] = True
        masks.append(mask)
    return masks


def block_budgets(st, masks: Sequence[np.ndarray]) -> List[int]:
    """Per-block node budget: the block's pod count — the exact worst case
    (one node per pod), so a block solve can never hit slot exhaustion and
    the no-retry (``full_nr``) megabatch contract holds."""
    counts = np.asarray(st.counts)
    return [max(1, int(counts[m].sum())) for m in masks]


# ---------------------------------------------------------------------------
# block entries: one shared base build, per-block masked counts
# ---------------------------------------------------------------------------


def hier_dims(st, node_budget: int) -> dict:
    """Shared dims bucket for every block slot: the standard
    :func:`tpu.solve_dims` bucketing at the WORST block's node budget with
    the full-NR axis (no per-slot exhaustion retry)."""
    from .tpu import solve_dims

    return solve_dims(st, NE=0, node_budget=node_budget, track=True,
                      full_nr=True)


def hier_signature(st, dims: dict, slots: int, mesh=None) -> tuple:
    """Compile signature of the block wave's program.  The blocks ride the
    SAME megabatch program the consolidation sweep compiles (dims + slot
    rung + vocab tail), so the signature IS the dispatch's mega key —
    readiness earned by either caller serves both."""
    from .consolidation import sweep_signature

    return sweep_signature(st, dims, slots, mesh)


def build_block_entries(
    solver,
    st,
    masks: Sequence[np.ndarray],
    budgets: Sequence[int],
    dims: dict,
    *,
    base=None,
    cand_price: Optional[np.ndarray] = None,
    trace=None,
) -> Tuple[List[dict], tuple]:
    """One megabatch entry per block from ONE shared base build.  A block
    differs from the base only by (a) its counts vector masked to member
    groups, (b) the matching per-zone suffix backfill projection, (c) its
    node budget, and — on price waves — (d) the dual-adjusted candidate
    prices.  Everything else (catalog, feasibility inputs, init state) is
    the SAME array object across entries, which the dispatcher's ``_stack``
    broadcasts instead of copying."""
    from .tpu import suffix_projection, zone_share_matrix

    if base is None:
        base = solver._host_arrays(
            st, (), node_budget=max(budgets), track_assignments=True,
            full_nr=True, dims=dims,
        )
    np_consts0, feas0, np_init0, _ = base
    pad_g = dims["G"] - st.G
    Z = dims["Z"]
    np_requests = np_consts0["requests"]
    zone_share = zone_share_matrix(st, pad_g, Z)
    counts_full = np.asarray(st.counts)

    entries: List[dict] = []
    for mask, budget in zip(masks, budgets):
        counts = np.pad(counts_full * mask, (0, pad_g), constant_values=0)
        demand = (counts[:, None] * np_requests).astype(np.float32)
        demand_z = demand[:, None, :] * zone_share[:, :, None]
        count_z = counts[:, None].astype(np.float32) * zone_share
        suffix_res, suffix_cnt = suffix_projection(demand_z, count_z)
        consts = dict(np_consts0, counts=counts, suffix_res=suffix_res,
                      suffix_cnt=suffix_cnt,
                      node_budget=np.int32(budget))
        if cand_price is not None:
            consts["cand_price"] = cand_price
        entries.append(dict(
            r=dict(st=st, existing_nodes=(), max_nodes=int(budget),
                   track_assignments=True, raise_on_exhaust=False,
                   trace=trace or NULL_TRACE),
            np_consts=consts, feas=feas0, np_init=np_init0, dims=dims,
            est_dims=dims, full_dims=dims, full_nr=True, NE=0,
        ))
    return entries, base


def warm_hier(solver, entries: List[dict], slots: int, sig: tuple,
              mesh=None) -> None:
    """Background-compile the block wave's program (compile-behind: the
    serving path falls back to flat while XLA works).  Same thunk shape as
    the consolidation sweep's warm — it IS the same program."""
    from .consolidation import _warm_sweep

    _warm_sweep(solver, entries, slots, sig, mesh=mesh)


# ---------------------------------------------------------------------------
# packed feasibility+score hot path (int8 / bf16; lax or Pallas)
# ---------------------------------------------------------------------------

_PROGRAMS: Dict[object, object] = {}


def _lax_score():
    """The lax reference program: cheapest feasible candidate per group
    over int8 feasibility and bf16 prices (upcast to f32 for compare —
    exactly what the Pallas kernel does, so parity is bit-for-bit)."""
    prog = _PROGRAMS.get("lax")
    if prog is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def run(f_i8, price):  # ktlint: allow[KT008] memoized once in _PROGRAMS — wrapper and compile cache created on first call, reused after

            cost = jnp.where(f_i8 > 0,
                             price.astype(jnp.float32)[None, :], _BIG)
            return (jnp.min(cost, axis=1),
                    jnp.argmin(cost, axis=1).astype(jnp.int32))

        prog = _PROGRAMS["lax"] = run
    return prog


#: Pallas tile: int8 feasibility wants (32, 128) native tiles on TPU
#: (pallas guide); the wrapper pads G/C up to multiples
_TILE_G = 32
_TILE_C = 128


def _pallas_score(Gp: int, Cp: int, interpret: bool = False):
    """Hand-written Pallas kernel for the packed score reduction.  Grid
    over row tiles; the price row is broadcast to every tile.  Argmin is
    expressed as min-over-matching-column-index (first-minimum tie-break,
    identical to ``jnp.argmin``).  Mosaic-lowered unless the caller asks
    for ``interpret`` (the CPU tests do, explicitly — the backend is never
    sniffed to decide it)."""
    key = ("pallas", Gp, Cp, interpret)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(f_ref, p_ref, cost_ref, idx_ref):
        # widen before the compare: an i1 mask made from the int8 tile
        # keeps its (32, 128) layout, and Mosaic cannot relayout it onto
        # the f32 (8, 128) tiles the select runs on ("Invalid relayout" on
        # the v5e); int8 -> int32 is a supported extension
        f = f_ref[...].astype(jnp.int32)
        p = p_ref[...].astype(jnp.float32)          # [1, Cp]
        cost = jnp.where(f > 0, jnp.broadcast_to(p, f.shape), _BIG)
        best = jnp.min(cost, axis=1, keepdims=True)
        col = jax.lax.broadcasted_iota(jnp.int32, cost.shape, 1)
        hit = jnp.where(cost == best, col, Cp)
        cost_ref[...] = best
        idx_ref[...] = jnp.min(hit, axis=1, keepdims=True).astype(jnp.int32)

    call = pl.pallas_call(
        kernel,
        grid=(Gp // _TILE_G,),
        in_specs=[
            pl.BlockSpec((_TILE_G, Cp), lambda i: (i, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TILE_G, 1), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_G, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Gp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Gp, 1), jnp.int32),
        ],
        interpret=interpret,
    )
    # ktlint: allow[KT008] memoized per (Gp, Cp, interpret) in _PROGRAMS —
    # one wrapper per padded shape, created once and reused
    prog = _PROGRAMS[key] = jax.jit(call)
    return prog


def packed_scan_scores(
    f_packed: np.ndarray,
    price_packed: np.ndarray,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(best_cost[G] f32, best_idx[G] i32)`` — cheapest feasible
    candidate per group from PACKED inputs (int8 feasibility, bf16
    prices).  ``use_pallas`` overrides ``KT_PALLAS`` (the parity harness
    runs both); ``interpret`` runs the Pallas kernel in interpret mode
    (CPU tests only).  All-infeasible rows return (``3.0e38``, 0) on
    either path."""
    G, C = f_packed.shape
    if use_pallas is None:
        use_pallas = pallas_enabled()
    if not use_pallas:
        cost, idx = _lax_score()(f_packed, price_packed)
        return np.asarray(cost), np.asarray(idx)
    Gp = -(-G // _TILE_G) * _TILE_G
    Cp = -(-C // _TILE_C) * _TILE_C
    f = np.zeros((Gp, Cp), dtype=np.int8)
    f[:G, :C] = f_packed
    p = np.zeros((1, Cp), dtype=price_packed.dtype)
    p[0, :C] = price_packed
    cost, idx = _pallas_score(Gp, Cp, interpret)(f, p)
    return np.asarray(cost)[:G, 0], np.asarray(idx)[:G, 0]


# ---------------------------------------------------------------------------
# price loop helpers (host-side dual bookkeeping)
# ---------------------------------------------------------------------------


def _prov_usage(st, nodes: Sequence[SimNode], P: int) -> np.ndarray:
    """[P, R] capacity bought per provisioner (the creation-time limit
    accounting rule: ``capacity_row``)."""
    R = st.R
    usage = np.zeros((P, R), dtype=np.float64)
    index = {name: i for i, name in enumerate(st.prov_names)}
    for n in nodes:
        pi = index.get(n.provisioner)
        if pi is not None:
            usage[pi] += st.capacity_row(n.instance_type, n.allocatable)
    return usage


def _limit_violation(usage: np.ndarray, limits: np.ndarray) -> np.ndarray:
    """[P] worst usage/limit ratio over FINITE limit resources (1.0 = at
    the limit; the 3.0e38 padding sentinel counts as unlimited)."""
    finite = limits < 1e37
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(finite, usage / np.maximum(limits, 1e-9), 0.0)
    return ratio.max(axis=1) if ratio.size else np.zeros(usage.shape[0])


def price_adjusted(cand_price: np.ndarray, cand_prov: np.ndarray,
                   lam: np.ndarray) -> np.ndarray:
    """Candidate prices under duals ``lam[P]``: multiply by ``exp(lam)`` of
    the owning provisioner, leaving the 3.0e38/inf no-offering sentinels
    alone (a float32 multiply past 1e38 overflows to inf and would change
    the padding the compiled program was built against).  ``cand_price``
    is the solver's ``[C, D]`` per-domain layout (or any array whose
    leading axis is candidates) — the multiplier broadcasts across the
    trailing axes."""
    base = np.asarray(cand_price, dtype=np.float32)
    m = np.exp(lam).astype(np.float32)[np.asarray(cand_prov)]
    m = m.reshape(m.shape + (1,) * (base.ndim - 1))
    with np.errstate(over="ignore"):  # sentinel rows overflow, then drop
        return np.where(base >= 1e37, base, base * m).astype(np.float32)


#: a block tail node below this peak-resource fill is a candidate for the
#: cross-block repack — fuller nodes have nothing left to merge
_TAIL_FILL = 0.9


def _node_fill(n: SimNode) -> float:
    """Peak fill fraction across resources (1.0 = some resource full)."""
    fill = 0.0
    alloc = n.allocatable
    for k, v in n.used().items():
        cap = alloc.get(k, 0.0)
        if cap > 0.0:
            fill = max(fill, v / cap)
    return fill


# ---------------------------------------------------------------------------
# the hierarchical solve
# ---------------------------------------------------------------------------


def _record(registry, path: str) -> None:
    registry.counter(HIER_SOLVES).inc({"path": path})


def solve_hierarchical(
    scheduler,
    pods,
    provisioners,
    instance_types,
    daemonsets=(),
    unavailable=None,
    trace=None,
    registry: Optional[Registry] = None,
    stats: Optional[dict] = None,
) -> Optional[SolveResult]:
    """Partition -> one-dispatch block waves -> price ascent -> repair.
    Returns ``None`` when flat is the right (or only warm) program — the
    scheduler falls through to ``_solve_tpu``; the metrics label says why.
    ``stats``, when given, receives per-stage timings and dispatch counts
    (``tests/test_hierarchy.py`` asserts exactly ONE dispatch per block
    wave).

    Re-entrancy: repair re-seats stragglers through ``scheduler._solve_once``
    — if that inner solve routed hierarchically again (a straggler batch at/
    above ``KT_HIER_THRESHOLD``), repair would recurse without bound.  The
    depth counter pins every nested solve to the flat path
    (``_route_hier`` checks it)."""
    scheduler._hier_depth = getattr(scheduler, "_hier_depth", 0) + 1
    try:
        return _solve_hierarchical(
            scheduler, pods, provisioners, instance_types,
            daemonsets=daemonsets, unavailable=unavailable, trace=trace,
            registry=registry, stats=stats,
        )
    finally:
        scheduler._hier_depth -= 1


def _solve_hierarchical(
    scheduler,
    pods,
    provisioners,
    instance_types,
    daemonsets=(),
    unavailable=None,
    trace=None,
    registry: Optional[Registry] = None,
    stats: Optional[dict] = None,
) -> Optional[SolveResult]:
    t0 = time.perf_counter()
    registry = registry or scheduler.registry
    zero_init_hier_metrics(registry)
    trace = trace or NULL_TRACE
    st_out = stats if stats is not None else {}

    st, tensorize_s = scheduler._tensorize(
        pods, provisioners, instance_types, daemonsets, unavailable,
        trace=trace,
    )
    t_part0 = time.perf_counter()
    comps = coupling_components(st)
    from .tpu import MEGA_MAX_SLOTS, max_mega_slots

    max_blocks = (MEGA_MAX_SLOTS if scheduler.mesh is None
                  else max_mega_slots(scheduler.mesh))
    if len(comps) < 2 or max_blocks < 2:
        _record(registry, "fallback_structure")
        return None
    masks = partition_blocks(st, comps, max_blocks)
    if len(masks) < 2:
        _record(registry, "fallback_structure")
        return None
    budgets = block_budgets(st, masks)
    partition_ms = (time.perf_counter() - t_part0) * 1000.0

    # ---- entries + compile gating --------------------------------------
    t_ent0 = time.perf_counter()
    solver = scheduler._tpu
    mesh = scheduler.mesh
    dims = hier_dims(st, max(budgets))
    slots0 = len(masks)
    sig = hier_signature(st, dims, slots0, mesh)
    entries, base = build_block_entries(
        solver, st, masks, budgets, dims, trace=trace)
    entries_ms = (time.perf_counter() - t_ent0) * 1000.0
    if scheduler.compile_behind and not solver.ready(sig):
        if not solver.warm_pending(sig):
            warm_hier(solver, entries, slots0, sig, mesh=mesh)
        _record(registry, "fallback_cold")
        return None

    # ---- block waves ----------------------------------------------------
    guard = scheduler._guard
    price_budget = hier_price_iters()
    wave_frac = 1.0 / (1.0 + price_budget)
    dispatches = 0
    wave_ms: List[float] = []

    def wave(wave_entries):
        nonlocal dispatches
        tw = time.perf_counter()

        def call():
            pending = solver.solve_many_prepared(
                wave_entries, min_slots=slots0, mesh=mesh,
                registry=registry)
            return pending.results()

        outs = (guard.run_budgeted(call, budget_frac=wave_frac)
                if guard.enabled else call())
        dispatches += 1
        wave_ms.append((time.perf_counter() - tw) * 1000.0)
        for o in outs:
            if isinstance(o, Exception):
                raise o
        return outs

    from .guard import DeviceHang

    P = len(st.prov_names)
    limits = np.asarray(st.prov_limits, dtype=np.float64)
    iters_run = 0
    try:
        outs = wave(entries)

        # ---- price ascent (fixed budget, mirror-descent schedule) ------
        from ..models.tensorize import pack_feasibility, pack_scores
        from .relax import host_feasibility, mirror_eta

        lam = np.zeros(P, dtype=np.float64)
        f_packed: Optional[np.ndarray] = None
        # ktlint: allow[KT020] price waves are sequentially dependent —
        # each dual update needs the PREVIOUS wave's usage; every wave is
        # still ONE vmapped dispatch over all contending blocks
        for t in range(price_budget):
            usage = np.zeros((len(masks), P, st.R), dtype=np.float64)
            for bi, out in enumerate(outs):
                usage[bi] = _prov_usage(st, out.result.nodes, P)
            v = _limit_violation(usage.sum(axis=0), limits)
            hot = v > 1.0 + 1e-6
            if not hot.any():
                break
            iters_run += 1
            eta = float(mirror_eta(np.float32(t)))
            lam = np.minimum(np.where(hot, lam + eta * (v - 1.0),
                                      lam * 0.5), 8.0)
            # adjust the PADDED sentinel tensor (3.0e38 rows stay put —
            # the compiled program's padding contract) and slice the real
            # candidates back out for the kernel
            adj_padded = price_adjusted(base[0]["cand_price"],
                                        base[0]["cand_prov"], lam)
            # packed hot path: which provisioner each group would buy
            # NOW, under the adjusted prices — int8 feasibility, bf16
            # prices (cheapest offering per candidate: min over the
            # domain axis; all-sentinel rows stay >= 1e37), lax or
            # Pallas per KT_PALLAS
            adj = adj_padded[:st.C].min(axis=1)
            if f_packed is None:
                f_packed = pack_feasibility(host_feasibility(st))
            _cost, best = packed_scan_scores(f_packed, pack_scores(adj))
            want_hot = np.zeros(st.G, dtype=bool)
            if st.C:
                prov_of_best = np.asarray(st.cand_prov)[best]
                want_hot = hot[prov_of_best] & (np.asarray(_cost) < 1e37)
            contending = [
                bi for bi in range(len(masks))
                if usage[bi][hot].any() or want_hot[masks[bi]].any()
            ]
            if not contending:
                break
            sub_entries, _ = build_block_entries(
                solver, st, [masks[bi] for bi in contending],
                [budgets[bi] for bi in contending], dims, base=base,
                cand_price=adj_padded, trace=trace,
            )
            sub_outs = wave(sub_entries)
            for bi, out in zip(contending, sub_outs):
                outs[bi] = out
    except DeviceHang:
        logger.warning("hierarchical block wave hit the hang guard; "
                       "flat degradation ladder serves this batch")
        _record(registry, "fallback_degraded")
        return None
    except Exception:
        logger.warning("hierarchical wave failed; falling back to flat",
                       exc_info=True)
        _record(registry, "fallback_degraded")
        return None

    # ---- merge ----------------------------------------------------------
    t_rep0 = time.perf_counter()
    member_names: List[set] = []
    for mask in masks:
        names = set()
        for gi in np.nonzero(mask)[0]:
            names.update(p.name for p in st.groups[gi].pods)
        member_names.append(names)

    nodes: List[SimNode] = []
    assignments: Dict[str, str] = {}
    straggler_names: set = set()
    block_of: Dict[str, int] = {}  # node name -> owning block
    for bi, out in enumerate(outs):
        res = out.result
        members = member_names[bi]
        nodes.extend(res.nodes)
        for n in res.nodes:
            block_of[n.name] = bi
        for pn, nn in res.assignments.items():
            if pn in members:
                assignments[pn] = nn
        # a block's extract marks every pod of every MASKED-OUT group
        # infeasible (zero counts -> zero takes); only member infeasibility
        # is real
        straggler_names.update(pn for pn in res.infeasible if pn in members)

    # ---- exact limit enforcement + warm-start repair --------------------
    usage_all = _prov_usage(st, nodes, P)
    v = _limit_violation(usage_all, limits)
    evicted: List[SimNode] = []
    for pi in np.nonzero(v > 1.0 + 1e-6)[0]:
        prov = st.prov_names[pi]
        mine = sorted((n for n in nodes if n.provisioner == prov),
                      key=lambda n: (-n.price, n.name))
        for n in mine:
            if _limit_violation(usage_all[pi:pi + 1],
                                limits[pi:pi + 1])[0] <= 1.0 + 1e-6:
                break
            usage_all[pi] -= st.capacity_row(n.instance_type, n.allocatable)
            evicted.append(n)
    if evicted:
        gone = {id(n) for n in evicted}
        nodes = [n for n in nodes if id(n) not in gone]
        for n in evicted:
            straggler_names.update(p.name for p in n.pods)
        assignments = {pn: nn for pn, nn in assignments.items()
                       if pn not in straggler_names}

    pods_by_name = {p.name: p for p in pods}
    stragglers = [pods_by_name[pn] for pn in sorted(straggler_names)
                  if pn in pods_by_name]
    n_repair = len(stragglers)
    infeasible: Dict[str, str] = {}

    def _repair_solve(rp, existing, unav):
        return scheduler._solve_once(
            list(rp), provisioners, instance_types, list(existing),
            daemonsets, unav, True, None, trace=trace,
        )

    if stragglers:
        from .warmstart import delta_solve

        merged = SolveResult(nodes=nodes, assignments=assignments,
                             infeasible={}, existing_nodes=[])

        outcome = delta_solve(
            merged, added=stragglers,
            solve_displaced=_repair_solve, solve_full=_repair_solve,
            registry=registry, unavailable=unavailable,
        )
        repaired = outcome.result
        nodes = list(repaired.existing_nodes) + list(repaired.nodes)
        assignments = dict(repaired.assignments)
        infeasible = dict(repaired.infeasible)

    # ---- cross-block tail consolidation ---------------------------------
    # every block rounds its own tail up to a whole node — with B blocks
    # the merged solution can carry up to B underfull tails that the flat
    # program would have shared.  Evict each block's least-filled node
    # (under _TAIL_FILL peak fill), re-seat those pods jointly through the
    # same warm-start path, and ship the cheaper of before/after — the
    # select makes this pass never-worse.  delta_solve mutates its inputs,
    # so the candidate runs against copies of the kept nodes.
    n_tail = 0
    if len(masks) > 1 and nodes:
        tails: List[SimNode] = []
        by_block: Dict[int, List[SimNode]] = {}
        for n in nodes:
            bi = block_of.get(n.name)
            if bi is not None and n.pods:
                by_block.setdefault(bi, []).append(n)
        for mine in by_block.values():
            cand = min(mine, key=_node_fill)
            if _node_fill(cand) < _TAIL_FILL:
                tails.append(cand)
        # only tails that could actually co-reside merge: a tail whose
        # zone no OTHER block's tail shares has nothing to merge with —
        # evicting it would let the repair repack a single block's answer
        # and break byte-parity on fully block-disjoint batches (the
        # ISSUE gate: disjoint blocks must ship flat's exact placement)
        zone_counts: Dict[str, int] = {}
        for n in tails:
            zone_counts[n.zone] = zone_counts.get(n.zone, 0) + 1
        tails = [n for n in tails if zone_counts[n.zone] > 1]
        tail_pods = [pods_by_name[p.name] for n in tails for p in n.pods
                     if p.name in pods_by_name]
        if len(tails) > 1 and tail_pods:
            from dataclasses import replace

            from .warmstart import delta_solve

            gone = {n.name for n in tails}
            kept = [replace(n, pods=list(n.pods),
                            allocatable=dict(n.allocatable))
                    for n in nodes if n.name not in gone]
            alt = SolveResult(
                nodes=kept,
                assignments={pn: nn for pn, nn in assignments.items()
                             if nn not in gone},
                infeasible={}, existing_nodes=[])
            outcome = delta_solve(
                alt, added=tail_pods,
                solve_displaced=_repair_solve, solve_full=_repair_solve,
                registry=registry, unavailable=unavailable,
            )
            r2 = outcome.result
            nodes2 = list(r2.existing_nodes) + list(r2.nodes)
            if (not r2.infeasible
                    and sum(n.price for n in nodes2)
                    < sum(n.price for n in nodes) - 1e-9):
                n_tail = len(tail_pods)
                nodes = nodes2
                assignments = dict(r2.assignments)
    repair_ms = (time.perf_counter() - t_rep0) * 1000.0

    elapsed_ms = (time.perf_counter() - t0) * 1000.0
    registry.histogram(HIER_BLOCKS).observe(float(len(masks)))
    registry.histogram(HIER_PRICE_ITERATIONS).observe(float(iters_run))
    registry.histogram(HIER_REPAIR_PODS).observe(float(n_repair))
    registry.histogram(HIER_DURATION).observe(elapsed_ms / 1000.0)
    _record(registry, "hierarchical")
    trace.annotate(hier_blocks=len(masks), hier_price_iters=iters_run,
                   hier_repair_pods=n_repair)
    st_out.update(
        blocks=len(masks), components=len(comps), waves=1 + iters_run,
        price_iters=iters_run, dispatches=dispatches,
        repair_pods=n_repair, tail_repack_pods=n_tail,
        partition_ms=round(partition_ms, 3),
        entries_ms=round(entries_ms, 3),
        wave_ms=[round(w, 2) for w in wave_ms],
        repair_ms=round(repair_ms, 2), total_ms=round(elapsed_ms, 2),
        n_pods=len(pods),
    )
    logger.info(
        "hierarchical solve: %d pods, %d components -> %d blocks, "
        "%d price wave(s), %d repaired, %.1f ms",
        len(pods), len(comps), len(masks), iters_run, n_repair, elapsed_ms,
    )
    return SolveResult(
        nodes=nodes, assignments=assignments, infeasible=infeasible,
        existing_nodes=[], solve_ms=elapsed_ms,
        tensorize_ms=tensorize_s * 1000.0,
    )


# ---------------------------------------------------------------------------
# dev-host scale model
# ---------------------------------------------------------------------------


def scale_model(measured: dict, n_pods: int) -> dict:
    """Project the hierarchical wall at ``n_pods`` from one measured run —
    pure host math (no jax), used by ``scripts/profile_solve.py --hier``
    and ``scripts/hier_demo.py``.

    Stage scaling: partition/entry build and repair are host-linear in the
    pod count; a block wave is ONE vmapped dispatch whose per-slot scan
    state is the block's share ``n_pods / blocks`` (slots run data-parallel
    on device), so device wave time scales with the BLOCK size, not the
    batch — that is the whole decomposition dividend.  The device per-pod
    rate is ``measured['device_per_pod_us']``, which only a run on the chip
    can supply: without it ``wave_ms`` and ``total_ms`` are the string
    ``NOT_MEASURED`` and only the host stages carry numbers."""
    n0 = max(1, int(measured.get("n_pods", 1)))
    blocks = max(1, int(measured.get("blocks", 1)))
    waves = max(1, int(measured.get("waves", 1)))
    s = n_pods / n0
    host_ms = (float(measured.get("partition_ms", 0.0))
               + float(measured.get("entries_ms", 0.0))) * s
    repair_ms = float(measured.get("repair_ms", 0.0)) * s
    out = {
        "n_pods": int(n_pods), "blocks": blocks, "waves": waves,
        "host_ms": round(host_ms, 2), "wave_ms": NOT_MEASURED,
        "repair_ms": round(repair_ms, 2), "total_ms": NOT_MEASURED,
    }
    per_pod_us = measured.get("device_per_pod_us")
    if per_pod_us:
        dispatch_ms = float(measured.get("dispatch_overhead_ms", 2.0))
        wave_ms = float(per_pod_us) * (n_pods / blocks) / 1000.0 + dispatch_ms
        out["wave_ms"] = round(wave_ms, 2)
        out["total_ms"] = round(host_ms + waves * wave_ms + repair_ms, 2)
    return out
