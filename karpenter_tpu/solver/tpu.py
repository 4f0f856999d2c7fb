"""TPU batch solver — vectorized FFD bin-packing as a jitted JAX program.

This is the component BASELINE.json's north star names: karpenter-core's
``scheduling.Solve`` first-fit-decreasing loop (SURVEY.md §3.2 step 3)
re-expressed as dense tensor math so 50k pods x the full catalog solve in
milliseconds on a TPU.

Design (tpu-first, not a port of the Go loop):

- **Feasibility is tensor algebra.**  ``F[g, c] = label_ok & fit_ok & prov_ok``
  computed by packed-bitmask gathers (models/vocab.py) and broadcast resource
  compares; zone/capacity-type feasibility joins per-domain:
  ``Fd[c, d] = F[g, c] & avail[c, d] & zone_ok[g, d] & ct_ok[g, d]``.
- **The pack is a scan over pod *groups*, not pods.**  Identical pods (same
  constraints+requests) collapse into one scan step; within a step every
  placement decision is closed-form vector math over node slots:
  first-fit = prefix-sum allocation in slot-creation order
  (ops/masks.prefix_allocate), topology spread = integer water-fill over
  zones (ops/masks.water_fill), new-node selection = lexicographic argmin
  over (candidate x domain) score tensors.  No data-dependent Python control
  flow — one traced step, in one device loop over the groups that stops
  after the last group that has pods (:func:`_scan_groups`).
- **Node state is slot-per-node.**  Preallocated arrays of NR node slots
  (existing nodes first, then creation order), so "first fit in creation
  order" is literally array order.

Known v1 semantic gaps vs the CPU oracle (solver/reference.py), accepted
within the 1.02x cost-parity budget and flagged for later rounds:
- positive pod-affinity IS solved on-device (per-group modes: co-locate with
  existing matches / seed one zone-or-node / infeasible), but only one
  positive term per topology key and only zone/hostname keys; other shapes
  are marked by tensorize and routed to the oracle by the scheduler,
- maxSkew > 1 spread is allocated by the skew-band fill (free-row-preferring
  banded leveling) instead of strict first-fit-within-band,
- in-step provisioner-limit fallback depth is 2 (bulk, tail) creation rounds
  per zone pass = 4 candidate picks; residue a deeper cascade would strand
  is re-solved by the scheduler's host-side residue-convergence waves
  (solver/scheduler.py MAX_RESIDUE_WAVES) against the accumulated state,
  matching the oracle's unbounded invalidate-and-retry.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as faults_mod
from ..models import labels as L
from ..models.tensorize import NO_SELECTOR, SolveTensors
from ..metrics import (
    COALESCE,
    COALESCE_WHAT,
    SCAN_AXES,
    SCAN_AXIS,
    SCAN_SLOT_RETRIES,
    Registry,
    registry as default_registry,
)
from ..obs.trace import NULL_TRACE
from ..utils.clock import Clock
from ..ops.masks import (
    BIG,
    gather_pm_bits,
    lex_argmin,
    prefix_allocate,
    skew_band_fill,
    water_fill,
)
from .types import SimNode, SolveResult

# host-side on purpose (see ops/masks.py BIG): no device init at import time
BIGN = np.float32(1e9)  # "unbounded" node/pod counts

#: where compiled programs persist when nothing outside the process says
#: otherwise: a FIXED directory inside the checkout.  The directory is part
#: of jax's cache key, so a path that moves (a temp name, a pid, the time)
#: never hits twice.  For an installed package this is beside site-packages,
#: which an image keeps read-only: jax then warns on every compile and runs
#: uncached (checked on jax 0.9.0), so both deploy manifests export
#: ``JAX_COMPILATION_CACHE_DIR`` at a writable mount instead.
DEFAULT_JIT_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_cache")


def jit_cache_dir() -> str:
    """The directory the persistent compile cache resolves to — the ONE
    resolution: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
    (deploy/solver.yaml exports it at the cache mount; JAX reads it itself),
    else :data:`DEFAULT_JIT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_JIT_CACHE_DIR


def jit_cache_entries() -> int:
    """Compiled programs currently persisted under :func:`jit_cache_dir`."""
    try:
        return sum(1 for name in os.listdir(jit_cache_dir())
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


def _init_jit_cache() -> None:
    """Turn on JAX's persistent (on-disk) compilation cache at solver init:
    every process that builds a solver — serve replicas, the operator's
    in-process tier, bench children — shares compiled XLA programs through
    :func:`jit_cache_dir`, so a restarted or scaled-out replica loads the
    solver compiles from disk instead of re-paying them.  Where the
    environment placed the cache, JAX has already taken the directory and
    none is set here.  Idempotent."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JIT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def _rung(n: int, quantum: int, linear_max: int, ratio: float = 1.5,
          axis_div: int = 1) -> int:
    """Bucket ``n`` up to a small, stable rung ladder: linear multiples of
    ``quantum`` up to ``linear_max``, then a geometric x``ratio`` ladder
    (each rung rounded to the quantum).  Linear quanta keep padding waste
    near zero for the common small shapes; the geometric tail bounds the
    TOTAL number of distinct rungs (≈ log-many), so a growing cluster stops
    triggering a fresh XLA compile every ``quantum`` of growth — the compile
    ladder becomes warmable.  ``axis_div`` keeps the rung divisible for mesh
    sharding.  A rung is the SHAPE of a tensor axis and so costs bytes; on
    the group axis it is not the scan's trip count (:func:`_scan_groups`
    stops after the last group that has pods)."""
    q = max(quantum, axis_div)
    q = ((q + axis_div - 1) // axis_div) * axis_div

    def up(m: int) -> int:
        out = ((m + q - 1) // q) * q
        return max(out, axis_div)

    if n <= linear_max:
        return up(n)
    rung = up(linear_max)
    while rung < n:
        rung = up(int(rung * ratio))
    return rung


def _mesh_divs(mesh) -> Tuple[int, int]:
    if mesh is None:
        return 1, 1
    from ..parallel.mesh import POD_AXIS, TYPE_AXIS

    return mesh.shape[POD_AXIS], mesh.shape[TYPE_AXIS]


def _nr_estimate(st: SolveTensors, NE: int, node_budget: int) -> int:
    """Optimistic-but-padded node-slot count for the scan's NR axis.

    The worst-case budget (one node per pod) makes the per-step state
    enormous — a 50k-pod solve would carry res[55k, R] + selcnt[55k, S]
    through every scan step when it ends up creating ~558 nodes; the
    [NR]-axis traffic, not arithmetic, then dominates device time.
    Estimate instead: per group, the node count if
    packing hit the best resource-only pods-per-node any candidate offers,
    summed, doubled (zone splits/interleave slack), plus slack.  Hostname
    caps are deliberately ignored (capped groups share rows with other
    groups); when the estimate is genuinely short the solve detects slot
    exhaustion and retries once at the full budget (TpuSolver.solve),
    counted in ``karpenter_solver_scan_slot_retries_total``.

    Rounded up per GROUP on purpose, also where most groups are a handful
    of pods: what has to fit is the slots the SCAN opens, not the nodes
    the answer keeps after ``coalesce``.  A step seats its group on the
    room earlier groups left and buys right-sized nodes for the rest, so a
    long-tailed batch opens about one node per tiny group (1,640
    deployments of 250/30/5 pods: 2,880-2,930 slots in use when the scan
    ends, 256-1,250 nodes in the answer; this estimate 3,470, rung 4,608).
    Pooling groups of equal requests before rounding up gives 506-520
    there, and every such request then runs twice, the second time at the
    full budget (``karpenter_solver_scan_axis_total`` shows both)."""
    if node_budget <= 2048:  # min rung: estimate can't help
        return node_budget
    # memoized on the tensors: solve()/signature()/prepare each consult the
    # dims several times per solve, and the [G, C, R] broadcast below is the
    # only non-trivial part
    cache = getattr(st, "_nr_est_cache", None)
    key = (NE, node_budget)
    if cache is not None and cache[0] == key:
        return cache[1]
    req = np.asarray(st.requests, dtype=np.float32)      # [G, R]
    alloc = np.asarray(st.cand_alloc, dtype=np.float32)  # [C, R]
    if alloc.shape[0] == 0 or req.shape[0] == 0:
        return node_budget
    with np.errstate(divide="ignore", invalid="ignore"):
        ratios = np.floor(alloc[None, :, :] / np.maximum(req[:, None, :], 1e-9))
    ratios = np.where(req[:, None, :] > 1e-12, ratios, np.inf)  # [G, C, R]
    ppn = ratios.min(axis=2)                                    # [G, C]
    best = np.maximum(ppn.max(axis=1), 1.0)                     # [G]
    best = np.where(np.isfinite(best), best, 1.0)
    nodes = np.ceil(np.asarray(st.counts, dtype=np.float64) / best)
    est = NE + int(2.0 * nodes.sum()) + 128
    out = int(min(max(est, 1), node_budget))
    st._nr_est_cache = (key, out)
    return out


def solve_dims(st: SolveTensors, *, NE: int, node_budget: int,
               a: int = 1, b: int = 1, track: bool = True,
               full_nr: bool = False) -> dict:
    """The padded tensor dimensions (and thus the XLA compile signature) for
    a solve of ``st`` against ``NE`` existing nodes with ``node_budget`` max
    node slots.  The SINGLE source of the bucketing math: ``prepare`` pads to
    these dims and ``TpuSolver.signature`` keys compile-readiness on them, so
    the two can never drift.  ``full_nr`` forces the worst-case NR axis (the
    slot-exhaustion retry path)."""
    G_pad = _rung(st.G, 16, 128, axis_div=a)
    C_pad = _rung(max(1, st.C), 64, 512, axis_div=b)
    nr_slots = node_budget if full_nr else _nr_estimate(st, NE, node_budget)
    NR = _rung(max(1, nr_slots), 512, 2048, axis_div=a)
    NE_pad = _rung(max(1, NE), 16, 64)
    S_pad = _rung(st.S, 8, 32) if st.S else 0
    P_pad = _rung(max(1, len(st.prov_names)), 4, 8)
    K, W = st.pm.shape[1], st.pm.shape[2]
    return dict(
        G=G_pad, C=C_pad, NR=NR, NE_pad=NE_pad, S=S_pad, P=P_pad,
        D=st.D, R=st.R, Z=max(1, st.n_zones), K=K, W=W,
        track=bool(track), a=a, b=b,
    )


def _dims_key(dims: dict) -> tuple:
    return tuple(sorted(dims.items()))


# ---------------------------------------------------------------------------
# feasibility precompute
# ---------------------------------------------------------------------------


def compute_feasibility(
    pm: jnp.ndarray,          # [G, K, W] uint32
    requests: jnp.ndarray,    # [G, R]
    gp_ok: jnp.ndarray,       # [G, P]
    cand_vw: jnp.ndarray,     # [C, K]
    cand_vb: jnp.ndarray,     # [C, K]
    cand_alloc: jnp.ndarray,  # [C, R]
    cand_prov: jnp.ndarray,   # [C]
    key_check: jnp.ndarray,   # [K]
    dom_vw: jnp.ndarray,      # [D, 2]
    dom_vb: jnp.ndarray,      # [D, 2]
    zone_key: int,
    ct_key: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (F[G, C] candidate feasibility, dom_ok[G, D] zone&ct allowed)."""
    from ..ops.feasibility import MATMUL_MIN_G, candidate_selector, label_feasibility_matmul

    G = pm.shape[0]

    def fit_group(req_g):
        return jnp.all(
            (req_g[None, :] <= cand_alloc + 1e-6) | (req_g[None, :] <= 0), axis=1
        )

    if G >= MATMUL_MIN_G:
        # heterogeneous-pod shapes: one bf16 MXU contraction over the value
        # vocabulary replaces G x C x K gathers (ops/feasibility.py)
        sel = candidate_selector(cand_vw, cand_vb, key_check, pm.shape[2])
        lab = label_feasibility_matmul(pm, sel, key_check)
        fit = jax.vmap(fit_group)(requests)
        F = lab & fit
    else:
        def one_group(args):
            pm_g, req_g = args
            bits = gather_pm_bits(pm_g, cand_vw, cand_vb)      # [C, K]
            lab = jnp.all(bits | ~key_check[None, :], axis=1)  # [C]
            return lab & fit_group(req_g)

        # chunked vmap bounds the materialized [chunk, C, K] gather intermediate
        outs = []
        for i in range(0, G, 512):
            outs.append(jax.vmap(one_group)((pm[i : i + 512], requests[i : i + 512])))
        F = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    F = F & gp_ok[jnp.arange(G)[:, None], cand_prov[None, :]]

    # domain allowance from the zone / capacity-type keys of each group's mask
    def dom_one(pm_g):
        zw = pm_g[zone_key][dom_vw[:, 0]]
        zok = ((zw >> dom_vb[:, 0].astype(jnp.uint32)) & jnp.uint32(1)).astype(bool)
        cw = pm_g[ct_key][dom_vw[:, 1]]
        cok = ((cw >> dom_vb[:, 1].astype(jnp.uint32)) & jnp.uint32(1)).astype(bool)
        return zok & cok

    dom_ok = jax.vmap(dom_one)(pm)
    return F, dom_ok


# Module-level jitted feasibility.  The wrapper is created ONCE: a per-call
# ``jax.jit(compute_feasibility)`` owns a fresh compile cache and silently
# recompiles on every solve (the KT008 class); here the cache persists and
# the bucketed input shapes keep the compile count log-bounded.  The zone/ct
# key ids are static so the traced program indexes with constants, exactly
# like the eager path.
feasibility_jit = partial(jax.jit, static_argnames=("zone_key", "ct_key"))(
    compute_feasibility
)


# ---------------------------------------------------------------------------
# the scan step
# ---------------------------------------------------------------------------


def _make_step(
    consts: dict,
    NR: int,
    Z: int,
    track: bool,
):
    """Build the per-group scan step closure over constant tensors."""
    counts = consts["counts"]          # [G]
    suffix_res = consts["suffix_res"]  # [G, Z, R] later-group demand per zone
    suffix_cnt = consts["suffix_cnt"]  # [G, Z] later-group pod count per zone
    requests = consts["requests"]      # [G, R]
    F = consts["F"]                    # [G, C]
    dom_ok = consts["dom_ok"]          # [G, D]
    g_zone_spread = consts["g_zone_spread"]
    g_zone_skew = consts["g_zone_skew"]
    g_host_spread = consts["g_host_spread"]
    g_host_cap = consts["g_host_cap"]
    g_zone_anti = consts["g_zone_anti"]
    g_zone_paff = consts["g_zone_paff"]
    g_host_paff = consts["g_host_paff"]
    g_sel_match = consts["g_sel_match"]  # [S, G]
    cand_alloc = consts["cand_alloc"]  # [C, R]
    cand_cap = consts["cand_cap"]      # [C, R]
    cand_prov = consts["cand_prov"]    # [C]
    cand_price = consts["cand_price"]  # [C, D]
    cand_avail = consts["cand_avail"]  # [C, D]
    prov_limits = consts["prov_limits"]  # [P, R]
    dom_zone = consts["dom_zone"]      # [D]
    ex_ok = consts["ex_ok"]            # [G, NE_pad] existing-node label/taint compat
    node_budget = consts["node_budget"]  # [] int32 — semantic max_nodes cap
    # NR is bucketed up for jit-shape stability; node_budget carries the
    # caller's real max_nodes so the budget survives the padding.

    C, D = cand_price.shape
    NE_pad = ex_ok.shape[1]
    slot_idx = jnp.arange(NR, dtype=jnp.int32)

    def step(carry, g):
        (res, row_zone, row_dom, row_cand, row_price, selcnt, active,
         n_used, zc, tot, prov_used, infeasible) = carry

        req_g = requests[g]                      # [R]
        cnt = counts[g].astype(jnp.float32)
        Fg = F[g]                                # [C]
        dok = dom_ok[g]                          # [D]
        Fd_g = (Fg[:, None] & cand_avail & dok[None, :])  # [C, D]

        # ---- per-slot feasibility & capacity --------------------------
        safe_cand = jnp.maximum(row_cand, 0)
        safe_dom = jnp.maximum(row_dom, 0)
        rf_cand = Fd_g[safe_cand, safe_dom]
        # slots >= NE_pad always have row_cand >= 0 (solver-created), so the
        # clamped gather below never feeds a wrong ex_ok value into rf
        exv = ex_ok[g][jnp.minimum(slot_idx, NE_pad - 1)]
        rf = active & jnp.where(row_cand >= 0, rf_cand, exv)

        # ---- positive pod-affinity modes (reference.py _zone_allowed /
        # _host_cap / _new_node_host_cap semantics, per group):
        #   A: matching pods exist -> co-locate (their zones / their nodes,
        #      no fresh hostname domain),
        #   B: none exist, group self-matches -> seed ONE zone / ONE node,
        #   C: none exist, no self-match -> infeasible.
        zpa = g_zone_paff[g]
        zpa_on = zpa >= 0
        zpa_i = jnp.maximum(zpa, 0)
        ztot = tot[zpa_i] > 0
        zself = g_sel_match[zpa_i, g]
        zone_seed = zpa_on & ~ztot & zself
        zdead = zpa_on & ~ztot & ~zself

        hpa = g_host_paff[g]
        hpa_on = hpa >= 0
        hpa_i = jnp.maximum(hpa, 0)
        htot = tot[hpa_i] > 0
        hhave = selcnt[:, hpa_i] > 0
        hself = g_sel_match[hpa_i, g]
        host_seed = hpa_on & ~htot & hself
        host_gated = hpa_on & htot
        hdead = hpa_on & ~htot & ~hself

        rf = rf & (~host_gated | hhave) & ~hdead & ~zdead
        # an empty node never satisfies mode-A/C hostname affinity
        new_allowed = ~host_gated & ~hdead & ~zdead

        # step-entry PER-ZONE net-backfill state for pick(): how much of the
        # later-group demand committed to each zone the FREE capacity on that
        # zone's open rows absorbs, in units of the zone's average later-pod
        # request vector.  Per-zone on both sides (fuzz seed 14): a huge free
        # row in zone c must not cancel the backfill credit of zones a/b,
        # whose committed spread-group share can only land on nodes bought
        # THERE.  Hoisted here — it depends only on the step-entry carry
        # (pick() closes over this `res`, not the threaded creation state),
        # and the [NR, R] reduction is the most memory-heavy scoring term.
        # zero-guard only (not a floor): the even zone split makes per-zone
        # counts FRACTIONAL, and flooring a 1/3-pod count at 1 would shrink
        # the average request (and the net fraction below) threefold
        cnt_z_safe = jnp.where(suffix_cnt[g] > 0, suffix_cnt[g], 1.0)    # [Z]
        avg_req_z = suffix_res[g] / cnt_z_safe[:, None]                  # [Z, R]
        row_avg = avg_req_z[jnp.maximum(row_zone, 0)]                   # [NR, R]
        per_row_absorb = jnp.min(jnp.where(
            row_avg > 0,
            jnp.maximum(res, 0.0) / jnp.maximum(row_avg, 1e-9),
            BIGN,
        ), axis=1)                                                      # [NR]
        rows_absorb_z = jnp.zeros(Z, dtype=jnp.float32).at[
            jnp.maximum(row_zone, 0)
        ].add(jnp.where(active, per_row_absorb, 0.0))                   # [Z]
        net_backfill_frac_z = jnp.clip(
            (suffix_cnt[g] - rows_absorb_z) / cnt_z_safe,
            0.0, 1.0,
        )                                                               # [Z]
        # later-group demand convertible into THIS group's pod-equivalents,
        # per zone (hoisted from pick(): depends only on g)
        backfill_eq_z = jnp.min(jnp.where(
            req_g[None, :] > 0,
            suffix_res[g] / jnp.maximum(req_g[None, :], 1e-9),
            BIGN,
        ), axis=1)                                                      # [Z]

        ratios = jnp.where(req_g[None, :] > 0, jnp.floor((res + 1e-6) / jnp.maximum(req_g[None, :], 1e-9)), BIGN)
        cap = jnp.min(ratios, axis=1)            # [NR]

        sh = g_host_spread[g]
        hk = g_host_cap[g].astype(jnp.float32)
        selrow = selcnt[:, jnp.maximum(sh, 0)].astype(jnp.float32)
        hcap = jnp.where(hk > 0, hk - selrow, jnp.where(selrow > 0, 0.0, BIGN))
        cap = jnp.where(sh >= 0, jnp.minimum(cap, hcap), cap)
        cap = jnp.maximum(cap, 0.0) * rf

        # ---- zone-level caps ------------------------------------------
        zsp = g_zone_spread[g]
        za = g_zone_anti[g]
        zoned = (zsp >= 0) | (za >= 0) | zpa_on

        # eligible zones: any allowed domain in the zone
        el = jnp.zeros(Z, dtype=bool).at[dom_zone].max(dok)
        # zone positive affinity, modes A and C
        zcpa = zc[zpa_i] > 0                                        # [Z]
        el = el & (~(zpa_on & ztot) | zcpa) & ~zdead
        # zone anti-affinity cap
        zc_an = zc[jnp.maximum(za, 0)].astype(jnp.float32)          # [Z]
        self_match = g_sel_match[jnp.maximum(za, 0), g]
        anti_cap = jnp.where(
            self_match, jnp.maximum(1.0 - zc_an, 0.0),
            jnp.where(zc_an > 0, 0.0, BIGN),
        )
        anti_cap = jnp.where(za >= 0, anti_cap, BIGN)               # [Z]

        rowcap_z = jnp.zeros(Z, dtype=jnp.float32).at[jnp.maximum(row_zone, 0)].add(
            jnp.where(active, cap, 0.0)
        )

        # per-zone budget from zone anti-affinity + zone-spread headroom
        # (oracle _zone_allowed: counts[z] + 1 - min_eligible <= maxSkew);
        # the seed flows must honor it — the normal flow gets it via cap_z
        zc_sp = jnp.where(zsp >= 0, zc[jnp.maximum(zsp, 0)], jnp.zeros(Z, jnp.int32)).astype(jnp.float32)
        min_sp = jnp.min(jnp.where(el, zc_sp, BIGN))
        spread_cap = jnp.where(
            zsp >= 0, g_zone_skew[g].astype(jnp.float32) + min_sp - zc_sp, BIGN
        )
        zone_budget = jnp.minimum(anti_cap, jnp.maximum(spread_cap, 0.0))   # [Z]

        # ---- new-node candidate scoring --------------------------------
        nr_ratios = jnp.where(
            req_g[None, :] > 0,
            jnp.floor((cand_alloc + 1e-6) / jnp.maximum(req_g[None, :], 1e-9)),
            BIGN,
        )
        # ppn is the resource-only pods-per-node; take_pn is what THIS group
        # actually places per node (hostname caps applied).  Scoring uses a
        # backfill-aware blend of the two — see pick().
        ppn = jnp.min(nr_ratios, axis=1)                            # [C]
        hcap_new = jnp.where((sh >= 0) & (hk > 0), hk, BIGN)
        take_pn = jnp.minimum(ppn, hcap_new)
        lim_ok = jnp.all(
            prov_used[cand_prov] + cand_cap <= prov_limits[cand_prov] + 1e-6, axis=1
        )                                                            # [C]
        new_ok = (Fd_g & (take_pn[:, None] >= 1.0) & lim_ok[:, None]
                  & new_allowed)                                     # [C, D]
        zone_of_dom = dom_zone                                       # [D]

        # ---- candidate pick (used by creation AND the zone-seed choice) --
        # Mirrors the oracle: argmin price / min(ppn, remaining); nodes of the
        # chosen type are created in bulk while remaining >= ppn, then the
        # tail re-scores once with the smaller remainder.
        ci_key = jnp.broadcast_to(jnp.arange(C, dtype=jnp.float32)[:, None], (C, D))
        di_key = jnp.broadcast_to(jnp.arange(D, dtype=jnp.float32)[None, :], (C, D))
        new_ok_nolim = Fd_g & (take_pn[:, None] >= 1.0) & new_allowed

        def _lim_ok_cur(prov_used_cur):
            return jnp.all(
                prov_used_cur[cand_prov] + cand_cap <= prov_limits[cand_prov] + 1e-6,
                axis=1,
            )

        def pick(rem, dom_mask, prov_used_cur, tail_rem=None,
                 size_tiebreak=True, pool_rem=None):
            """argmin over (C, D & dom_mask) of price / min(fill, rem),
            where fill = min(ppn, take_pn + later-group demand committed to
            the candidate domain's ZONE) — the backfill-aware effective
            pods-per-node (see comment below).

            Limit feasibility is recomputed from the *current* provisioner
            usage so once a limit binds mid-group the next pick falls back to
            the next-best candidate (mirroring the oracle's invalidate-and-
            retry at reference.py _create_node)."""
            ok_cd = new_ok_nolim & _lim_ok_cur(prov_used_cur)[:, None] & dom_mask[None, :]
            # Effective fill for scoring: this group fills take_pn per node
            # (hostname caps included); slack beyond that is only worth
            # paying for when LATER groups exist to backfill it IN THIS
            # ZONE.  The oracle scores resource-only ppn because its
            # sequential interleave always has backfill in flight; here the
            # later-group RESOURCE demand committed to the candidate's zone
            # (converted to this-group pod equivalents, backfill_eq_z) makes
            # that optimism explicit and zone-local — a hostname-capped
            # group solved last buys right-sized nodes instead of betting on
            # backfill that never comes (fuzz seeds 14/20), while capped
            # groups with real later demand still buy big co-location nodes
            # (bench c3).
            # The zone's backfill pool is shared across every node this
            # group will create there: per-node slack is only worth what the
            # pool can deliver to ONE node.  The node-count estimate is
            # pool_rem/take_pn (the creation remainder this pick serves —
            # the zone's share under zoned creation) CLAMPED by how many
            # nodes the provisioner limit can still fund — when the limit
            # tail binds (one node left), the whole pool concentrates on it,
            # and a roomier type is worth its price premium (the sequential
            # oracle gets this for free: its tail placement sees every
            # group's residual at once; fuzz seed 27).
            head_nodes = jnp.min(
                jnp.floor(
                    (prov_limits[cand_prov] - prov_used_cur[cand_prov] + 1e-6)
                    / jnp.maximum(cand_cap, 1e-9)
                ),
                axis=1,
            )                                                        # [C]
            est_rem = rem if pool_rem is None else pool_rem
            n_nodes_est = jnp.clip(
                jnp.minimum(est_rem / jnp.maximum(take_pn, 1.0),
                            jnp.clip(head_nodes, 0.0, BIGN)),
                1.0, BIGN,
            )                                                        # [C]
            per_node_backfill = (
                backfill_eq_z[dom_zone][None, :] / n_nodes_est[:, None]
            )                                                        # [C, D]
            fill = jnp.minimum(ppn[:, None], take_pn[:, None] + per_node_backfill)
            denom = jnp.maximum(jnp.minimum(fill, jnp.maximum(rem, 1.0)), 1.0)
            pnb_net = per_node_backfill * net_backfill_frac_z[dom_zone][None, :]
            if tail_rem is not None:
                # TAIL purchases are the oracle's last-pods-standing buys:
                # cap the utilization estimate additionally by the zone's
                # own tail count plus only the NET backfill — the zone-
                # committed later-group demand minus what the free capacity
                # on THAT ZONE's open rows absorbs first (later groups
                # first-fit free rows, so gross suffix demand over-credits a
                # tail node — fuzz seed 14's 8x node for a 2-pod tail; but
                # when the zone's rows are full or a limit squeezes later
                # demand onto this very node, the credit is real — fuzz
                # seed 27's 2-cpu tail).  Rows absorb in units of their
                # zone's average later-pod request vector (resource-coupled:
                # free memory with no free cpu absorbs nothing).
                denom = jnp.maximum(
                    jnp.minimum(
                        denom, jnp.maximum(tail_rem, 1.0) + pnb_net
                    ),
                    1.0,
                )
            score = jnp.where(ok_cd, cand_price / denom, BIG)
            # tie-break at exactly equal $/pod: prefer the LARGER candidate,
            # but only when this group's own remainder fills it completely
            # (take_pn <= rem) — then the $ outcome is identical by
            # construction and the cluster gets fewer, larger nodes (less
            # kubelet/API/image-pull/ENI load at the same price).
            # Partially-fillable candidates never win the tie: their equal
            # score rests on backfill estimates, not on guaranteed $ — even
            # the per-zone projected credit must not upsize a tie, because
            # when a provisioner limit binds, a node bought "for backfill"
            # spends limit headroom later zones of THIS group still need
            # (fuzz seed 27: a 16x tail node starves zone c below its skew
            # band).  Cross-group tail fragmentation is handled after
            # extraction by cost-neutral coalescing (solver/coalesce.py),
            # not by upsizing picks here.  For TAIL picks the guard compares
            # against the zone's own tail count (tail_rem), not the
            # group-wide scoring remainder.  The host-seed flow opts out
            # entirely (size_tiebreak=False): it buys exactly ONE node
            # either way, so a larger type is strictly more $.
            guard_rem = (
                jnp.broadcast_to(jnp.maximum(rem, 1.0), (C, D))
                if tail_rem is None
                else jnp.broadcast_to(jnp.maximum(tail_rem, 1.0), (C, D))
            )
            full_take = jnp.where(
                take_pn[:, None] <= guard_rem, take_pn[:, None], 0.0,
            )
            if not size_tiebreak:
                full_take = jnp.zeros_like(full_take)
            size_key = jnp.where(ok_cd, -full_take, BIG)
            pk = jnp.where(ok_cd, cand_price, BIG)
            flat = lex_argmin(score, size_key, pk, ci_key * D + di_key)
            bc = (flat // D).astype(jnp.int32)
            bd = (flat % D).astype(jnp.int32)
            ok = score.reshape(-1)[flat] < BIG
            return bc, bd, ok


        # ---- zone-seed (mode B): the whole group lands in ONE zone — the
        # cheapest-absorption zone when open slots exist, else the best
        # new-node zone (after the first placement every later pod must join
        # a zone with a matching pod, so the seed choice is the whole game)
        def _z_seed(_):
            # only zones with anti-affinity/spread headroom are seedable
            elb = el & (zone_budget >= 1.0)
            ok_slots0 = rf & (cap >= 1.0) & elb[jnp.maximum(row_zone, 0)]
            has0 = jnp.any(ok_slots0)
            # Seed the zone that ABSORBS the group most cheaply, not the
            # earliest open slot's zone: eligible free-row capacity takes
            # pods at zero marginal cost, the remainder pays the zone's best
            # new-node $/pod (kubelet fuzz seed 20: the earliest open slot
            # sat in zone-1a while a hostname-spread fleet's free rows —
            # enough for the whole group — sat in zone-1b; chasing the slot
            # bought 4 dedicated nodes the sequential oracle never buys).
            # Ties (several zones absorb everything free) break on
            # first-open-slot order then zone index — the old deterministic
            # behavior, which also serves as the all-BIG fallback when no
            # zone can host the whole group.
            free_z = jnp.zeros(Z, dtype=jnp.float32).at[
                jnp.maximum(row_zone, 0)
            ].add(jnp.where(ok_slots0, cap, 0.0))
            # the zone's LEGAL headroom for this group (anti-affinity +
            # spread band) caps both free-row absorption and what new nodes
            # can add — a zone whose rows could hold the group but whose
            # budget admits one pod must not win on phantom capacity
            budget_z = jnp.where(elb, zone_budget, 0.0)
            place_z = jnp.minimum(jnp.minimum(free_z, budget_z), cnt)
            paid_z = jnp.maximum(jnp.minimum(cnt, budget_z) - place_z, 0.0)
            ok_cd0 = (new_ok_nolim & _lim_ok_cur(prov_used)[:, None]
                      & elb[dom_zone][None, :])
            # $/pod amortized over the ZONE's paid remainder, not the whole
            # group: a 2-pod remainder on a 40-pod node pays the full node
            ppp_cd = jnp.where(
                ok_cd0,
                cand_price / jnp.maximum(
                    jnp.minimum(take_pn[:, None], paid_z[dom_zone][None, :]),
                    1.0,
                ),
                BIG,
            )
            ppp_z = jnp.full(Z, BIG).at[dom_zone].min(jnp.min(ppp_cd, axis=0))
            # budget headroom only counts as placeable when there is SUPPLY
            # behind it — free rows, or a purchasable candidate in the zone
            # (limits can exhaust a zone's candidates mid-solve; an empty
            # zone with a big spread budget but nothing to buy must not win
            # the seed and strand the whole group)
            purch_z = jnp.where(ppp_z < BIG, paid_z, 0.0)
            unplaced_z = jnp.maximum(cnt - place_z - purch_z, 0.0)
            cost_z = jnp.where(
                elb, jnp.minimum(purch_z * ppp_z, BIG), BIG,
            )
            first_slot = jnp.min(
                jnp.where(
                    ok_slots0[:, None]
                    & (row_zone[:, None] == jnp.arange(Z)[None, :]),
                    slot_idx[:, None].astype(jnp.float32), BIGN,
                ), axis=0,
            )                                                       # [Z]
            z_best = lex_argmin(
                jnp.where(elb, unplaced_z, BIGN), cost_z, first_slot,
                jnp.arange(Z, dtype=jnp.float32),
            ).astype(jnp.int32)
            _bc0, bd0, okp0 = pick(cnt, elb[dom_zone], prov_used)
            return jnp.where(has0, z_best, jnp.where(okp0, dom_zone[bd0], -1))

        z_star = jax.lax.cond(zone_seed, _z_seed,
                              lambda _: jnp.int32(-1), operand=None)
        el = jnp.where(zone_seed, el & (jnp.arange(Z) == z_star), el)

        new_ok_z = jnp.zeros(Z, dtype=bool).at[zone_of_dom].max(jnp.any(new_ok, axis=0))
        cap_z = jnp.minimum(rowcap_z + jnp.where(new_ok_z, BIGN, 0.0), anti_cap)
        cap_z = jnp.where(el, cap_z, 0.0)

        # ---- allocation: rows then new nodes ---------------------------
        def zoned_alloc(_):
            # Limit-aware, zone-fair allocation.  Per-zone creation below
            # runs zones SEQUENTIALLY, so a provisioner limit that binds
            # mid-group would be spent entirely on the first zones,
            # stranding later zones at 0 — a maxSkew violation the
            # sequential oracle never produces because it interleaves
            # zones.  Three closed-form passes:
            #   1. tentative fill with unlimited new capacity -> how many
            #      NEW pods each zone would need beyond its open rows;
            #   2. water-fill the limit-fundable new-pod budget (sum over
            #      provisioner pools of each pool's best whole-node count;
            #      partial nodes consume full capacity against the limit)
            #      across those needs;
            #   3. final fill with rows+funded caps, then the maxSkew recap
            #      (lvl_min over ALL eligible zones, capacity-stuck ones
            #      included) — overflow stays unplaced, it does NOT pile
            #      into unstuck zones.
            head_c = jnp.min(
                jnp.floor(
                    (prov_limits[cand_prov] - prov_used[cand_prov] + 1e-6)
                    / jnp.maximum(cand_cap, 1e-9)
                ),
                axis=1,
            )                                                           # [C]
            c_ok = jnp.any(new_ok_nolim, axis=1)
            per_c = jnp.where(c_ok, jnp.clip(head_c, 0.0, BIGN) * take_pn, 0.0)
            # provisioner limits are independent pools: the fundable total is
            # the SUM over provisioners of each pool's best candidate, not a
            # single global best
            per_p = jnp.zeros(prov_limits.shape[0], dtype=per_c.dtype).at[
                cand_prov
            ].max(per_c)
            fundable_new = jnp.minimum(jnp.sum(per_p), BIGN)
            # all three allocation passes prefer FREE existing-row capacity
            # within the skew band (skew_band_fill): plain leveling buys a
            # new node in one zone while free capacity idles in another —
            # the sequential oracle's first-fit never does (fuzz seed 14)
            rows_z = jnp.where(el, rowcap_z, 0.0)
            skew_eff = jnp.where(
                zsp >= 0, g_zone_skew[g].astype(jnp.float32), BIGN
            )
            alloc0 = skew_band_fill(
                zc_sp, rows_z, cap_z, cnt, skew_eff, el
            ).astype(jnp.float32)
            need_new = jnp.maximum(alloc0 - jnp.minimum(rows_z, alloc0), 0.0)
            funded_new = water_fill(
                jnp.zeros(Z, dtype=jnp.float32), need_new, fundable_new,
                el & (need_new > 0),
            ).astype(jnp.float32)
            cap_f = jnp.where(el, jnp.minimum(rows_z + funded_new, cap_z), 0.0)
            alloc1 = skew_band_fill(
                zc_sp, jnp.minimum(rows_z, cap_f), cap_f, cnt, skew_eff, el
            ).astype(jnp.float32)
            lvl_min = jnp.min(jnp.where(el, zc_sp + alloc1, BIGN))
            skew_cap = jnp.where(
                zsp >= 0,
                lvl_min + g_zone_skew[g].astype(jnp.float32) - zc_sp,
                BIGN,
            )
            cap_z2 = jnp.minimum(cap_f, jnp.maximum(skew_cap, 0.0))
            alloc_z = skew_band_fill(
                zc_sp, jnp.minimum(rows_z, cap_z2), cap_z2, cnt, skew_eff, el
            ).astype(jnp.float32)  # [Z]
            # per-zone prefix allocation over slots in creation order
            zone1h = (row_zone[:, None] == jnp.arange(Z)[None, :])           # [NR, Z]
            capz_slots = jnp.where(zone1h, cap[:, None], 0.0)
            before = jnp.cumsum(capz_slots, axis=0) - capz_slots
            take_slots = jnp.clip(alloc_z[None, :] - before, 0.0, capz_slots)
            take = jnp.sum(jnp.where(zone1h, take_slots, 0.0), axis=1)
            taken_z = jnp.sum(jnp.where(zone1h, take_slots, 0.0), axis=0)
            rem_z = jnp.maximum(alloc_z - taken_z, 0.0)
            return take, rem_z

        def simple_alloc(_):
            take = prefix_allocate(cap, cnt)
            rem = cnt - jnp.sum(take)
            return take, jnp.where(jnp.arange(Z) == 0, rem, 0.0)  # placeholder; zone chosen below

        state = (res, row_zone, row_dom, row_cand, row_price, active, prov_used,
                 jnp.zeros(NR, dtype=jnp.float32), n_used)

        def write_block(state, n_nodes, per_node, last_extra, bc, bd):
            """Append n_nodes slots of candidate bc/domain bd; each takes
            per_node pods except the last which takes last_extra.  Returns
            (state, pods actually placed)."""
            (res, row_zone, row_dom, row_cand, row_price, active, prov_used,
             new_take, cursor) = state
            # budget clamp; floor at 0 — cursor starts at NE which may already
            # exceed a small node_budget, and a negative count must not walk
            # the cursor backward or deduct phantom prov_used capacity
            n_req = n_nodes
            n_nodes = jnp.maximum(
                jnp.minimum(n_nodes, jnp.minimum(NR, node_budget) - cursor), 0
            )
            in_block = (slot_idx >= cursor) & (slot_idx < cursor + n_nodes)
            is_last = slot_idx == (cursor + n_nodes - 1)
            # last_extra is the partial fill of the block's true final node;
            # when the budget truncated the block, every written node is an
            # interior one and must take the full per_node
            last_take = jnp.where(n_nodes >= n_req, last_extra, per_node)
            blk = jnp.where(in_block, jnp.where(is_last, last_take, per_node), 0.0)
            new_take = new_take + blk
            res = jnp.where(in_block[:, None], cand_alloc[bc][None, :], res)
            row_zone = jnp.where(in_block, dom_zone[bd], row_zone)
            row_dom = jnp.where(in_block, bd, row_dom)
            row_cand = jnp.where(in_block, bc, row_cand)
            row_price = jnp.where(in_block, cand_price[bc, bd], row_price)
            active = active | in_block
            prov_used = prov_used.at[cand_prov[bc]].add(
                cand_cap[bc] * n_nodes.astype(jnp.float32)
            )
            state = (res, row_zone, row_dom, row_cand, row_price, active,
                     prov_used, new_take, cursor + n_nodes)
            return state, jnp.sum(blk)

        def limit_headroom(prov_used_cur, bc):
            """Max nodes of candidate bc before its provisioner limit binds."""
            p = cand_prov[bc]
            head = prov_limits[p] - prov_used_cur[p]          # [R]
            cap_row = cand_cap[bc]
            per = jnp.where(cap_row > 0, jnp.floor((head + 1e-6) / jnp.maximum(cap_row, 1e-9)), BIGN)
            return jnp.clip(jnp.min(per), 0.0, BIGN)

        def stage_pair(state, rem, dom_mask, score_rem):
            """One (bulk, tail) creation round; returns leftover pods.

            ``score_rem`` is the remaining count used in the $/pod scoring
            denominator — the GROUP's remainder, not this zone's share.  The
            sequential oracle scores every placement against the whole
            group's remaining pods (reference.py _best_in_zone), so a
            3-zone-spread group still buys node types sized for the full
            group; scoring per-zone thirds buys smaller types and ~2x the
            node count at similar cost."""
            bc, bd, ok = pick(score_rem, dom_mask, state[6], pool_rem=rem)
            ppn_b = jnp.maximum(take_pn[bc], 1.0)
            n_bulk_f = jnp.where(ok, jnp.floor(rem / ppn_b), 0.0)
            n_bulk = jnp.minimum(n_bulk_f, limit_headroom(state[6], bc)).astype(jnp.int32)
            state, took_b = write_block(state, n_bulk, ppn_b, ppn_b, bc, bd)
            rem_t = jnp.maximum(rem - took_b, 0.0)
            score_t = jnp.maximum(score_rem - took_b, rem_t)
            ct_, dt_, ok_t = pick(score_t, dom_mask, state[6], tail_rem=rem_t,
                                  pool_rem=rem_t)
            ppn_t = jnp.maximum(take_pn[ct_], 1.0)
            n_tail_f = jnp.where(ok_t & (rem_t > 0), jnp.ceil(rem_t / ppn_t), 0.0)
            n_tail = jnp.minimum(n_tail_f, limit_headroom(state[6], ct_)).astype(jnp.int32)
            last = rem_t - (n_tail.astype(jnp.float32) - 1.0) * ppn_t
            state, took_t = write_block(
                state, n_tail, ppn_t, jnp.clip(last, 0.0, ppn_t), ct_, dt_
            )
            return state, jnp.maximum(rem_t - took_t, 0.0)

        def two_stage(state, rem, dom_mask, score_rem=None):
            # round 2 only fires when a provisioner limit (or slot budget)
            # clamped round 1; pick() re-derives limit feasibility, so the
            # remainder falls back to the next-best candidate type.
            if score_rem is None:
                score_rem = rem
            state, left = stage_pair(state, rem, dom_mask, score_rem)
            state, _ = stage_pair(state, left, dom_mask, jnp.maximum(score_rem - (rem - left), left))
            return state

        def normal_flow(state):
            take, rem_z = jax.lax.cond(zoned, zoned_alloc, simple_alloc, operand=None)

            def create_simple(state):
                return two_stage(state, jnp.sum(rem_z), jnp.ones(D, dtype=bool))

            def create_zoned(state):
                # scan (not a Python loop) over zones: the two_stage creation
                # body is traced ONCE instead of Z times, cutting the XLA
                # program size — and thus compile time — roughly by the zone
                # count for the creation section (the dominant traced code).
                # Every zone's BULK type choice scores against the group's
                # FULL new-node demand (not a zone-decremented remainder):
                # the sequential oracle interleaves zones, so each zone's
                # first node is created while `remaining` is still the whole
                # group — a later-ordered zone must not buy a smaller type
                # (worse $/pod after the reserved-overhead staircase) just
                # because the scan visited it second (fuzz seed 14).  Tail
                # picks stay honest via tail_rem; an oversized bulk choice
                # self-corrects (n_bulk floors to 0 and the tail re-scores).
                total = jnp.sum(rem_z)

                def zbody(st_z, z):
                    st_z = two_stage(st_z, rem_z[z], zone_of_dom == z,
                                     score_rem=total)
                    return st_z, jnp.int32(0)

                state, _ = jax.lax.scan(
                    zbody, state, jnp.arange(Z, dtype=jnp.int32),
                )
                return state

            state = jax.lax.cond(zoned, create_zoned, create_simple, state)
            return state, take

        def host_seed_flow(state):
            # mode-B hostname affinity: every pod of the group must land on
            # the SAME node — first-fit the earliest compatible open slot,
            # else create one node; the un-fitting remainder is infeasible
            # (exactly where the sequential oracle ends up: after pod 1 seeds
            # a node, pods 2..k must join it, and a fresh node is never
            # admissible again because matching pods now exist).
            elb = el & (zone_budget >= 1.0)
            ok_slots = rf & (cap >= 1.0) & elb[jnp.maximum(row_zone, 0)]
            has = jnp.any(ok_slots)
            first = jnp.argmax(ok_slots)
            z_first = jnp.maximum(row_zone[first], 0)
            take = jnp.zeros(NR, dtype=jnp.float32).at[first].set(
                jnp.where(has,
                          jnp.minimum(jnp.minimum(cnt, cap[first]),
                                      zone_budget[z_first]),
                          0.0)
            )
            bc, bd, okp = pick(cnt, elb[dom_zone], state[6], size_tiebreak=False)
            n_new = jnp.where(~has & okp, 1, 0).astype(jnp.int32)
            per = jnp.minimum(jnp.minimum(cnt, jnp.maximum(take_pn[bc], 1.0)),
                              jnp.maximum(zone_budget[dom_zone[bd]], 0.0))
            state, _ = write_block(state, n_new, per, per, bc, bd)
            return state, take

        state, take = jax.lax.cond(host_seed, host_seed_flow, normal_flow, state)
        (res, row_zone, row_dom, row_cand, row_price, active, prov_used,
         new_take, n_used) = state

        total_take = take + new_take
        res = res - total_take[:, None] * req_g[None, :]

        # ---- counters -----------------------------------------------------
        match_g = g_sel_match[:, g].astype(jnp.float32)                        # [S]
        selcnt = selcnt + (total_take[:, None] * match_g[None, :]).astype(selcnt.dtype)
        placed_z = jnp.zeros(Z, dtype=jnp.float32).at[jnp.maximum(row_zone, 0)].add(
            jnp.where(active, total_take, 0.0)
        )
        zc = zc + (match_g[:, None] * placed_z[None, :]).astype(zc.dtype)
        placed = jnp.sum(total_take)
        tot = tot + (match_g * placed).astype(tot.dtype)
        infeasible = infeasible.at[g].set(jnp.round(cnt - placed).astype(jnp.int32))

        carry = (res, row_zone, row_dom, row_cand, row_price, selcnt, active,
                 n_used, zc, tot, prov_used, infeasible)
        ys = total_take.astype(jnp.int32) if track else jnp.int32(0)
        return carry, ys

    return step


def _last_group(counts):
    """1 + the index of the last group that has pods (0 where none has):
    the steps a scan over ``counts`` has to take.  Rows past it are the
    ``G`` rung's padding (``host_count_arrays`` pads ``counts`` with zeros),
    and a step for a group of no pods leaves the carry as it found it."""
    G = counts.shape[0]
    return jnp.max(jnp.where(counts > 0,
                             jnp.arange(1, G + 1, dtype=jnp.int32), 0))


def _scan_groups(step, init, n_steps, take_shape: tuple, track: bool):
    """The ONE loop of the device program, for the single solve and the
    megabatch alike: ``step(carry, g)`` for ``g`` in ``0..n_steps-1``,
    ``n_steps`` a traced int32 the program read off its own batch — the
    ``G`` rung is the SHAPE of the group axis, not the trip count.  The take
    matrix is carried as a zero buffer of ``take_shape`` (group axis second
    to last) that each step writes its row of in place, so the rows of
    groups the loop never reached read zero, as a step for a group of no
    pods would have left them.  Returns ``(carry, takes, steps_run)`` with
    ``steps_run`` the loop's final index."""
    axis = len(take_shape) - 2

    def body(state):
        g, carry, takes = state
        carry, row = step(carry, g)
        if track:
            takes = jax.lax.dynamic_update_index_in_dim(takes, row, g, axis)
        return g + 1, carry, takes

    # untracked solves keep the [.., G] zeros the scan used to stack
    takes0 = jnp.zeros(take_shape if track else take_shape[:-1],
                       dtype=jnp.int32)
    steps_run, carry, takes = jax.lax.while_loop(
        lambda state: state[0] < n_steps, body, (jnp.int32(0), init, takes0))
    return carry, takes, steps_run


@partial(jax.jit, static_argnames=("NR", "Z", "track"))
def _run_scan(consts, init, NR: int, Z: int, track: bool):
    """Module-level jitted scan: the jit cache persists across solves, so
    bucketed shapes recompile once per signature, not once per call.  It
    takes a step for every group up to the last one that has pods and
    returns ``(carry, takes, steps_run)``; the ``G`` rung it was compiled at
    is the take matrix's first axis."""
    step = _make_step(consts, NR, Z, track)
    counts = consts["counts"]
    return _scan_groups(step, init, _last_group(counts),
                        (counts.shape[0], NR), track)


#: megabatch request-slot cap: one vmapped dispatch solves at most this many
#: independent solve requests (service/server.py --max-slots clamps here)
MEGA_MAX_SLOTS = 32


def _mega_rung(n: int, n_dev: int = 1) -> int:
    """Pad the request-slot axis to a power-of-two rung (1,2,4,...,32): the
    megabatch kernel compiles per (dims, B) signature, so bucketing B keeps
    the compile ladder log-bounded and AOT-precompilable, exactly like the
    tensor-axis rungs of :func:`_rung`.

    ``n_dev`` > 1 is the SHARDED megabatch (slot axis data-parallel over the
    flattened mesh — parallel/mesh.py slot_mesh): the rung ladder floors at
    the device count and doubles from there (8 devices -> 8, 16, 32), so the
    slot axis always divides evenly over the chips and every rung keeps the
    whole mesh lit — a 3-slot flush on an 8-chip mesh pads to 8 (padding
    slots replicate request 0 and are discarded; idle chips would cost the
    same wall time and serve nothing).  The result never exceeds
    MEGA_MAX_SLOTS: a non-power-of-two device count whose next double would
    cross the cap stops at its largest in-ladder rung (24 devices -> {24},
    6 -> {6, 12, 24}) — callers cap their flush size at that rung
    (:func:`max_mega_slots`), so no off-ladder program is ever compiled."""
    r = max(1, n_dev)
    while r < min(max(1, n), MEGA_MAX_SLOTS) and r * 2 <= MEGA_MAX_SLOTS:
        r *= 2
    return r


def max_mega_slots(mesh) -> int:
    """Largest megabatch flush this mesh can serve on the sharded rung
    ladder (= MEGA_MAX_SLOTS when unmeshed or the devices divide it evenly;
    smaller for awkward device counts — 24 chips cap flushes at 24), or 0
    for an unshardable mesh (device count past the ladder): no sharded
    megabatch program exists to size a flush for, and returning the raw
    device count would let a trusting caller build a flush that
    solve_many_async can only reject."""
    if not mesh_shardable(mesh):
        return 0
    return _mega_rung(MEGA_MAX_SLOTS, _mesh_size(mesh))


def _mesh_size(mesh) -> int:
    return 1 if mesh is None else int(mesh.devices.size)


def _mega_key_tail(slots: int, zone_key: int, ct_key: int, mesh) -> tuple:
    """The megabatch compile-key suffix: slot rung + zone/ct vocab
    positions (+ the mesh fingerprint when sharded).  The SINGLE source of
    this format — ``mega_signature``, ``_dispatch_prepared`` and the
    consolidation sweep's ``sweep_signature`` all append exactly this, so
    readiness/warm bookkeeping can never drift from what dispatch keys."""
    tail = (
        ("mega_slots", _mega_rung(slots, _mesh_size(mesh))),
        ("zk", zone_key),
        ("ck", ct_key),
    )
    if mesh is not None:
        from ..parallel.mesh import mesh_signature

        tail += (("mesh", mesh_signature(mesh)),)
    return tail


def mesh_shardable(mesh) -> bool:
    """True when the megabatch slot axis can shard over ``mesh``: the
    device count must fit inside the slot-rung ladder (a 64-chip mesh
    cannot pad a <=32-slot batch to one slot per chip — such schedulers
    keep the sharded single-solve path and count mesh_serial flushes)."""
    return _mesh_size(mesh) <= MEGA_MAX_SLOTS


#: the megabatch bucket-key components that are PADDED axis rungs — two
#: buckets differing only here can share one dispatch when one dominates
#: (building the smaller request at the larger rungs is the normal padding
#: path `_host_arrays` already runs for every solve)
UNIFIABLE_DIMS = ("G", "C", "NR", "NE_pad", "S", "P")
#: the non-dims tail components `_mega_key_tail` appends — derived FROM
#: the tail builder (plus the mesh fingerprint it conditionally adds), so
#: key-splitting can never drift from key construction (KT014's
#: single-source contract)
_MEGA_TAIL_NAMES = tuple(
    k for k, _ in _mega_key_tail(1, 0, 0, None)) + ("mesh",)


def unify_mega_keys(a: tuple, b: tuple) -> Optional[tuple]:
    """The DOMINANT of two megabatch bucket keys when one subsumes the
    other, else None — the host-aware coalescer's mixed-bucket unification
    (ISSUE 14): a flush holding bucket A can admit a bucket-B request iff
    every axis rung of one key >= the other's and everything else (vocab
    key positions, track, mesh fingerprint, slot rung) matches exactly;
    the dominated requests then build their tensors at the dominant dims
    (``solve_many_async(target_dims=...)``) and the whole flush runs ONE
    mesh dispatch instead of two serial ones.

    Domination-only on purpose: the unified program IS the dominant
    bucket's own program, which real traffic already warms — a
    component-wise-max of divergent keys would mint programs nothing
    precompiles (the KT014 compile-surface discipline)."""
    if a == b:
        return a
    da, db = dict(a), dict(b)
    if set(da) != set(db):
        return None
    a_dom = b_dom = True
    for k, va in da.items():
        vb = db[k]
        if va == vb:
            continue
        if k not in UNIFIABLE_DIMS:
            return None
        if va < vb:
            a_dom = False
        else:
            b_dom = False
    if a_dom:
        return a
    if b_dom:
        return b
    return None


def mega_key_dims(key: tuple) -> dict:
    """The solve_dims dict embedded in a megabatch bucket key (everything
    but the `_mega_key_tail` components) — what a unified dispatch passes
    to ``_host_arrays(dims=...)`` so dominated requests pad to the
    dominant bucket's rungs."""
    return {k: v for k, v in dict(key).items() if k not in _MEGA_TAIL_NAMES}


def mega_key_at_slots(key: tuple, slots: int, mesh) -> tuple:
    """Re-key a slots=1 megabatch bucket key at a real flush size: the
    dims part stays, the tail is re-derived for ``slots`` — the signature
    a unified flush's readiness/warm bookkeeping probes (single-sourced
    through `_mega_key_tail` like every other mega key)."""
    d = dict(key)
    dims_part = tuple(sorted(
        (k, v) for k, v in d.items() if k not in _MEGA_TAIL_NAMES))
    return dims_part + _mega_key_tail(slots, d["zk"], d["ck"], mesh)


def multihost_fence_enabled() -> bool:
    """Per-host megabatch fences (read only the process-addressable slot
    shards) — default on; ``KT_MULTIHOST=0`` forces the legacy whole-batch
    readback (an emergency kill switch)."""
    import os

    return os.environ.get("KT_MULTIHOST", "1") != "0"


def read_slot_rows(arrays, *, local_only: bool = False):
    """Fence + read the leading (request-slot) axis of stacked megabatch
    arrays — THE addressable-shard accessor (ktlint KT018's sanctioned
    home): serving-path extraction must route mesh-sharded carry reads
    through here, never a raw ``np.asarray``/``device_get`` on the whole
    array, which on a multi-host mesh pays DCN latency (and memory) for
    every slot other hosts own.

    ``local_only`` reads ONLY ``jax.process_index()``-addressable shards
    (single-process: that is every shard, byte-identical to the whole
    read); otherwise one whole-array D2H per array (the single-device /
    kill-switch path).  Returns ``(rows, bytes_read, bytes_total)`` where
    ``rows[k][s]`` is slot ``s`` of array ``k`` — only locally-owned slots
    are present under ``local_only`` on a multi-process mesh."""
    rows: List[dict] = []
    bytes_read = 0
    bytes_total = 0
    for arr in arrays:
        bytes_total += int(getattr(arr, "nbytes", 0) or 0)
        per: Dict[int, np.ndarray] = {}
        if local_only:
            for shard in arr.addressable_shards:
                # D2H of the LOCAL shard only: this np.asarray is the
                # per-host fence — it blocks until the shard's slots
                # finish and transfers just their bytes
                data = np.asarray(shard.data)  # ktlint: allow[KT018] the accessor itself
                start = shard.index[0].start or 0
                for j in range(data.shape[0]):
                    per[start + j] = data[j]
                bytes_read += int(data.nbytes)
        else:
            a = np.asarray(arr)  # ktlint: allow[KT018] the accessor itself
            for s in range(a.shape[0]):
                per[s] = a[s]
            bytes_read += int(a.nbytes)
        rows.append(per)
    return rows, bytes_read, bytes_total


@partial(jax.jit, static_argnames=("NR", "Z", "track", "zone_key", "ct_key"))
def _run_scan_many(consts_b, feas_b, init_b, NR: int, Z: int, track: bool,
                   zone_key: int, ct_key: int):
    """Megabatch kernel: B independent solve requests in ONE device dispatch.

    ``jax.vmap`` over the per-request (consts, feasibility-input, carry)
    pytrees — every slot runs the same feasibility + step program the single
    path runs, over its own tensors, inside the one loop of
    :func:`_scan_groups`.  Slots cannot interact by construction: the only
    cross-slot value is the loop's trip count (the longest slot's, and a
    step past a slot's last group is a no-op for it), so a slot's result is
    a pure function of that slot's inputs (tests/test_megabatch.py pins
    per-request byte parity with serial solves and adversarial cross-tenant
    isolation).  Feasibility runs inside the program (not eagerly per
    request) so the whole megabatch costs one dispatch + one fence.

    SHARDED megabatches need no kernel change: when the caller commits the
    stacked inputs with the slot-axis sharding (``_dispatch_prepared``
    with a mesh — dim 0 one-slot-per-chip, parallel/mesh.py slot_mesh),
    GSPMD partitions this very program on the batch dimension; the
    independence argument above is also why the partitioning introduces
    no collective but the 4-byte maximum that agrees the trip count, once,
    before the loop (tests/test_megabatch_sharded.py pins parity and the
    every-chip placement)."""

    def feasibility(consts, feas):
        return compute_feasibility(
            feas["pm"], consts["requests"], feas["gp_ok"], feas["cand_vw"],
            feas["cand_vb"], consts["cand_alloc"], consts["cand_prov"],
            feas["key_check"], feas["dom_vw"], feas["dom_vb"],
            zone_key, ct_key,
        )

    F_b, dom_ok_b = jax.vmap(feasibility)(consts_b, feas_b)
    consts_b = dict(consts_b, F=F_b, dom_ok=dom_ok_b)

    def step(carry_b, g):
        return jax.vmap(
            lambda consts, carry: _make_step(consts, NR, Z, track)(carry, g)
        )(consts_b, carry_b)

    # ONE trip count for the batch, the longest slot's: the loop's bound
    # stays a scalar every slot (and every chip of a slot mesh) agrees on,
    # and a slot that ran out of groups earlier takes no-op steps, as it
    # did on the rung's padding
    counts_b = consts_b["counts"]
    n_steps = jnp.max(jax.vmap(_last_group)(counts_b))
    return _scan_groups(step, init_b, n_steps, counts_b.shape + (NR,), track)


# ---------------------------------------------------------------------------
# host-facing API
# ---------------------------------------------------------------------------


@dataclass
class TpuSolveOutput:
    result: SolveResult
    takes: Optional[np.ndarray]  # [G, NR] pods placed per slot per group step
    n_used: int
    solve_ms: float
    compile_ms: float


class SlotsExhausted(Exception):
    """The optimistic NR axis ran out of node slots and the full-budget
    program is not compiled yet (see TpuSolver.solve raise_on_exhaust)."""

    def __init__(self, full_sig: tuple) -> None:
        super().__init__("node-slot estimate exhausted; full program cold")
        self.full_sig = full_sig


class MegaBucketMismatch(ValueError):
    """A megabatch flush's requests do not share one compile bucket (the
    caller's grouping raced a bucket-state change, or a direct caller
    over/mis-filled the slots).  The collector degrades the flush to serial
    per-request dispatches — clients must never see this."""


def _node_budget(st: SolveTensors, NE: int, max_nodes: Optional[int]) -> int:
    if max_nodes is None:
        max_nodes = NE + int(st.counts.sum())  # worst case: one pod per node
    return max(1, max_nodes)


def zone_share_matrix(st: SolveTensors, pad_g: int, Z: int) -> np.ndarray:
    """``[G+pad, Z]`` even split over each group's eligible zones — the
    counts-INdependent factor of :func:`host_count_arrays`, memoized on the
    tensors (like ``_nr_est_cache``): the hierarchical block builder
    (solver/hierarchy.py) rebuilds the suffix projections once per block
    per price wave and must not re-walk every group's zone requirements
    each time."""
    cache = getattr(st, "_zone_share_cache", None)
    key = (pad_g, Z)
    if cache is not None and cache[0] == key:
        return cache[1]
    G = st.G
    zone_share = np.zeros((G + pad_g, Z), dtype=np.float32)
    for gi, grp in enumerate(st.groups):
        vs = grp.requirements.get(L.ZONE)
        ok = np.zeros(Z, dtype=bool)
        for zi, zname in enumerate(st.zone_names):
            ok[zi] = vs.contains(zname)
        if not ok.any():
            ok[:] = True
        zone_share[gi] = ok.astype(np.float32) / float(ok.sum())
    st._zone_share_cache = (key, zone_share)
    return zone_share


def suffix_projection(demand_z: np.ndarray, count_z: np.ndarray):
    """``(suffix_res[G, Z, R], suffix_cnt[G, Z])`` — the later-group
    backfill suffix sums of per-zone demand.  Shared by
    :func:`host_count_arrays` and the hierarchical block builder's masked
    per-block recompute (one source for the cumsum orientation)."""
    suffix_res = np.concatenate(
        [np.cumsum(demand_z[::-1], axis=0)[::-1][1:],
         np.zeros((1,) + demand_z.shape[1:])]
    ).astype(np.float32)
    suffix_cnt = np.concatenate(
        [np.cumsum(count_z[::-1], axis=0)[::-1][1:],
         np.zeros((1, count_z.shape[1]))]
    ).astype(np.float32)
    return suffix_res, suffix_cnt


def host_count_arrays(st: SolveTensors, pad_g: int, Z: int):
    """The counts-dependent host tensors of one solve: padded counts +
    requests and the PER-ZONE suffix projection of later-group demand
    (suffix sums of count*request, distributed over each group's eligible
    zones) — the backfill available to fill slack on nodes bought for the
    current group, in resource units: 50 tiny pods cannot justify a big
    node the way 50 same-sized pods can, and a later group zone-pinned (or
    hard-spread) elsewhere cannot justify THIS zone's node at all.  The
    sequential oracle gets this for free by replaying demand zone by zone
    (designs/bin-packing.md:28-43); here the zone share is an even split
    over the group's eligible zones (node_selector folds into group
    requirements), which is exactly what a hard DoNotSchedule spread
    commits and a conservative, pool-conserving estimate for flexible
    groups.

    Factored out of ``_host_arrays`` because these are the ONLY group-side
    tensors that depend on the counts vector: the consolidation sweep
    (solver/consolidation.py) derives every candidate what-if from one
    shared base build and recomputes just this per candidate."""
    np_counts = np.pad(st.counts, (0, pad_g), constant_values=0)
    np_requests = np.pad(st.requests, ((0, pad_g), (0, 0)),
                         constant_values=0)
    demand = (np_counts[:, None] * np_requests).astype(np.float32)   # [G, R]
    zone_share = zone_share_matrix(st, pad_g, Z)
    demand_z = demand[:, None, :] * zone_share[:, :, None]           # [G, Z, R]
    count_z = np_counts[:, None].astype(np.float32) * zone_share     # [G, Z]
    np_suffix_res, np_suffix_cnt = suffix_projection(demand_z, count_z)
    return np_counts, np_requests, np_suffix_res, np_suffix_cnt


class TpuSolver:
    """Builds and caches the jitted solve for a tensor shape signature.

    Compile-readiness is tracked per signature (the padded-dims key from
    ``solve_dims``): ``ready()`` tells the scheduler whether a solve of this
    shape will hit the jit cache or stall ~tens of seconds in XLA, and
    ``warm_async()`` compiles a signature on a background thread — the
    scheduler's compile-behind fallback and the operator's startup warmup
    both ride it.  The reference bar is the Go FFD's zero-warmup ms-scale
    first solve (designs/bin-packing.md:28-43): callers must never eat a
    cold compile."""

    #: at most this many concurrent background compiles; extras queue (FIFO,
    #: bounded) and start as slots free up
    MAX_CONCURRENT_WARMS = 2
    MAX_QUEUED_WARMS = 8
    #: a shape whose background compile failed is not retried for this long
    #: (prevents a deterministically-failing compile from burning a full
    #: compile of CPU on every solve of that shape)
    WARM_FAILURE_BACKOFF = 300.0

    def __init__(self, clock: Optional[Clock] = None,
                 registry: Optional[Registry] = None) -> None:
        import threading

        # the scan's axes, its slot retries and the merge pass over its
        # nodes, zero-initialised so the families exist from the first
        # scrape (KT003)
        self.registry = registry or default_registry
        for axis in SCAN_AXES:
            self.registry.counter(SCAN_AXIS).inc({"axis": axis}, value=0.0)
        self.registry.counter(SCAN_SLOT_RETRIES).inc(value=0.0)
        for what in COALESCE_WHAT:
            self.registry.counter(COALESCE).inc({"what": what}, value=0.0)
        # persistent compile cache: every process that constructs a solver
        # shares previously compiled XLA programs — a restarted replica
        # skips the compile
        _init_jit_cache()
        # injectable clock for the warm-failure backoff (tests advance a
        # FakeClock past WARM_FAILURE_BACKOFF instead of sleeping it out)
        self._clock = clock or Clock()
        # fault-injection plane (docs/RESILIENCE.md): null + falsy unless
        # KT_FAULTS configures a chaos schedule — the dispatch/fence choke
        # points below guard with one truthiness check
        self._faults = faults_mod.plane()
        self._lock = threading.Lock()
        self._ready: set = set()                     # guarded-by: _lock
        self._compiling: set = set()                 # guarded-by: _lock
        self._queued: list = []                      # guarded-by: _lock  [(sig, kwargs)]
        self._failed_until: Dict[tuple, float] = {}  # guarded-by: _lock
        self._stopped = False                        # guarded-by: _lock  stop_warms(): no new spawns
        # shape families whose optimistic NR estimate exhausted at least
        # once: their signature permanently resolves to the full-budget
        # dims, so readiness checks / warmups / solves all target the
        # program that will actually serve them (no per-solve double run)
        self._nr_exhausted: set = set()              # guarded-by: _lock

    # ---- compile-readiness ----------------------------------------------
    def signature(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        mesh=None,
    ) -> tuple:
        NE = len(existing_nodes)
        a, b = _mesh_divs(mesh)
        node_budget = _node_budget(st, NE, max_nodes)
        dims = solve_dims(
            st, NE=NE, node_budget=node_budget,
            a=a, b=b, track=track_assignments,
        )
        key = _dims_key(dims)
        with self._lock:
            exhausted = key in self._nr_exhausted
        if exhausted:
            key = _dims_key(solve_dims(
                st, NE=NE, node_budget=node_budget,
                a=a, b=b, track=track_assignments, full_nr=True,
            ))
        return key

    def mega_signature(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        slots: int = 2,
        mesh=None,
    ) -> tuple:
        """Compile signature of the megabatch program that would serve a
        ``slots``-request batch of this shape: the single-solve dims key plus
        the padded request-slot rung and the vocab positions of the zone/ct
        keys (static args of the vmapped kernel — two catalogs interning the
        keys differently are different programs AND different buckets).

        ``mesh`` is the SHARDED megabatch: per-slot dims stay the
        single-device ones (each slot runs whole on one chip — the slot
        axis, not the tensor axes, is what shards), the slot rung floors at
        the device count, and the mesh's (axis, size) fingerprint joins the
        key — the partitioned program is a different XLA binary AND a
        different coalescer bucket than the single-device one."""
        base = self.signature(
            st, existing_nodes=existing_nodes, max_nodes=max_nodes,
            track_assignments=track_assignments,
        )
        return base + _mega_key_tail(
            slots, st.vocab.key_id[L.ZONE], st.vocab.key_id[L.CAPACITY_TYPE],
            mesh,
        )

    def ready(self, sig: tuple) -> bool:
        with self._lock:
            return sig in self._ready

    def compiling(self, sig: tuple) -> bool:
        with self._lock:
            return sig in self._compiling

    def warm_pending(self, sig: tuple) -> bool:
        """A warm for ``sig`` is already compiling, queued, or in its
        failure backoff — admitting another would be refused, so callers
        can skip preparing its (potentially expensive) inputs."""
        with self._lock:
            return (sig in self._compiling
                    or any(s == sig for s, _ in self._queued)
                    or self._clock.now() < self._failed_until.get(sig, 0.0))

    def compiles_in_flight(self) -> int:
        with self._lock:
            return len(self._compiling)

    def warm_idle(self) -> bool:
        """No background compile running or queued."""
        with self._lock:
            return not self._compiling and not self._queued

    def stop_warms(self) -> None:
        """Drop all queued warms and stop the drain (operator shutdown):
        exit then waits only for the compiles already in flight, never the
        queue."""
        with self._lock:
            self._stopped = True
            self._queued.clear()

    def _mark_ready(self, sig: tuple) -> None:
        # NOTE: deliberately does NOT discard the sig from _compiling — a
        # warm thread for this sig may still be mid-flight, and the
        # "compiles_in_flight() == 0 implies every on_done ran" invariant
        # (watchers poll it, then read the compile metrics) requires the
        # warm thread itself to clear its entry AFTER its on_done callback
        with self._lock:
            self._ready.add(sig)

    def warm_async(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        mesh=None,
        on_done=None,
        slots: Optional[int] = None,
    ) -> bool:
        """Compile this solve's signature on a background thread (running
        the full solve and discarding the result — compile dominates).
        Returns True when the warm was accepted (started or queued), False
        when the signature is already ready/compiling/queued, is in its
        failure backoff, or the queue is full.  ``on_done(sig, seconds,
        error)`` fires from the worker thread when the warm ends.
        ``slots`` > 1 warms the MEGABATCH program at that request-slot rung
        instead of the single-solve program; with ``mesh`` that is the
        SHARDED megabatch program (slot axis over the flattened mesh)."""
        if slots and slots > 1:
            sig = self.mega_signature(
                st, existing_nodes=existing_nodes, max_nodes=max_nodes,
                track_assignments=track_assignments, slots=slots, mesh=mesh,
            )
        else:
            slots = None
            sig = self.signature(
                st, existing_nodes=existing_nodes, max_nodes=max_nodes,
                track_assignments=track_assignments, mesh=mesh,
            )
        kwargs = dict(
            st=st, existing_nodes=existing_nodes, max_nodes=max_nodes,
            track_assignments=track_assignments, mesh=mesh, on_done=on_done,
            slots=slots,
        )
        return self._admit_warm(sig, kwargs)

    def warm_custom(self, sig, thunk, on_done=None) -> bool:
        """Background-compile an arbitrary prepared device program on the
        warm machinery (concurrency cap, bounded queue, failure backoff):
        ``thunk()`` must run — and thereby compile + ``_mark_ready`` — the
        program ``sig`` names.  The consolidation sweep uses this to warm
        its shared-base vmapped what-if program while serving the first
        sweeps serially (the compile-behind contract)."""
        return self._admit_warm(sig, dict(on_done=on_done, thunk=thunk))

    def _admit_warm(self, sig: tuple, kwargs: dict) -> bool:
        with self._lock:
            if self._stopped:
                return False
            if sig in self._ready or sig in self._compiling:
                return False
            if any(s == sig for s, _ in self._queued):
                return False
            if self._clock.now() < self._failed_until.get(sig, 0.0):
                return False  # recent compile failure: back off
            if len(self._compiling) >= self.MAX_CONCURRENT_WARMS:
                if len(self._queued) >= self.MAX_QUEUED_WARMS:
                    return False
                self._queued.append((sig, kwargs))
                return True
            self._compiling.add(sig)
        self._spawn_warm(sig, kwargs)
        return True

    def _spawn_warm(self, sig: tuple, kwargs: dict) -> None:
        import threading

        on_done = kwargs.pop("on_done")
        slots = kwargs.pop("slots", None)
        thunk = kwargs.pop("thunk", None)

        def work():
            t0 = time.perf_counter()
            err = None
            try:
                if thunk is not None:
                    # custom prepared program (warm_custom): the thunk owns
                    # compilation AND the _mark_ready of its signature
                    thunk()
                elif slots:
                    # megabatch warm: one request padded up to the slot rung
                    # compiles exactly the program a full batch will run
                    # (with a mesh, the SHARDED rung program)
                    warm_mesh = kwargs.pop("mesh", None)
                    outs = self.solve_many([dict(kwargs)], min_slots=slots,
                                           mesh=warm_mesh)
                    if isinstance(outs[0], Exception):
                        raise outs[0]
                else:
                    self.solve(**kwargs)
            # ktlint: allow[KT005] compile failure is surfaced via on_done
            # (the scheduler's callback logs it) and arms the retry backoff
            except Exception as e:  # pragma: no cover - surfaced via on_done
                err = e
                with self._lock:
                    self._failed_until[sig] = self._clock.now() + self.WARM_FAILURE_BACKOFF
            try:
                if on_done is not None:
                    on_done(sig, time.perf_counter() - t0, err)
                elif err is not None:
                    # no callback to surface it (the sweep / block-wave
                    # warms): a failed compile is never silent
                    import logging as _logging

                    _logging.getLogger(__name__).warning(
                        "background compile failed after %.1fs: %r",
                        time.perf_counter() - t0, err)
            except Exception:  # a throwing callback must not wedge the tier
                import logging as _logging

                _logging.getLogger(__name__).warning(
                    "warm on_done callback raised", exc_info=True
                )
            finally:
                # clear the in-flight entry only AFTER on_done: watchers
                # poll compiles_in_flight() down to 0 and then read the
                # metrics the callback records — dropping the count first
                # is a race.  In a finally (with the callback exception
                # swallowed above) so neither the entry leaks nor the queue
                # drain below is skipped — either would permanently consume
                # a MAX_CONCURRENT_WARMS slot
                with self._lock:
                    self._compiling.discard(sig)
            # drain: start the next queued warm that is still cold — unless
            # the process is exiting (threading._shutdown is joining us: the
            # main thread is gone) or stop_warms() ran; exit must wait only
            # for compiles already in flight, never the whole queue
            import threading as _threading

            while True:
                with self._lock:
                    if (self._stopped
                            or not _threading.main_thread().is_alive()
                            or not self._queued
                            or len(self._compiling) >= self.MAX_CONCURRENT_WARMS):
                        return
                    next_sig, next_kwargs = self._queued.pop(0)
                    if next_sig in self._ready:
                        continue  # compiled by a direct solve meanwhile
                    self._compiling.add(next_sig)
                self._spawn_warm(next_sig, next_kwargs)
                return

        # NON-daemon: a daemon thread hard-killed at interpreter exit while
        # inside an XLA compile aborts the whole process (std::terminate);
        # a non-daemon thread instead delays exit until the compile lands,
        # which is the safe behavior for operator shutdown and CLI runs
        threading.Thread(target=work, name="tpu-solver-warm").start()

    def _host_arrays(
        self,
        st: SolveTensors,
        existing_nodes: Sequence[SimNode],
        *,
        node_budget: int,
        track_assignments: bool,
        full_nr: bool,
        a: int = 1,
        b: int = 1,
        dims: Optional[dict] = None,
    ):
        """Pure-host (numpy) build of one solve's padded tensors: returns
        ``(np_consts, feas, np_init, dims)`` with every value a numpy array.
        The SINGLE source of the padding/bucketing both device paths share:
        :meth:`prepare` (single solve — device placement + feasibility
        precompute) and :meth:`solve_many` (megabatch — slot-stacked arrays,
        feasibility inside the vmapped program) each consume this, so the
        two programs can never pad a batch differently.  No device ops run
        here (``feas`` carries the feasibility INPUTS, not F).

        ``dims`` overrides the :func:`solve_dims` bucketing with caller-
        chosen padded dimensions (the consolidation sweep's fine-grained
        small-solve rungs) — callers own the compile-ladder consequences."""
        G, C, D, R = st.G, max(1, st.C), st.D, st.R
        S, Z = st.S, max(1, st.n_zones)
        K, W = st.pm.shape[1], st.pm.shape[2]
        NE = len(existing_nodes)

        # ---- shape bucketing + mesh padding ------------------------------
        # The scan compiles per (G, C, NR, ...) signature; rung-bucketing the
        # axes (linear quanta for small shapes, geometric beyond — see _rung)
        # makes repeated controller solves hit the persistent jit cache
        # instead of paying a fresh XLA compile per batch shape, and keeps
        # the total rung ladder small enough to precompile (warm_async).
        if dims is None:
            dims = solve_dims(st, NE=NE, node_budget=node_budget, a=a, b=b,
                              track=track_assignments, full_nr=full_nr)
        pad_g = dims["G"] - G
        pad_c = dims["C"] - C
        pad_s = dims["S"] - S
        NR = dims["NR"]

        def _pad(arr, n, axis, value):
            if n == 0:
                return arr
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, n)
            return np.pad(arr, widths, constant_values=value)

        np_counts, np_requests, np_suffix_res, np_suffix_cnt = (
            host_count_arrays(st, pad_g, Z))
        np_pm = _pad(st.pm, pad_g, 0, 0)
        np_gzs = _pad(st.g_zone_spread, pad_g, 0, -1)
        np_gzk = _pad(st.g_zone_skew, pad_g, 0, 1)
        np_ghs = _pad(st.g_host_spread, pad_g, 0, -1)
        np_ghc = _pad(st.g_host_cap, pad_g, 0, 0)
        np_gza = _pad(st.g_zone_anti, pad_g, 0, -1)
        np_gzp = _pad(st.g_zone_paff, pad_g, 0, -1)
        np_ghp = _pad(st.g_host_paff, pad_g, 0, -1)
        np_gsm = _pad(_pad(st.g_sel_match, pad_g, 1, False), pad_s, 0, False)
        np_gp_ok = _pad(st.gp_ok, pad_g, 0, False)
        np_cvw = _pad(st.cand_vw, pad_c, 0, 0)
        np_cvb = _pad(st.cand_vb, pad_c, 0, 0)
        np_calloc = _pad(st.cand_alloc, pad_c, 0, 0)
        np_ccap = _pad(st.cand_cap, pad_c, 0, 0)
        np_cprov = _pad(st.cand_prov, pad_c, 0, 0)
        np_cprice = _pad(st.cand_price, pad_c, 0, np.float32(3.0e38))
        np_cavail = _pad(st.cand_avail, pad_c, 0, False)
        G = G + pad_g
        S = S + pad_s

        # ---- existing-node tensors (host-side compat precompute) -------
        NE_pad = dims["NE_pad"]  # rung-bucketed: stable jit shapes
        P_pad = dims["P"]
        ex_res = np.zeros((NR, R), dtype=np.float32)
        ex_zone = np.zeros(NR, dtype=np.int32)
        ex_sel = np.zeros((NR, S), dtype=np.int32)
        ex_ok = np.zeros((G, NE_pad), dtype=bool)
        ex_price = np.zeros(NR, dtype=np.float32)
        zone_index = {z: i for i, z in enumerate(st.zone_names)}
        zc0 = np.zeros((S, Z), dtype=np.int32)
        tot0 = np.zeros(S, dtype=np.int32)
        prov_used0 = np.zeros((P_pad, R), dtype=np.float32)
        prov_index = {n: i for i, n in enumerate(st.prov_names)}

        # limits bind on raw machine CAPACITY (st.capacity_row; the
        # independent validator agrees) — fuzz seed 23
        for ni, node in enumerate(existing_nodes):
            ex_res[ni] = st.vocab.resources_to_row(node.remaining()).astype(np.float32)
            ex_zone[ni] = zone_index.get(node.zone, 0)
            ex_price[ni] = node.price
            pi = prov_index.get(node.provisioner)
            if pi is not None:
                prov_used0[pi] += st.capacity_row(node.instance_type,
                                                  node.allocatable)
            for gi, g in enumerate(st.groups):
                rep = g.pods[0]
                ex_ok[gi, ni] = (
                    not any(t.blocks(rep.tolerations) for t in node.taints)
                    and g.requirements.compatible(node.labels) is None
                )
        # selector counts on existing nodes + zone counters
        for si, (sel, topo, kind) in enumerate(st.selector_defs):
            for ni, node in enumerate(existing_nodes):
                n_match = sum(1 for p in node.pods if sel.matches(p.labels))
                ex_sel[ni, si] = n_match
                zc0[si, zone_index.get(node.zone, 0)] += n_match
                tot0[si] += n_match

        np_consts = dict(
            counts=np_counts,
            suffix_res=np_suffix_res,
            suffix_cnt=np_suffix_cnt,
            requests=np_requests,
            g_zone_spread=np_gzs,
            g_zone_skew=np_gzk,
            g_host_spread=np_ghs,
            g_host_cap=np_ghc,
            g_zone_anti=np_gza,
            g_zone_paff=np_gzp,
            g_host_paff=np_ghp,
            g_sel_match=np_gsm,
            cand_alloc=np_calloc,
            cand_cap=np_ccap,
            cand_prov=np_cprov,
            cand_price=np.where(np.isinf(np_cprice), np.float32(3.0e38),
                                np_cprice).astype(np.float32),
            cand_avail=np_cavail,
            prov_limits=_pad(
                np.where(np.isinf(st.prov_limits), np.float32(3.0e38),
                         st.prov_limits).astype(np.float32),
                P_pad - st.prov_limits.shape[0], 0, np.float32(3.0e38),
            ),
            dom_zone=st.dom_zone,
            ex_ok=ex_ok,
            node_budget=np.int32(node_budget),
        )
        feas = dict(
            pm=np_pm,
            gp_ok=np_gp_ok,
            cand_vw=np_cvw,
            cand_vb=np_cvb,
            key_check=st.key_check,
            dom_vw=st.dom_vw,
            dom_vb=st.dom_vb,
        )
        np_init = (
            ex_res,                                  # res
            ex_zone,                                 # row_zone
            np.full(NR, -1, dtype=np.int32),         # row_dom
            np.full(NR, -1, dtype=np.int32),         # row_cand
            ex_price,                                # row_price
            ex_sel,                                  # selcnt
            np.arange(NR) < NE,                      # active
            np.int32(NE),                            # n_used
            zc0,                                     # zc
            tot0,                                    # tot
            prov_used0,                              # prov_used
            np.zeros(G, dtype=np.int32),             # infeasible
        )
        return np_consts, feas, np_init, dims

    def prepare(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        mesh=None,
        full_nr: bool = False,
    ):
        """Build (run_fn, init_carry, NE); ``run_fn(init)`` is
        :func:`_run_scan`'s ``(carry, takes, steps_run)``.  ``mesh`` shards
        the group/candidate/node-slot axes over a jax.sharding.Mesh
        (parallel/mesh.py layout)."""
        NE = len(existing_nodes)
        node_budget = _node_budget(st, NE, max_nodes)
        a, b = _mesh_divs(mesh)
        np_consts, feas, np_init, dims = self._host_arrays(
            st, existing_nodes, node_budget=node_budget,
            track_assignments=track_assignments, full_nr=full_nr, a=a, b=b,
        )
        NR, Z = dims["NR"], dims["Z"]

        consts = {k: jnp.asarray(v) for k, v in np_consts.items()}

        zone_key = st.vocab.key_id[L.ZONE]
        ct_key = st.vocab.key_id[L.CAPACITY_TYPE]

        if mesh is not None:
            from ..parallel.distributed import put_sharded
            from ..parallel.mesh import POD_AXIS, TYPE_AXIS, axis_sharding

            # cached construction (parallel/mesh.py): sharding objects are
            # built once per (mesh, spec), not once per solve (KT011)
            sg = axis_sharding(mesh, POD_AXIS)     # group axis
            sc = axis_sharding(mesh, TYPE_AXIS)    # candidate axis
            sr = axis_sharding(mesh)               # replicated
            place = {
                "counts": sg, "requests": sg, "suffix_res": sg,
                "suffix_cnt": sg,
                "g_zone_spread": sg, "g_zone_skew": sg,
                "g_host_spread": sg, "g_host_cap": sg, "g_zone_anti": sg,
                "g_zone_paff": sg, "g_host_paff": sg,
                "g_sel_match": sr, "cand_alloc": sc, "cand_cap": sc,
                "cand_prov": sc, "cand_price": sc, "cand_avail": sc,
                "prov_limits": sr, "dom_zone": sr, "ex_ok": sg,
            }
            consts = {k: put_sharded(v, place.get(k, sr)) for k, v in consts.items()}

        if mesh is not None and jax.process_count() > 1:
            # multi-process: eager per-op execution on non-addressable global
            # arrays is not allowed — run the feasibility precompute as one
            # jitted SPMD program over explicitly placed inputs
            from ..parallel.distributed import put_sharded

            F, dom_ok = feasibility_jit(
                put_sharded(feas["pm"], sg), consts["requests"],
                put_sharded(feas["gp_ok"], sg),
                put_sharded(feas["cand_vw"], sc),
                put_sharded(feas["cand_vb"], sc), consts["cand_alloc"],
                consts["cand_prov"], put_sharded(feas["key_check"], sr),
                put_sharded(feas["dom_vw"], sr),
                put_sharded(feas["dom_vb"], sr),
                zone_key=zone_key, ct_key=ct_key,
            )
        elif mesh is not None:
            # single-process mesh: eager compute respects the consts'
            # explicit shardings (GSPMD layout is driven by input placement)
            F, dom_ok = compute_feasibility(
                jnp.asarray(feas["pm"]), consts["requests"],
                jnp.asarray(feas["gp_ok"]), jnp.asarray(feas["cand_vw"]),
                jnp.asarray(feas["cand_vb"]), consts["cand_alloc"],
                consts["cand_prov"], jnp.asarray(feas["key_check"]),
                jnp.asarray(feas["dom_vw"]), jnp.asarray(feas["dom_vb"]),
                zone_key, ct_key,
            )
        else:
            # single-device: the module-level jitted program replaces ~a
            # dozen eager op dispatches per solve (each ~host-ms on the
            # serving path); compare ops and exact bf16 bit-counts make the
            # jitted result byte-identical to the eager one
            F, dom_ok = feasibility_jit(
                jnp.asarray(feas["pm"]), consts["requests"],
                jnp.asarray(feas["gp_ok"]), jnp.asarray(feas["cand_vw"]),
                jnp.asarray(feas["cand_vb"]), consts["cand_alloc"],
                consts["cand_prov"], jnp.asarray(feas["key_check"]),
                jnp.asarray(feas["dom_vw"]), jnp.asarray(feas["dom_vb"]),
                zone_key=zone_key, ct_key=ct_key,
            )
        consts["F"], consts["dom_ok"] = F, dom_ok

        init = tuple(jnp.asarray(v) for v in np_init)
        if mesh is not None:
            from ..parallel.distributed import put_sharded
            from ..parallel.mesh import POD_AXIS, axis_sharding

            sn = axis_sharding(mesh, POD_AXIS)   # node-slot axis
            sr = axis_sharding(mesh)
            shardings = (sn, sn, sn, sn, sn, sn, sn, sr, sr, sr, sr, sr)
            init = tuple(put_sharded(a, s) for a, s in zip(init, shardings))

        def run(init):
            return _run_scan(consts, init, NR, Z, track_assignments)

        return run, init, NE

    def _prepare_dispatch(
        self, st: SolveTensors, existing_nodes, max_nodes,
        track_assignments: bool, mesh, full_nr: bool,
    ):
        """Shared dispatch preamble for ``solve`` and ``solve_async`` —
        the SINGLE source of the dims/bucketing/exhausted-promotion steps,
        so the synchronous and pipelined paths can never run different
        programs for the same batch.  Returns
        ``(run, init, NE, est_dims, full_dims, full_nr)``; ``run(init)``
        has NOT been called."""
        a, b = _mesh_divs(mesh)
        NE0 = len(existing_nodes)
        node_budget = _node_budget(st, NE0, max_nodes)
        est_dims = solve_dims(st, NE=NE0, node_budget=node_budget, a=a, b=b,
                              track=track_assignments)
        full_dims = solve_dims(st, NE=NE0, node_budget=node_budget, a=a, b=b,
                               track=track_assignments, full_nr=True)
        if not full_nr:
            # shape families that exhausted the optimistic NR before go
            # straight to the full program (see _nr_exhausted)
            with self._lock:
                full_nr = _dims_key(est_dims) in self._nr_exhausted
        run, init, NE = self.prepare(
            st, existing_nodes=existing_nodes, max_nodes=max_nodes,
            track_assignments=track_assignments, mesh=mesh, full_nr=full_nr,
        )
        return run, init, NE, est_dims, full_dims, full_nr

    def _count_scan(self, st: SolveTensors, dims: dict, n_used: int,
                    steps_run: int, span) -> None:
        """One finished device scan, by the axes it ran at: the batch's
        groups, the ``G`` rung the program was compiled at (the shape of its
        group axis, no longer its trip count), the serial steps it TOOK as
        the program itself reports them (read at the fence beside
        ``n_used``: up to the last group that has pods; in a megabatch the
        longest slot's), the node slots every step carries and the slots in
        use when it ended (existing nodes included, as in ``NR``; the host's
        ``coalesce`` may merge them into fewer nodes).  ``dims`` is the
        program that ran (the estimate's, or the full budget's on a retry)
        and ``span`` the one that fenced it."""
        axes = {"groups": st.G, "groups_padded": dims["G"],
                "steps_run": steps_run,
                "node_slots": dims["NR"], "nodes_used": n_used}
        counter = self.registry.counter(SCAN_AXIS)
        for axis in SCAN_AXES:
            counter.inc({"axis": axis}, value=float(axes[axis]))
        span.annotate(S=dims["S"], **axes)

    # ktlint: fence reads two scalars off the finished carry to decide the
    # slot-exhaustion retry — the solve is already fenced by its caller
    def _maybe_retry_exhausted(
        self, carry, est_dims: dict, full_dims: dict, full_nr: bool,
        raise_on_exhaust: bool, retry,
    ) -> Optional["TpuSolveOutput"]:
        """Slot-exhaustion epilogue, the SINGLE source of the retry protocol
        shared by ``solve`` and ``PendingTpuSolve.result``: when the
        optimistic NR axis genuinely ran out of node slots AND left pods
        unplaced, remember the shape family (``_nr_exhausted``), honor
        ``raise_on_exhaust`` (the compile-behind contract), register the
        inline full-budget compile so a concurrent ``warm_async`` of the
        same shape doesn't spawn a duplicate XLA compile, and run
        ``retry()`` (a full-budget re-solve).  Returns None when the solve
        stands.  Rare by construction — the estimate is doubled — so steady
        state keeps the small fast program."""
        if full_nr or est_dims["NR"] >= full_dims["NR"]:
            return None
        n_used_v = int(np.asarray(carry[7]))
        infeasible_v = int(np.asarray(carry[11]).sum())
        if n_used_v < est_dims["NR"] or infeasible_v <= 0:
            return None
        full_key = _dims_key(full_dims)
        self.registry.counter(SCAN_SLOT_RETRIES).inc()
        with self._lock:
            self._nr_exhausted.add(_dims_key(est_dims))
            full_ready = full_key in self._ready
        if raise_on_exhaust and not full_ready:
            raise SlotsExhausted(full_key)
        with self._lock:
            inline_compile = full_key not in self._compiling
            if inline_compile:
                self._compiling.add(full_key)
        try:
            return retry()
        finally:
            if inline_compile:
                with self._lock:
                    self._compiling.discard(full_key)

    # ktlint: fence the synchronous solve IS the sync point — dispatch, the
    # D2H fence, and the measured re-run all live here by contract
    def solve(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        mesh=None,
        measure: bool = False,
        full_nr: bool = False,
        raise_on_exhaust: bool = False,
        trace=None,
    ) -> TpuSolveOutput:
        """One device solve.  ``measure=True`` adds a second, results-discarded
        execution with fenced timing (benchmarks only — production controller
        solves must pay exactly one device execution).

        ``raise_on_exhaust=True`` raises :class:`SlotsExhausted` instead of
        inline-compiling the full-budget program when the optimistic NR axis
        ran out of slots and the full program is not compiled yet — the
        scheduler catches it and serves the solve from the warm tier while
        the full program compiles behind (the 'callers must never eat a cold
        compile' contract)."""
        t0 = time.perf_counter()
        trace = trace or NULL_TRACE
        with trace.span("device_prepare"):
            run, init, NE, est_dims, full_dims, full_nr = self._prepare_dispatch(
                st, existing_nodes, max_nodes, track_assignments, mesh, full_nr,
            )
        with trace.span("device_execute", full_nr=full_nr) as span:
            if self._faults:
                self._faults.fire("dispatch")     # dispatch_exc raises here
            carry, ys, steps = run(init)
            if self._faults:
                effect = self._faults.fire("fence")  # device_hang raises
                if effect is not None and effect.kind == "slow_fence":
                    self._faults.sleep(effect)
            # D2H fence: reading a 4-byte result of the scan back cannot
            # complete before the program has, and the extraction below
            # needs the carry on the host anyway
            n_used = int(np.asarray(carry[7]))
            self._count_scan(st, full_dims if full_nr else est_dims, n_used,
                             int(np.asarray(steps)), span)
        compile_ms = (time.perf_counter() - t0) * 1000.0
        solve_ms = compile_ms
        # mark ready the key of the program that ACTUALLY compiled (a fresh
        # signature() could race a concurrent _nr_exhausted insert and mark
        # the full program ready when only the estimated one compiled)
        self._mark_ready(_dims_key(full_dims if full_nr else est_dims))

        # slot-exhaustion retry: NR is sized by an optimistic estimate
        # (_nr_estimate); see _maybe_retry_exhausted for the protocol
        retried = self._maybe_retry_exhausted(
            carry, est_dims, full_dims, full_nr, raise_on_exhaust,
            lambda: self.solve(
                st, existing_nodes=existing_nodes, max_nodes=max_nodes,
                track_assignments=track_assignments, mesh=mesh,
                measure=measure, full_nr=True,
            ),
        )
        if retried is not None:
            return retried

        if measure:
            # Timing run, results discarded: the same program on the same
            # inputs, executed again (a local PJRT client runs every
            # dispatch; chip_smoke.py checks that on the chip) and fenced
            # by the same D2H read as above.
            t1 = time.perf_counter()
            carry2, _ys2, _steps2 = run(init)
            np.asarray(carry2[7])
            solve_ms = (time.perf_counter() - t1) * 1000.0

        with trace.span("extract"):
            return self._extract(
                st, carry, ys if track_assignments else None, existing_nodes,
                NE, solve_ms, compile_ms, trace,
            )

    def solve_async(
        self,
        st: SolveTensors,
        *,
        existing_nodes: Sequence[SimNode] = (),
        max_nodes: Optional[int] = None,
        track_assignments: bool = True,
        mesh=None,
        raise_on_exhaust: bool = False,
        trace=None,
    ) -> "PendingTpuSolve":
        """Dispatch one device solve WITHOUT fencing.

        JAX dispatch is asynchronous: ``run(init)`` enqueues the H2D
        transfers (double-buffered ``device_put`` of this batch's tensors)
        and the scan, then returns while the device may still be executing
        the PREVIOUS batch.  The caller keeps the host free — typically to
        tensorize batch N+1 while batch N computes — and later calls
        :meth:`PendingTpuSolve.result` to fence and extract.  Callers are
        expected to dispatch only shapes that are already compiled
        (``ready()``); a cold shape compiles inline at dispatch, stalling
        the pipeline exactly like a cold ``solve`` would."""
        t0 = time.perf_counter()
        trace = trace or NULL_TRACE
        with trace.span("device_dispatch"):
            run, init, NE, est_dims, full_dims, full_nr = self._prepare_dispatch(
                st, existing_nodes, max_nodes, track_assignments, mesh,
                full_nr=False,
            )
            if self._faults:
                self._faults.fire("dispatch")  # dispatch_exc raises here
            carry, ys, steps = run(init)  # async: enqueued, not fenced
        return PendingTpuSolve(
            solver=self, st=st, existing_nodes=existing_nodes, NE=NE,
            carry=carry, ys=ys, steps=steps, t0=t0, track=track_assignments,
            est_dims=est_dims, full_dims=full_dims, full_nr=full_nr,
            raise_on_exhaust=raise_on_exhaust,
            solve_kwargs=dict(
                existing_nodes=existing_nodes, max_nodes=max_nodes,
                track_assignments=track_assignments, mesh=mesh,
            ),
            trace=trace,
        )

    def solve_many_async(
        self,
        requests: Sequence[dict],
        *,
        min_slots: Optional[int] = None,
        mesh=None,
        target_dims: Optional[dict] = None,
        registry=None,
    ) -> "PendingMegaSolve":
        """Dispatch B independent, signature-compatible solve requests as
        ONE vmapped device program over padded request slots, WITHOUT
        fencing — the continuous-batching analog of :meth:`solve_async`:
        the caller (SolvePipeline via the scheduler's collector) coalesces
        and tensorizes megabatch N+1 while megabatch N executes, then calls
        :meth:`PendingMegaSolve.results` for the single batch-wide fence.

        Each request is a dict with ``st`` (required) and optionally
        ``existing_nodes``, ``max_nodes``, ``track_assignments``,
        ``raise_on_exhaust``, ``trace``.  Every request must resolve to the
        SAME :meth:`mega_signature` bucket (the scheduler's coalescer groups
        by it; asserted here).  The batch axis pads up to the power-of-two
        slot rung (``_mega_rung``; ``min_slots`` forces a larger rung — the
        warm path compiles the full-batch program from one request); padding
        slots replicate request 0 and their outputs are discarded — vmap
        slots are independent by construction, so padding can never leak
        into a real request's result.

        ``mesh`` serves the batch SHARDED: the slot axis becomes a
        data-parallel dimension over the flattened mesh (one slot per chip,
        parallel/mesh.py slot_mesh), so a mesh-configured scheduler's
        coalesced flush lights every device — still ONE dispatch and ONE
        batch-wide fence (per-HOST fences on a multi-process mesh: each
        serving process reads only its addressable slot shards).  Per-slot
        programs are the single-device ones (results byte-identical to
        unmeshed serial solves).

        ``target_dims`` builds every request at caller-chosen padded dims
        (a UNIFIED mixed-bucket flush: dominated requests pad up to the
        dominant bucket's rungs — see :func:`unify_mega_keys`); the usual
        per-request `solve_dims` bucketing is bypassed, so callers own the
        compile-ladder consequences (the `_host_arrays(dims=...)`
        contract).  ``registry`` observes the per-host fence metrics."""
        assert requests, "empty megabatch"
        if len(requests) > MEGA_MAX_SLOTS:
            # a silent truncation would compile at shape B while marking the
            # rung-32 signature ready — callers (the pipeline's coalescer)
            # clamp to MEGA_MAX_SLOTS; a direct caller must too
            raise MegaBucketMismatch(
                f"{len(requests)} requests exceed MEGA_MAX_SLOTS="
                f"{MEGA_MAX_SLOTS}")
        t0 = time.perf_counter()
        defaults = dict(
            existing_nodes=(), max_nodes=None, track_assignments=True,
            raise_on_exhaust=False, trace=NULL_TRACE,
        )
        reqs = [{**defaults, **r} for r in requests]
        n_slots = max(len(reqs), min_slots or 1)
        # ONE snapshot of the exhausted families for the whole call: a
        # background warm thread flipping _nr_exhausted mid-flush must not
        # make the per-request dims diverge (the single path guards the
        # same race in solve(); see _mark_ready's comment there)
        with self._lock:
            exhausted = set(self._nr_exhausted)
        track = reqs[0]["track_assignments"]
        zone_key = reqs[0]["st"].vocab.key_id[L.ZONE]
        ct_key = reqs[0]["st"].vocab.key_id[L.CAPACITY_TYPE]

        entries = []
        for r in reqs:
            st = r["st"]
            NE = len(r["existing_nodes"])
            nb = _node_budget(st, NE, r["max_nodes"])
            est_dims = solve_dims(st, NE=NE, node_budget=nb, track=track)
            full_dims = solve_dims(st, NE=NE, node_budget=nb, track=track,
                                   full_nr=True)
            full_nr = _dims_key(est_dims) in exhausted
            np_consts, feas, np_init, dims = self._host_arrays(
                st, r["existing_nodes"], node_budget=nb,
                track_assignments=track, full_nr=full_nr,
                # unified flush: every request pads to the dominant
                # bucket's rungs, so one program serves the mixed batch
                dims=dict(target_dims) if target_dims is not None else None,
            )
            entries.append(dict(
                r=r, np_consts=np_consts, feas=feas, np_init=np_init,
                dims=dims, est_dims=est_dims, full_dims=full_dims,
                full_nr=full_nr, NE=NE,
            ))
        return self._dispatch_prepared(entries, n_slots=n_slots, track=track,
                                       zone_key=zone_key, ct_key=ct_key,
                                       t0=t0, mesh=mesh, registry=registry)

    def solve_many_prepared(
        self,
        entries: Sequence[dict],
        *,
        min_slots: Optional[int] = None,
        mesh=None,
        registry=None,
    ) -> "PendingMegaSolve":
        """Dispatch PRE-BUILT megabatch entries as one vmapped device
        program, without fencing — the consolidation sweep's entry point:
        it derives every candidate's entry from ONE shared base build
        (solver/consolidation.py build_sweep_entries) instead of paying a
        per-request ``_host_arrays``.  Each entry carries the same fields
        :meth:`solve_many_async` builds internally (``r``, ``np_consts``,
        ``feas``, ``np_init``, ``dims``, ``est_dims``, ``full_dims``,
        ``full_nr``, ``NE``); all entries must share one dims bucket."""
        if not entries:
            # typed like every other megabatch-construction failure (the
            # collector degrades these to serial dispatches) — a bare
            # assert vanishes under python -O and decays to an IndexError
            raise MegaBucketMismatch("empty megabatch")
        if len(entries) > MEGA_MAX_SLOTS:
            raise MegaBucketMismatch(
                f"{len(entries)} entries exceed MEGA_MAX_SLOTS="
                f"{MEGA_MAX_SLOTS}")
        t0 = time.perf_counter()
        r0 = entries[0]["r"]
        st0 = r0["st"]
        return self._dispatch_prepared(
            entries, n_slots=max(len(entries), min_slots or 1),
            track=r0["track_assignments"],
            zone_key=st0.vocab.key_id[L.ZONE],
            ct_key=st0.vocab.key_id[L.CAPACITY_TYPE], t0=t0, mesh=mesh,
            registry=registry,
        )

    def _dispatch_prepared(
        self, entries, *, n_slots: int, track: bool, zone_key: int,
        ct_key: int, t0: float, mesh=None, registry=None,
    ) -> "PendingMegaSolve":
        """Stack + dispatch prepared entries (shared by the request path and
        :meth:`solve_many_prepared`); validates the one-bucket invariant."""
        reqs = [e["r"] for e in entries]
        dims0 = entries[0]["dims"]
        if not all(e["dims"] == dims0 for e in entries) or any(
            r["st"].vocab.key_id[L.ZONE] != zone_key
            or r["st"].vocab.key_id[L.CAPACITY_TYPE] != ct_key
            or r["track_assignments"] != track
            for r in reqs
        ):
            # mis-bucketed flush (caller raced a bucket-state change): a
            # typed error the collector degrades to serial dispatches on —
            # never an opaque crash fanned to every RPC in the batch
            raise MegaBucketMismatch("requests span megabatch buckets")
        NR, Z = dims0["NR"], dims0["Z"]
        n_dev = _mesh_size(mesh)
        if not mesh_shardable(mesh):
            # padding one-slot-per-chip would compile a program past the
            # rung ladder; the scheduler gates these meshes onto the serial
            # path (mesh_serial), so only a direct caller can land here
            raise MegaBucketMismatch(
                f"{n_dev}-device mesh exceeds MEGA_MAX_SLOTS="
                f"{MEGA_MAX_SLOTS}; sharded megabatch unavailable")
        mega_key = _dims_key(dims0) + _mega_key_tail(
            n_slots, zone_key, ct_key, mesh)

        B = len(entries)
        B_pad = _mega_rung(n_slots, n_dev)
        if B > B_pad:
            # an awkward device count's largest in-ladder rung can sit
            # below the caller's flush size (24 chips cap at 24 slots) —
            # a mis-sized flush must degrade to serial, not under-pad
            raise MegaBucketMismatch(
                f"{B} entries exceed the {B_pad}-slot sharded rung of a "
                f"{n_dev}-device mesh")
        padded = entries + [entries[0]] * (B_pad - B)

        if mesh is not None:
            # sharded megabatch: the slot axis (dim 0 of every stacked
            # array) shards one-slot-per-chip over the flattened mesh
            # (parallel/mesh.py slot_mesh); trailing axes replicate, so a
            # slot's feasibility+scan run entirely on its own device — the
            # jitted kernel partitions from this input placement alone, no
            # cross-slot collectives by construction.  put_sharded keeps
            # the multi-process case honest (each host contributes only
            # its addressable — contiguous, host-major — slot shards).
            from ..parallel.distributed import put_sharded
            from ..parallel.mesh import slot_sharding

            slot_sh = slot_sharding(mesh)

        def _stack(vals):
            # slots built from one shared base (the consolidation sweep)
            # carry the SAME array object in most positions — broadcast the
            # batch axis instead of materializing B host copies (device_put
            # makes it contiguous once, at transfer)
            first = vals[0]
            if all(v is first for v in vals[1:]):
                arr = np.asarray(first)
                out = np.broadcast_to(arr, (len(vals),) + arr.shape)
            else:
                out = np.stack(vals)
            if mesh is not None:
                return put_sharded(out, slot_sh)
            return jnp.asarray(out)

        consts_b = {
            k: _stack([e["np_consts"][k] for e in padded])
            for k in entries[0]["np_consts"]
        }
        feas_b = {
            k: _stack([e["feas"][k] for e in padded])
            for k in entries[0]["feas"]
        }
        init_b = tuple(
            _stack([e["np_init"][i] for e in padded])
            for i in range(len(entries[0]["np_init"]))
        )

        # per-request trace stamps: the shared device phase is recorded on
        # EVERY request's trace as a pre-closed "megabatch" span carrying its
        # slot index and the batch occupancy (obs: per-slot attribution of a
        # shared dispatch)
        t_starts = [e["r"]["trace"].now() for e in entries]
        carry_b, ys_b, steps = _run_scan_many(  # async: enqueued, not fenced
            consts_b, feas_b, init_b, NR, Z, track, zone_key, ct_key,
        )
        return PendingMegaSolve(
            solver=self, entries=entries, carry_b=carry_b, ys_b=ys_b,
            steps=steps, t0=t0, t_starts=t_starts, track=track, B=B,
            B_pad=B_pad, mega_key=mega_key, mesh=mesh, registry=registry,
        )

    def solve_many(
        self,
        requests: Sequence[dict],
        *,
        min_slots: Optional[int] = None,
        mesh=None,
    ) -> List[object]:
        """Synchronous megabatch: :meth:`solve_many_async` + the one
        batch-wide fence.  Returns one entry per request IN ORDER: a
        :class:`TpuSolveOutput`, or the Exception that request alone hit
        (``SlotsExhausted`` under the compile-behind contract) — a bad slot
        must not poison its batchmates.  Per-request ``solve_ms`` is the
        megabatch wall time (dispatch→fence); callers wanting
        enqueue→respond latency stamp it themselves (service/server.py
        SolvePipeline does)."""
        if not requests:
            return []
        return self.solve_many_async(
            requests, min_slots=min_slots, mesh=mesh).results()

    def solve_delta(
        self,
        prev: "SolveResult",
        added: Sequence = (),
        removed: Sequence[str] = (),
        iced: Sequence[object] = (),
        *,
        provisioners,
        instance_types,
        daemonsets: Sequence = (),
        unavailable=None,
        max_delta_frac: Optional[float] = None,
        force_full: bool = False,
        tensorize_cache=None,
        registry=None,
        trace=None,
    ):
        """Warm-start delta solve: reuse ``prev``'s assignment and solve only
        the displaced subproblem (see solver/warmstart.py for the tiering
        and guards).  ``added`` are new pods, ``removed`` pod names leaving,
        ``iced`` newly unavailable offerings or reclaimed node names.

        The displaced-subproblem scan is SEEDED from the previous
        assignment: the surviving nodes (pods seated) become the existing-
        node tensors, so residual capacity, selector counts, zone counters
        and provisioner usage all start from the previous solution.  Passing
        a :class:`~karpenter_tpu.models.tensorize.TensorizeCache` reuses its
        catalog-side :class:`TensorizeContext` across the chain — the
        sub-millisecond tensorize the delta path rides.

        Consumes ``prev`` (node objects and assignment dict are carried
        forward, not copied).  Returns a ``DeltaOutcome``.  Device-
        expressible batches only — scheduler-level callers use
        :meth:`BatchScheduler.solve_delta`, which brings the full fallback
        ladder."""
        from ..models.tensorize import tensorize as _tensorize
        from . import warmstart

        def _tz(pods, unavail):
            if tensorize_cache is not None:
                st, _tier = tensorize_cache.tensorize(
                    pods, provisioners, instance_types,
                    daemonsets=daemonsets, unavailable=unavail,
                )
                return st
            return _tensorize(pods, provisioners, instance_types,
                              daemonsets=daemonsets, unavailable=unavail)

        def _solve(pods, existing, unavail):
            st = _tz(pods, unavail)
            out = self.solve(
                st, existing_nodes=existing,
                max_nodes=len(existing) + len(pods), trace=trace,
            )
            return out.result

        return warmstart.delta_solve(
            prev, added, removed, iced,
            solve_displaced=_solve, solve_full=_solve,
            max_delta_frac=max_delta_frac, registry=registry,
            unavailable=unavailable, force_full=force_full,
        )

    # ---- result extraction ---------------------------------------------
    # ktlint: fence extraction reads the whole carry back to host — it runs
    # strictly after the fence, on already-transferred results
    def _extract(
        self, st, carry, ys, existing_nodes, NE, solve_ms, compile_ms,
        trace=NULL_TRACE,
    ) -> TpuSolveOutput:
        # four leaves: the carry and the take matrix come back whole
        # (``readback``: 41 MB of takes for 1,640 groups on 4,608 slots), then
        # what the host makes of them (``nodes``, ``assign``, ``coalesce``)
        with trace.span("readback"):
            (res, row_zone, row_dom, row_cand, row_price, selcnt, active,
             n_used, zc, tot, prov_used, infeasible) = [
                np.asarray(x) for x in carry]
            n_used = int(n_used)
            takes = None if ys is None else np.asarray(ys)  # [G, NR]

        new_nodes: List[SimNode] = []
        slot_to_node: Dict[int, SimNode] = {}
        with trace.span("nodes"):
            for si in range(NE, n_used):
                ci = int(row_cand[si])
                if ci < 0 or not active[si]:
                    continue
                prov_name, type_name = st.cand_names[ci]
                zone = (st.zone_names[int(row_zone[si])]
                        if st.zone_names else "")
                node = SimNode(
                    instance_type=type_name,
                    provisioner=prov_name,
                    zone=zone,
                    capacity_type=self._ct_of_dom(st, int(row_dom[si])),
                    price=float(row_price[si]),
                    allocatable={
                        st.vocab.resources[r]: float(st.cand_alloc[ci, r])
                        for r in range(st.cand_alloc.shape[1])
                    },
                    existing=False,
                )
                node.stamp_labels()
                new_nodes.append(node)
                slot_to_node[si] = node

            # snapshots: placements must not leak into the caller's node
            # objects; the placed snapshots are returned (existing_nodes) so
            # retry waves can chain on them without double-booking capacity
            snap_existing = [n.snapshot() for n in existing_nodes]
            for ni, node in enumerate(snap_existing):
                slot_to_node[ni] = node

        assignments: Dict[str, str] = {}
        infeasible_map: Dict[str, str] = {}
        # id(node) -> {group: pods of it on the node}, off the take matrix
        node_groups: Optional[Dict[int, Dict[int, int]]] = None
        with trace.span("assign"):
            if takes is not None:
                node_groups = {}
                for gi, g in enumerate(st.groups):
                    placed_slots = np.nonzero(takes[gi])[0]
                    pod_iter = iter(g.pods)
                    for si in placed_slots:
                        node = slot_to_node.get(int(si))
                        if node is not None:
                            node_groups.setdefault(id(node), {})[gi] = int(
                                takes[gi, si])
                        for _ in range(int(takes[gi, si])):
                            try:
                                pod = next(pod_iter)
                            except StopIteration:
                                break
                            assignments[pod.name] = (node.name if node
                                                     else f"slot-{si}")
                            if node is not None:
                                node.pods.append(pod)
                    for pod in pod_iter:
                        infeasible_map[pod.name] = (
                            "solver: no feasible placement")
            else:
                for gi, g in enumerate(st.groups):
                    k = int(infeasible[gi])
                    for pod in g.pods[len(g.pods) - k:]:
                        infeasible_map[pod.name] = (
                            "solver: no feasible placement")

        # cost-neutral coalescing: merge small new nodes into larger types at
        # <= the same price (solver/coalesce.py — the scan buys each group's
        # tail at that group's step, so fragments accumulate across groups;
        # node count is operational load even when the $ match)
        from .coalesce import apply_coalesce

        # slots >= NE are exactly the new_nodes entries: what each has in
        # use is what its type allocates less what the scan left of it
        slots = [si for si in slot_to_node if si >= NE]
        used = (np.asarray(st.cand_alloc, dtype=np.float64)[row_cand[slots]]
                - np.asarray(res, dtype=np.float64)[slots])
        used_rows = {id(slot_to_node[si]): row for si, row in zip(slots, used)}
        n_in = len(new_nodes)
        with trace.span("coalesce") as span:
            new_nodes, pairs = apply_coalesce(
                st, new_nodes, used_rows, node_groups, assignments, span)
        counter = self.registry.counter(COALESCE)
        counter.inc({"what": "nodes_in"}, value=float(n_in))
        counter.inc({"what": "merges"}, value=float(n_in - len(new_nodes)))
        counter.inc({"what": "pairs"}, value=float(pairs))

        result = SolveResult(
            nodes=new_nodes,
            assignments=assignments,
            infeasible=infeasible_map,
            existing_nodes=snap_existing,
            solve_ms=solve_ms,
        )
        return TpuSolveOutput(
            result=result, takes=takes, n_used=n_used,
            solve_ms=solve_ms, compile_ms=compile_ms,
        )

    @staticmethod
    def _ct_of_dom(st, di: int) -> str:
        # tensorize builds domains zone-major: d = z * |ct| + ct_index
        n_ct = max(1, len(st.ct_names))
        if di < 0:
            return ""
        return st.ct_names[di % n_ct]


class PendingTpuSolve:
    """Handle for an async-dispatched device solve (``TpuSolver.solve_async``).

    ``result()`` performs the one D2H fence (a 4-byte read of the scan's
    carry), then extraction.  The published ``solve_ms`` spans dispatch
    start → fence completion, so it contains exactly one fence and
    includes any device queue wait behind an earlier in-flight batch (the
    caller-visible latency of the pipelined solve).  ``result()`` is
    idempotent; the slot-exhaustion retry semantics match ``solve``
    (including ``raise_on_exhaust`` for the compile-behind contract).
    """

    def __init__(self, solver, st, existing_nodes, NE, carry, ys, steps, t0,
                 track, est_dims, full_dims, full_nr, raise_on_exhaust,
                 solve_kwargs, trace=NULL_TRACE) -> None:
        self.solver = solver
        self.trace = trace
        self.st = st
        self.existing_nodes = existing_nodes
        self.NE = NE
        self.carry = carry
        self.ys = ys
        self.steps = steps
        self.t0 = t0
        self.track = track
        self.est_dims = est_dims
        self.full_dims = full_dims
        self.full_nr = full_nr
        self.raise_on_exhaust = raise_on_exhaust
        self.solve_kwargs = solve_kwargs
        self._out: Optional[TpuSolveOutput] = None

    # ktlint: fence result() IS the async handle's one D2H fence
    def result(self) -> TpuSolveOutput:
        if self._out is not None:
            return self._out
        s = self.solver
        with self.trace.span("device_fence") as span:
            if s._faults:
                effect = s._faults.fire("fence")  # device_hang raises here
                if effect is not None and effect.kind == "slow_fence":
                    s._faults.sleep(effect)
            n_used = int(np.asarray(self.carry[7]))  # the one D2H fence
            s._count_scan(self.st, self.full_dims if self.full_nr
                          else self.est_dims, n_used,
                          int(np.asarray(self.steps)), span)
        elapsed_ms = (time.perf_counter() - self.t0) * 1000.0
        s._mark_ready(_dims_key(self.full_dims if self.full_nr
                                else self.est_dims))
        # slot-exhaustion retry: the async handle resolves to a synchronous
        # full-budget re-solve via the same shared protocol as solve()
        retried = s._maybe_retry_exhausted(
            self.carry, self.est_dims, self.full_dims, self.full_nr,
            self.raise_on_exhaust,
            lambda: s.solve(self.st, full_nr=True, **self.solve_kwargs),
        )
        if retried is not None:
            self._out = retried
            return retried
        with self.trace.span("extract"):
            self._out = s._extract(
                self.st, self.carry, self.ys if self.track else None,
                self.existing_nodes, self.NE, elapsed_ms, elapsed_ms,
                self.trace,
            )
        return self._out


class PendingMegaSolve:
    """Handle for an async-dispatched megabatch (``solve_many_async``):
    ``results()`` performs the ONE batch-wide D2H fence — a PER-HOST fence
    on a meshed dispatch: only the ``jax.process_index()``-addressable
    slot shards are read back (:func:`read_slot_rows`), so on a
    multi-process mesh each serving process pays D2H for exactly the slots
    it owns instead of DCN latency for the whole batch — then per-slot
    extraction of the owned slots.  Slots another host owns resolve to a
    typed :class:`~karpenter_tpu.parallel.forward.SlotNotOwned` in their
    position (the per-slot boxed-outcome contract); the serving layer's
    forwarding shim routes those to the owning host.  Idempotent; per-slot
    slot-exhaustion semantics match ``solve_many``."""

    def __init__(self, solver, entries, carry_b, ys_b, steps, t0, t_starts,
                 track, B, B_pad, mega_key, mesh=None, registry=None) -> None:
        self.solver = solver
        self.entries = entries
        self.carry_b = carry_b
        self.ys_b = ys_b
        #: the steps the ONE loop took (a scalar: every slot ran them)
        self.steps = steps
        self.t0 = t0
        self.t_starts = t_starts
        self.track = track
        self.B = B
        self.B_pad = B_pad
        self.mega_key = mega_key
        #: the dispatch's mesh: the per-slot exhausted retry must re-solve
        #: on the MESHED full-budget program (the only one the meshed warm
        #: ladder covers), like the sibling retry sites in solve() and
        #: PendingTpuSolve
        self.mesh = mesh
        self.registry = registry
        #: per-host fence accounting, populated by results(): bytes this
        #: process actually read vs what a whole-batch readback would
        #: have, and the [start, stop) slot range it owns
        self.fence_bytes_read = 0
        self.fence_bytes_total = 0
        self.owned_slots: Tuple[int, int] = (0, B_pad)
        self._outputs: Optional[List[object]] = None

    # ktlint: fence the megabatch handle's one D2H read completes ALL
    # locally-owned request slots (the whole point: B solves, one device
    # round trip per host — addressable shards only on a meshed dispatch)
    def results(self) -> List[object]:
        if self._outputs is not None:
            return self._outputs
        s = self.solver
        # per-host fence (ISSUE 14): meshed dispatches read ONLY the
        # process-addressable slot shards of the carry — single-process
        # meshes own every shard (byte-identical to the whole read), and
        # KT_MULTIHOST=0 forces the legacy whole-batch readback
        per_host = self.mesh is not None and multihost_fence_enabled()
        owners: Optional[tuple] = None
        if per_host:
            from ..parallel.mesh import local_slot_range, multihost

            if multihost(self.mesh):
                from ..parallel.mesh import slot_hosts

                owners = slot_hosts(self.mesh, self.B_pad)
                self.owned_slots = local_slot_range(self.mesh, self.B_pad)
        # fence element 7 (n_used) first so elapsed_ms spans dispatch ->
        # fence completion exactly like the single-solve handle; the
        # remaining carry reads are post-fence extraction traffic
        rows7, br, bt = read_slot_rows([self.carry_b[7]],
                                       local_only=per_host)
        elapsed_ms = (time.perf_counter() - self.t0) * 1000.0
        steps_run = int(np.asarray(self.steps))
        s._mark_ready(self.mega_key)
        rest = [x for k, x in enumerate(self.carry_b) if k != 7]
        if self.track:
            rest.append(self.ys_b)
        rows_rest, br2, bt2 = read_slot_rows(rest, local_only=per_host)
        self.fence_bytes_read = br + br2
        self.fence_bytes_total = bt + bt2
        if per_host and self.registry is not None:
            from ..metrics import MULTIHOST_FENCE_BYTES

            c = self.registry.counter(MULTIHOST_FENCE_BYTES)
            c.inc({"scope": "read"}, value=float(self.fence_bytes_read))
            c.inc({"scope": "whole"}, value=float(self.fence_bytes_total))
        carry_rows = list(rows_rest[:len(self.carry_b) - 1])
        carry_rows.insert(7, rows7[0])
        ys_rows = rows_rest[-1] if self.track else None
        lo, hi = self.owned_slots
        outputs: List[object] = []
        for i, e in enumerate(self.entries):
            r = e["r"]
            trace = r["trace"] or NULL_TRACE
            span = trace.record(
                "megabatch", self.t_starts[i], trace.now(),
                slot=i, slots=self.B_pad, occupied=self.B,
            )
            if not (lo <= i < hi):
                # another host's slot: this process holds no shard of it.
                # A typed, boxed per-slot outcome — the serving layer's
                # forwarding shim (parallel/forward.py) re-routes it to
                # the owning host over the fleet transport
                from ..parallel.forward import SlotNotOwned

                outputs.append(SlotNotOwned(
                    i, owners[i] if owners else -1))
                continue
            carry_i = tuple(x[i] for x in carry_rows)
            ys_i = ys_rows[i] if ys_rows is not None else None
            s._count_scan(r["st"], e["full_dims"] if e["full_nr"]
                          else e["est_dims"], int(carry_i[7]), steps_run,
                          span)
            try:
                retried = s._maybe_retry_exhausted(
                    carry_i, e["est_dims"], e["full_dims"], e["full_nr"],
                    r["raise_on_exhaust"],
                    lambda r=r: s.solve(
                        r["st"], existing_nodes=r["existing_nodes"],
                        max_nodes=r["max_nodes"],
                        track_assignments=r["track_assignments"],
                        mesh=self.mesh, full_nr=True,
                    ),
                )
            # ktlint: allow[KT005] per-slot boxed outcome: the exhausted
            # slot's exception is returned in its slot so batchmates still
            # get their results; the caller re-raises per request
            except Exception as err:
                outputs.append(err)
                continue
            if retried is not None:
                outputs.append(retried)
                continue
            with trace.span("extract", slot=i):
                outputs.append(s._extract(
                    r["st"], carry_i, ys_i, r["existing_nodes"], e["NE"],
                    elapsed_ms, elapsed_ms, trace,
                ))
        if owners is not None and self.registry is not None:
            from ..metrics import MULTIHOST_SLOTS
            from ..parallel.forward import SlotNotOwned

            n_foreign = sum(1 for o in outputs
                            if isinstance(o, SlotNotOwned))
            slots_c = self.registry.counter(MULTIHOST_SLOTS)
            slots_c.inc({"ownership": "foreign"}, value=float(n_foreign))
            slots_c.inc({"ownership": "owned"},
                        value=float(len(outputs) - n_foreign))
        self._outputs = outputs
        return outputs


_default_solver = TpuSolver()


def solve_tensors(st: SolveTensors, **kw) -> TpuSolveOutput:
    return _default_solver.solve(st, **kw)
