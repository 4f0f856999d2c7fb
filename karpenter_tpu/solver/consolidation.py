"""Batched consolidation what-ifs on the device.

The Go reference evaluates consolidation candidates one simulated scheduling
pass at a time (SURVEY.md §3.3); this module vectorizes the dominant
questions — "which single nodes could be deleted, with their pods absorbed by
the rest of the cluster?" and "which node *subsets* could be deleted
together?" — over EVERY candidate at once (SURVEY §7.6: "multi-node candidate
subsets on-TPU ... the big win vs the Go heuristic").

Formulation: for candidate subset S, greedily pack the union of S's pods
(largest first, same FFD key as the solvers) into the non-members' residual
capacity, honoring per-(source, target) label/taint compatibility.  One
``vmap`` over subsets of one ``lax.scan`` over padded pod slots; state is the
[N, R] residual matrix per subset.  Dense, regular, MXU/VPU-friendly — and
one device call for the whole screen.

The kernel is a single module-level jit over shape-bucketed arrays, so
steady-state controller reconciles hit the persistent jit cache instead of
recompiling (same pattern as solver/tpu.py's _run_scan).  The screen is
resource+compat only: topology constraints are NOT evaluated here, so the
deprovisioning controller exact-confirms every hit with the sequential
what-if before acting.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import (
    CONSOLIDATION_SWEEP_DURATION,
    CONSOLIDATION_SWEEP_SLOTS,
    CONSOLIDATION_SWEEPS,
    Registry,
)
from ..gang import nodes_carry_gangs
from ..models import labels as L
from ..obs.trace import NULL_TRACE
from .types import SimNode, SolveResult, node_classes

logger = logging.getLogger(__name__)

_RESOURCES = (L.RESOURCE_CPU, L.RESOURCE_MEMORY, L.RESOURCE_PODS)


@dataclass
class DeleteScreenResult:
    deletable: np.ndarray        # [N] bool — pods fit on other nodes
    n_candidates: int
    eval_ms: float
    compile_ms: float


@dataclass
class SubsetScreenResult:
    deletable: np.ndarray        # [K] bool — subset's pods fit on non-members
    n_subsets: int
    eval_ms: float
    compile_ms: float


def _ffd_key(p) -> float:
    return -(p.requests.get(L.RESOURCE_CPU, 0.0)
             + p.requests.get(L.RESOURCE_MEMORY, 0.0) / (4 * 1024.0**3))


def _bucket(n: int, q: int) -> int:
    return max(q, ((n + q - 1) // q) * q)


@jax.jit
def _screen_kernel(residual, member, pods, src, compat):
    """[K] bool: per subset, does a greedy first-fit place every pod of the
    member nodes onto compatible non-member residuals?"""

    def one_subset(member_k, pods_k, src_k):
        res0 = jnp.where(member_k[:, None], 0.0, residual)

        def place(res, args):
            pod, s = args
            ok_t = compat[s] & ~member_k
            fits = jnp.all(res + 1e-6 >= pod[None, :], axis=1) & ok_t
            any_fit = jnp.any(fits)
            idx = jnp.argmax(fits)
            is_real = jnp.any(pod > 0)
            deduct = jnp.where(is_real & any_fit, pod, 0.0)
            res = res.at[idx].add(-deduct)
            return res, jnp.where(is_real, any_fit, True)

        _, oks = jax.lax.scan(place, res0, (pods_k, src_k))
        return jnp.all(oks)

    return jax.vmap(one_subset)(member, pods, src)


# ktlint: fence the screen IS the sync point — one dispatch + one D2H read
# whose result gates which candidates enter the sweep; the deprovisioning
# tick blocks on it by design (KT013: the fence bounds the whole screen)
def screen_subset_deletes(
    nodes: Sequence[SimNode],
    subsets: Sequence[Sequence[int]],   # K subsets of node indices
    compat: Optional[np.ndarray] = None,
    pmax_total: int = 128,
    measure: bool = False,
) -> SubsetScreenResult:
    """One device call: for every candidate subset, can the union of its
    members' pods fit on the non-members' residual capacity?

    Pods carry their source-node index so ``compat`` stays per-(source,
    target).  Subsets whose pod union exceeds ``pmax_total`` are
    conservatively marked undeletable.  With ``measure=True`` the kernel runs
    twice to split compile_ms from steady-state eval_ms (benchmarks); the
    default single run is what control loops want.
    """
    t0 = time.perf_counter()
    N = len(nodes)
    K = len(subsets)
    R = len(_RESOURCES)
    # shape bucketing -> persistent jit-cache hits across reconciles
    NP_ = _bucket(N, 256)
    KP = _bucket(K, 8)

    residual = np.zeros((NP_, R), dtype=np.float32)
    for i, n in enumerate(nodes):
        rem = n.remaining()
        residual[i] = [max(0.0, rem.get(r, 0.0)) for r in _RESOURCES]

    member = np.zeros((KP, NP_), dtype=bool)
    pods_mat = np.zeros((KP, pmax_total, R), dtype=np.float32)
    pods_src = np.zeros((KP, pmax_total), dtype=np.int32)
    overflow = np.zeros(KP, dtype=bool)
    pods_ridx = _RESOURCES.index(L.RESOURCE_PODS)
    for k, subset in enumerate(subsets):
        member[k, list(subset)] = True
        entries = [(_ffd_key(p), i, p) for i in subset for p in nodes[i].pods]
        if len(entries) > pmax_total:
            overflow[k] = True
            continue
        entries.sort(key=lambda e: e[0])
        for j, (_, i, p) in enumerate(entries):
            for r, name in enumerate(_RESOURCES):
                pods_mat[k, j, r] = p.requests.get(name, 0.0)
            pods_mat[k, j, pods_ridx] = 1.0
            pods_src[k, j] = i

    cm = np.zeros((NP_, NP_), dtype=bool)
    if compat is None:
        cm[:N, :N] = True
    else:
        cm[:N, :N] = compat

    args = (jnp.asarray(residual), jnp.asarray(member), jnp.asarray(pods_mat),
            jnp.asarray(pods_src), jnp.asarray(cm))
    # the fence is the D2H read of the (tiny) result: the caller needs the
    # verdicts on the host, and the read cannot complete before the kernel
    out_host = np.asarray(_screen_kernel(*args))
    first_ms = (time.perf_counter() - t0) * 1000.0
    if measure:
        # median of 3 timed re-runs on the same device-resident inputs,
        # outputs discarded (a local PJRT client executes every dispatch)
        times = []
        for _ in range(3):
            t1 = time.perf_counter()
            np.asarray(_screen_kernel(*args))
            times.append((time.perf_counter() - t1) * 1000.0)
        eval_ms = sorted(times)[1]
        compile_ms = first_ms
    else:
        eval_ms, compile_ms = first_ms, 0.0

    return SubsetScreenResult(
        deletable=out_host[:K] & ~overflow[:K],
        n_subsets=K, eval_ms=eval_ms, compile_ms=compile_ms,
    )


def screen_delete_candidates(
    nodes: Sequence[SimNode],
    compat: Optional[np.ndarray] = None,
    pmax: int = 64,
    measure: bool = False,
) -> DeleteScreenResult:
    """Single-node screen = the subset screen over all singletons.  A
    candidate's own capacity never counts (it is the deleted node)."""
    if compat is not None:
        compat = compat.copy()
        np.fill_diagonal(compat, False)
    else:
        compat = ~np.eye(len(nodes), dtype=bool)
    res = screen_subset_deletes(
        nodes, [[i] for i in range(len(nodes))], compat,
        pmax_total=pmax, measure=measure,
    )
    return DeleteScreenResult(
        deletable=res.deletable, n_candidates=len(nodes),
        eval_ms=res.eval_ms, compile_ms=res.compile_ms,
    )


def compat_matrix(
    nodes: Sequence[SimNode],
    sources: Optional[Sequence[int]] = None,
) -> np.ndarray:
    """Host-side label/taint compatibility: pods of node i can run on node j.

    ``sources`` limits the computed rows to those node indices (the screen
    only reads rows for member/candidate nodes) — O(|sources| * N) string
    work instead of O(N^2); uncomputed rows stay False.  Conservative: every
    pod of i must tolerate j's taints and have its node-selector satisfied by
    j's labels (full requirement algebra — the exact sequential what-if
    re-verifies anything the screen admits).
    """
    N = len(nodes)
    src = range(N) if sources is None else sources
    out = np.zeros((N, N), dtype=bool)

    # The naive O(|sources| x N x pods) requirement-algebra walk repeats the
    # same few questions millions of times at 5k nodes (~100 s of the 5k
    # consolidation reconcile).  Two-level memo instead:
    #  - a POD SIGNATURE is exactly what node-compat depends on — the pod's
    #    effective requirement set (node_selector + required affinity term
    #    0) plus its tolerations.  Requests/labels/owner do NOT widen it
    #    (group_key would: unique requests -> unique keys -> no dedup).
    #  - a DESTINATION CLASS is the node's taints plus only the label keys
    #    any source pod's requirements actually reference — a unique
    #    per-node hostname label must not split an otherwise uniform fleet
    #    into N classes when nothing selects on hostname.
    # The 5k bench fleet asks 1 question instead of 87M.
    pod_sig: Dict[int, tuple] = {}        # id(pod) -> signature
    sig_reqs: Dict[tuple, object] = {}    # signature -> Requirements
    relevant_keys: set = set()
    for i in src:
        for p in nodes[i].pods:
            reqs = p.scheduling_requirements()[0]
            # Requirements.signature() is the lossless structural key
            # (to_list()'s canonical operator form would collide
            # [Exists(k), NotIn(k,{x})] with [NotIn(k,{x})])
            key = (reqs.signature(), tuple(p.tolerations))
            pod_sig[id(p)] = key
            if key not in sig_reqs:
                sig_reqs[key] = reqs
                relevant_keys.update(reqs)

    cls_idx, class_rep = node_classes(nodes, relevant_keys)
    dst_class = np.asarray(cls_idx, dtype=np.int64)
    n_cls = len(class_rep)

    sig_cls_ok: Dict[tuple, np.ndarray] = {}  # signature -> [n_cls] bool

    def sig_ok_row(key: tuple) -> np.ndarray:
        row = sig_cls_ok.get(key)
        if row is None:
            reqs = sig_reqs[key]
            tols = key[1]  # the signature's second element IS the tolerations
            row = np.zeros(n_cls, dtype=bool)
            for c, dst in enumerate(class_rep):
                row[c] = (
                    not any(t.blocks(tols) for t in dst.taints)
                    and reqs.compatible(dst.labels) is None
                )
            sig_cls_ok[key] = row
        return row

    for i in src:
        node_i = nodes[i]
        if not node_i.pods:
            out[i, :] = True
            out[i, i] = False
            continue
        ok_cls = np.ones(n_cls, dtype=bool)
        for p in node_i.pods:
            ok_cls &= sig_ok_row(pod_sig[id(p)])
            if not ok_cls.any():
                break
        out[i] = ok_cls[dst_class]
        out[i, i] = False
    return out


# ---------------------------------------------------------------------------
# one-dispatch consolidation what-if sweeps
# ---------------------------------------------------------------------------
#
# The deprovisioning controller used to pay one full scheduler round trip per
# candidate what-if ("can this node's pods fit on the rest of the cluster
# plus at most one new node?") — N candidates, N dispatches, N fences.  Every
# candidate's what-if is a PERTURBATION of one base solution (the cluster
# with all nodes active): same catalog tensors, same existing-node state,
# only the member rows and the displaced pods differ.  The sweep exploits
# that: ONE shared host-array build of the base cluster, per-candidate
# derivations (deactivate the member rows, subtract their selector/limit
# contributions, swap in the candidate's counts), and ONE vmapped device
# dispatch + ONE fence for the whole sweep via TpuSolver.solve_many_prepared
# (the megabatch path of solver/tpu.py).
#
# Exactness contract: a slot whose device answer is anything but a clean
# "all pods fit on the survivors, no new node" is re-solved through the
# serial scheduler path (full relaxation/residue/reseat ladder), so sweep
# decisions are identical to the sequential what-if loop; per-slot boxed
# exceptions keep one poisoned candidate from failing its batchmates.  The
# sweep's vmapped program compiles behind (TpuSolver.warm_custom) — the
# first sweeps of a shape serve serially, never stalling a reconcile on XLA.

#: sweep candidates per vmapped dispatch (chunked above this)
SWEEP_MAX_SLOTS = 16


@dataclass
class SweepOutcome:
    """One consolidation what-if sweep: per-candidate results IN ORDER —
    a SolveResult, the Exception that candidate alone raised, or None for
    slots past a ``stop_on`` early exit (never evaluated)."""

    results: List[object]
    path: str                # "batched" | "serial" | "mixed"
    wall_ms: float
    n_batched: int = 0
    n_serial: int = 0
    dispatches: int = 0      # vmapped device dispatches (fences) paid


#: sweep execution paths — the zero-inited label population of
#: karpenter_solver_consolidation_sweeps_total (KT003)
SWEEP_PATHS = ("batched", "mixed", "serial")


def zero_init_sweep_metrics(registry: Registry) -> None:
    """Register the sweep series at 0 (KT003)."""
    for path in SWEEP_PATHS:
        if not registry.counter(CONSOLIDATION_SWEEPS).has({"path": path}):
            registry.counter(CONSOLIDATION_SWEEPS).inc(
                {"path": path}, value=0.0)
    registry.histogram(CONSOLIDATION_SWEEP_SLOTS)
    registry.histogram(CONSOLIDATION_SWEEP_DURATION)


def sweep_dims(st, NE: int, node_budget: int, track: bool = False) -> dict:
    """What-if-sized padded dims: the standard :func:`tpu.solve_dims`
    bucketing with FINE small-solve rungs on the G and NR axes.  A what-if
    places a handful of groups against a known node count; the serving-path
    rungs (G quantum 16, NR floor 512) would run the scan at 4-8x the
    state the sweep needs.  Confined to the sweep's own compile ladder —
    serving-path signatures are untouched."""
    from .tpu import _rung, solve_dims

    dims = solve_dims(st, NE=NE, node_budget=node_budget, track=track,
                      full_nr=True)
    if st.G <= 16:
        dims["G"] = _rung(st.G, 4, 16)
    if node_budget <= 512:
        dims["NR"] = _rung(max(1, node_budget), 64, 512)
    return dims


def sweep_signature(st, dims: dict, slots: int, mesh=None) -> tuple:
    """Compile signature of the sweep's vmapped program at a slot rung —
    the key TpuSolver readiness/warm bookkeeping tracks for it.  With a
    ``mesh``, the SHARDED sweep program: slot rung floored at the device
    count, mesh fingerprint in the key (the shared ``_mega_key_tail``
    format ``_dispatch_prepared`` keys dispatches with)."""
    from .tpu import _dims_key, _mega_key_tail

    return _dims_key(dims) + _mega_key_tail(
        slots, st.vocab.key_id[L.ZONE], st.vocab.key_id[L.CAPACITY_TYPE],
        mesh,
    )


def build_sweep_entries(
    solver,
    sts: Sequence[object],
    all_nodes: Sequence[SimNode],
    members: Sequence[Sequence[int]],
    dims: dict,
    node_budget: int,
    trace=None,
) -> List[dict]:
    """Derive one megabatch entry per candidate from ONE shared base build.

    Every candidate's what-if shares the base cluster's host arrays
    (residuals, compat, selector counts, provisioner usage over ALL nodes);
    a candidate differs only by (a) its member node rows being deactivated
    — an inactive row can never receive pods, which is exactly "this node
    is deleted" — (b) its members' selector/zone/provisioner contributions
    subtracted from the seeded counters, and (c) its own pods' counts
    tensors.  All ``sts`` must share one group structure (the shape-tier
    tensorize guarantee the caller groups by) and one ``dims`` bucket.
    """
    from .tpu import host_count_arrays

    st0 = sts[0]
    N = len(all_nodes)
    track = bool(dims["track"])
    np_consts0, feas0, np_init0, _ = solver._host_arrays(
        st0, all_nodes, node_budget=node_budget,
        track_assignments=track, full_nr=True, dims=dims,
    )
    (ex_res, ex_zone, row_dom, row_cand, ex_price, ex_sel, active0,
     n_used0, zc0, tot0, prov_used0, infeas0) = np_init0
    pad_g = dims["G"] - st0.G
    Z = dims["Z"]
    prov_index = {n: i for i, n in enumerate(st0.prov_names)}

    entries: List[dict] = []
    for st_k, member in zip(sts, members):
        counts, _req, suffix_res, suffix_cnt = host_count_arrays(
            st_k, pad_g, Z)
        consts_k = dict(np_consts0, counts=counts, suffix_res=suffix_res,
                        suffix_cnt=suffix_cnt)
        active = active0.copy()
        zc = zc0.copy()
        tot = tot0.copy()
        prov_used = prov_used0.copy()
        for idx in member:
            active[idx] = False
            sel_row = ex_sel[idx]
            if sel_row.size:
                zc[:, ex_zone[idx]] -= sel_row
                tot -= sel_row
            node = all_nodes[idx]
            pi = prov_index.get(node.provisioner)
            if pi is not None:
                prov_used[pi] = prov_used[pi] - st0.capacity_row(
                    node.instance_type, node.allocatable)
        init_k = (ex_res, ex_zone, row_dom, row_cand, ex_price, ex_sel,
                  active, n_used0, zc, tot, prov_used, infeas0)
        entries.append(dict(
            r=dict(st=st_k, existing_nodes=(), max_nodes=node_budget,
                   track_assignments=track, raise_on_exhaust=False,
                   trace=trace or NULL_TRACE),
            np_consts=consts_k, feas=feas0, np_init=init_k, dims=dims,
            est_dims=dims, full_dims=dims, full_nr=True, NE=N,
        ))
    return entries


# ktlint: fence the warm thunk's D2H read is the deliberate compile+fence of
# the background sweep-program warm (discarded results, warm thread only)
def _warm_sweep(solver, entries: List[dict], slots: int, sig: tuple,
                mesh=None) -> None:
    """Background-compile the sweep's vmapped program — the SHARDED one for
    a meshed scheduler (compile-behind: the serving sweep never stalls on
    XLA)."""

    def thunk():
        from .tpu import read_slot_rows

        pending = solver.solve_many_prepared(entries, min_slots=slots,
                                             mesh=mesh)
        # fence: the compile has landed.  Through the addressable-shard
        # accessor (KT018): on a multi-process mesh the warm thread owns
        # only its local shards — a whole-batch read would crash (and
        # pay DCN) for a result it discards anyway
        read_slot_rows([pending.carry_b[7]], local_only=mesh is not None)
        solver._mark_ready(sig)

    solver.warm_custom(sig, thunk)


def sweep_what_ifs(
    scheduler,
    all_nodes: Sequence[SimNode],
    candidates: Sequence[Sequence[int]],
    *,
    provisioners,
    instance_types,
    daemonsets: Sequence = (),
    unavailable=None,
    max_new: int = 1,
    registry: Optional[Registry] = None,
    trace=None,
    stop_on=None,
) -> SweepOutcome:
    """Evaluate every candidate's what-if ("delete these nodes; do their
    pods fit on the rest plus at most ``max_new`` new nodes?") — batched as
    slots of one vmapped device dispatch when the device path is warm,
    serially through ``scheduler.solve`` otherwise.  ``candidates`` are
    node-index subsets of ``all_nodes``.  Results are in candidate order;
    decisions are identical to the sequential what-if loop by construction
    (non-clean slots re-solve serially).

    ``stop_on(k, result)`` — optional early exit for the SERIAL fill, for
    callers that take the first confirming candidate in order (the loop
    this sweep replaced stopped there too): evaluated on every slot in
    candidate order — batched and serial alike — and once it returns True
    the remaining unresolved slots stay ``None`` instead of paying a full
    what-if solve each for answers the caller will never read.  Batched
    slots themselves always resolve (they arrive together in the one
    dispatch, already paid for)."""
    t0 = time.perf_counter()
    registry = registry or scheduler.registry
    zero_init_sweep_metrics(registry)
    trace = trace or NULL_TRACE
    from ..models.tensorize import batch_needs_oracle, device_inexpressible
    from .scheduler import _harden_preferences
    from .tpu import _dims_key

    K = len(candidates)
    results: List[object] = [None] * K
    n_batched = n_serial = dispatches = 0

    def serial_one(k: int) -> object:
        member = set(candidates[k])
        others = [n for j, n in enumerate(all_nodes) if j not in member]
        pods = [p for idx in candidates[k]
                for p in all_nodes[idx].pods if not p.is_daemon]
        try:
            return scheduler.solve(
                pods, provisioners, instance_types, existing_nodes=others,
                daemonsets=daemonsets, unavailable=unavailable,
                allow_new_nodes=True, max_new_nodes=max_new,
                trace=trace,
            )
        # ktlint: allow[KT005] per-candidate boxed outcome: one poisoned
        # what-if must not fail the sweep's batchmates; the controller
        # re-raises or skips per candidate
        except Exception as err:  # noqa: BLE001
            return err

    # whole-sweep device eligibility; per-candidate carve-outs below.
    # Meshed schedulers sweep SHARDED (slot axis over the mesh's chips,
    # one dispatch + one fence, same as single-device); only a mesh whose
    # device count exceeds the slot-rung ladder keeps the serial path —
    # explicitly metriced via the existing path="serial" label.
    from .tpu import mesh_shardable

    mesh = scheduler.mesh
    device_ok = (
        scheduler.backend in ("auto", "tpu")
        and mesh_shardable(mesh)
        and (scheduler.backend == "tpu" or not scheduler._guard.enabled
             or scheduler._guard.healthy)
    )

    N = len(all_nodes)
    node_budget = N + (max_new if max_new is not None else 0)
    buckets: Dict[tuple, List[int]] = {}
    prepared: Dict[int, tuple] = {}   # k -> (st, dims, skey)
    if device_ok:
        for k in range(K):
            pods = [p for idx in candidates[k]
                    for p in all_nodes[idx].pods if not p.is_daemon]
            if not pods:
                # empty candidate: trivially deletable, same as the serial
                # scheduler.solve([]) answer
                results[k] = SolveResult(nodes=[], assignments={},
                                         infeasible={})
                continue
            if nodes_carry_gangs([all_nodes[i] for i in candidates[k]]):
                # gang what-ifs re-seat the ENTIRE gang or the candidate
                # fails (ISSUE 20): only the serial path's gang epilogue
                # audits that (preseated-comember counting, typed
                # retraction) — the vmapped slot answer has no epilogue
                continue
            try:
                hardened = [_harden_preferences(p) for p in pods]
                if (batch_needs_oracle(hardened)
                        or any(device_inexpressible(p) for p in hardened)):
                    continue  # oracle-coupled shapes: serial path
                st, _tier = scheduler._tensorize_cache.tensorize(
                    hardened, provisioners, instance_types,
                    daemonsets=daemonsets, unavailable=unavailable,
                )
                dims = sweep_dims(st, N, node_budget)
                skey = tuple(g.key for g in st.groups)
                bkey = (_dims_key(dims), st.vocab.key_id[L.ZONE],
                        st.vocab.key_id[L.CAPACITY_TYPE])
                prepared[k] = (st, dims, skey)
                buckets.setdefault(bkey, []).append(k)
            # ktlint: allow[KT005] an unbatchable candidate just solves on
            # the serial path, where a real error surfaces with context
            except Exception:  # noqa: BLE001
                logger.debug("sweep candidate %d not batchable; serial",
                             k, exc_info=True)

    solver = scheduler._tpu if device_ok else None
    for bkey, idxs in buckets.items():
        for lo in range(0, len(idxs), SWEEP_MAX_SLOTS):
            chunk = idxs[lo:lo + SWEEP_MAX_SLOTS]
            st0, dims, _ = prepared[chunk[0]]
            sig = sweep_signature(st0, dims, len(chunk), mesh=mesh)
            if not solver.ready(sig) and solver.warm_pending(sig):
                # compile-behind already in flight: this sweep serves
                # serially anyway, so skip the shared-base host build
                # (entries are only needed to dispatch or to SEED a warm)
                continue
            # one base build per group structure within the chunk
            by_skey: Dict[tuple, List[int]] = {}
            for k in chunk:
                by_skey.setdefault(prepared[k][2], []).append(k)
            entry_of: Dict[int, dict] = {}
            for ks in by_skey.values():
                entries = build_sweep_entries(
                    solver, [prepared[k][0] for k in ks], all_nodes,
                    [candidates[k] for k in ks], prepared[ks[0]][1],
                    node_budget, trace=trace,
                )
                for k, e in zip(ks, entries):
                    entry_of[k] = e
            chunk_entries = [entry_of[k] for k in chunk]
            if not solver.ready(sig):
                # compile-behind: serve this sweep serially, warm the
                # vmapped program in the background
                _warm_sweep(solver, chunk_entries, len(chunk), sig,
                            mesh=mesh)
                continue
            try:
                with trace.span("sweep_dispatch", slots=len(chunk)):
                    outs = solver.solve_many_prepared(
                        chunk_entries, min_slots=len(chunk),
                        mesh=mesh).results()
            # ktlint: allow[KT005] a failed sweep dispatch degrades the
            # whole chunk to the proven serial path (decisions unchanged)
            except Exception:  # noqa: BLE001
                logger.warning("sweep dispatch failed; chunk served "
                               "serially", exc_info=True)
                continue
            dispatches += 1
            registry.histogram(CONSOLIDATION_SWEEP_SLOTS).observe(len(chunk))
            for k, out in zip(chunk, outs):
                if isinstance(out, BaseException):
                    continue  # serial below (boxed per-slot degrade)
                res = out.result
                if res.infeasible or res.nodes:
                    # not a clean "fits on the survivors" answer: the
                    # serial path's repair ladder (residue waves, reseat,
                    # replacement sizing) must judge it — exact parity
                    continue
                results[k] = res
                n_batched += 1

    for k in range(K):
        if results[k] is None:
            results[k] = serial_one(k)
            n_serial += 1
        # evaluated on EVERY slot in candidate order — batched slots too,
        # so a dispatch-confirmed early candidate stops the serial fill
        # before it pays for later unbatchable ones the caller won't read
        if stop_on is not None and stop_on(k, results[k]):
            break

    wall_ms = (time.perf_counter() - t0) * 1000.0
    # "serial" means serial FALLBACKS ran — a sweep resolved entirely by
    # pre-dispatch shortcuts (no solve on either path) stays "batched" so
    # the serial-fallback rate only counts real degradation
    path = ("serial" if n_serial and not n_batched
            else "mixed" if n_serial else "batched")
    registry.counter(CONSOLIDATION_SWEEPS).inc({"path": path})
    registry.histogram(CONSOLIDATION_SWEEP_DURATION).observe(wall_ms / 1000.0)
    return SweepOutcome(results=results, path=path, wall_ms=wall_ms,
                        n_batched=n_batched, n_serial=n_serial,
                        dispatches=dispatches)
