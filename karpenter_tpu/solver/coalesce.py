"""Cost-neutral node coalescing — merge small new nodes into larger types.

The scan-over-groups solver buys each group's tail residue at that group's
step, so two groups can each buy a half-size node where the sequential
oracle's pod-interleaved first-fit would have filled one larger node
(BASELINE config 5: +24 mid-size nodes at equal-or-lower $).  Node count is
real operational load — kubelet/API traffic, image pulls, ENI/IP slots,
interruption exposure — so after extraction the solver merges same-
(provisioner, zone, capacity-type) NEW nodes into one larger catalog type
whenever:

- the larger type's allocatable fits the combined used resources (including
  the pod-density row) and every group on either node admits it, and
- its price is <= the sum of the replaced nodes' prices (NEVER spends $ —
  in-family pricing is linear, so 2x 4xlarge -> 1x 8xlarge is exact), and
- the provisioner either has no finite limits or the replacement's raw
  capacity does not exceed the replaced capacity (limits bind on capacity),
  and
- on every hostname slot either node's groups cap (anti-affinity, spread
  maxSkew), the COMBINED count of matching pods stays within the stricter
  cap.  Untracked solves (no per-node groups) with any hostname-scoped
  constraint skip the pass; zone-scoped constraints are safe, a merge keeps
  the zone.

The merge rule, per bucket: keep the nodes in order — smallest first, and
in hostname-capped solves round-robin across group combinations — look at
the FRAG_WINDOW first of them, take the first pair (smallest i, then
smallest j > i) that some candidate can hold, replace both by the cheapest
such candidate, put it back in order, look again; stop when no pair of the
window merges.  Greedy and deterministic.  How it is held: a bucket's nodes
are rows 0..n-1 of dense arrays (used resources, price, candidate
feasibility, raw capacity, hostname counts and caps) and every merge writes
ONE new row (sum / AND / min of two); a pair's verdict — its cheapest
candidate, or none — is computed once, when the later of the two first
enters the window, for all pairs new to the window in one broadcast over
the candidates in price order (less those an earlier one beats on every
count), and lives in a matrix addressed by the two nodes' window slots, so
the first hit is an ``argmax`` over its upper triangle (a node the capped
order pushes out of the window keeps its slot, and on its way back is not
asked again about nodes that came in meanwhile: the rule of the loop this
replaced, kept so that the answers are its answers); the order is a sorted list of ``(rank, size, name)`` keys kept by
bisection, where a merge re-ranks only the later members of the
combinations it takes from and adds to.  Merged nodes are rows and a name
until the bucket is done: only the survivors become ``SimNode`` objects.

``TpuSolver._extract`` wraps the pass in a ``coalesce`` span (``nodes_in``,
``nodes_out``, ``merges``, ``buckets``) and counts it in
``karpenter_solver_coalesce_total{what="nodes_in"|"merges"}``.
"""

from __future__ import annotations

from bisect import bisect_left, insort
from collections import Counter
from itertools import chain
from typing import Dict, List, Optional, Tuple

import numpy as np

from .types import SimNode, next_node_name

#: prov_limits entries at/above this are "no limit" sentinels
_NO_LIMIT = 3.0e37
#: pair scan covers only this many smallest nodes per bucket (fragments
#: cluster at the small end; bounds host time on large solves)
FRAG_WINDOW = 64


def label_feasibility(st) -> np.ndarray:
    """Host-side [G, C] label/provisioner feasibility — the numpy mirror of
    the device precompute (tpu.compute_feasibility's gather branch): group g's
    packed requirement mask admits candidate c's label values, and the
    group tolerates/fits the candidate's provisioner.  Merge targets must be
    feasible for every group with pods on the merged node — the solve
    honored F, coalescing must too (a node_selector pinned to one instance
    type must never be merged onto another).  Cached on the tensors."""
    cached = getattr(st, "_host_F", None)
    if cached is not None:
        return cached
    pm = np.asarray(st.pm)                    # [G, K, W] uint32
    vw = np.asarray(st.cand_vw)               # [C, K]
    vb = np.asarray(st.cand_vb).astype(np.uint32)
    kc = np.asarray(st.key_check)             # [K]
    G, K, _W = pm.shape
    C = vw.shape[0]
    lab = np.ones((G, C), dtype=bool)
    for k in range(K):
        if not kc[k]:
            continue
        words = pm[:, k, :][:, vw[:, k]]      # [G, C]
        lab &= ((words >> vb[None, :, k]) & 1).astype(bool)
    gp_ok = np.asarray(st.gp_ok)
    lab &= gp_ok[np.arange(G)[:, None], np.asarray(st.cand_prov)[None, :]]
    st._host_F = lab
    return lab


def hostname_constrained(st) -> bool:
    """Any group whose constraints are scoped to individual nodes — merging
    nodes could violate them, so coalescing is skipped for the whole solve
    when per-node group tracking is unavailable."""
    return bool(
        (np.asarray(st.g_host_spread) >= 0).any()
        or (np.asarray(st.g_host_paff) >= 0).any()
        or (np.asarray(st.g_host_cap) > 0).any()
    )


def _domain_index(st, zone: str, ct: str) -> Optional[int]:
    try:
        zi = st.zone_names.index(zone)
        ci = st.ct_names.index(ct)
    except ValueError:
        return None
    return zi * max(1, len(st.ct_names)) + ci


def apply_coalesce(st, nodes, used_rows, node_groups, assignments, span=None):
    """Shared tier epilogue: run the merge pass and repoint assignments of
    absorbed nodes at their replacements.  Both the device tier
    (tpu._extract) and the native tier (native.solve_tensors_native) call
    this so the cold-start answer and the warm answer stay the same
    coalescing contract.  ``span``, where given, is told what the pass did."""
    n_in, buckets = len(nodes), 0
    if n_in >= 2:
        nodes, renames, buckets = coalesce_new_nodes(
            st, nodes, used_rows, node_groups=node_groups)
        if renames:
            for pod_name, node_name in list(assignments.items()):
                if node_name in renames:
                    assignments[pod_name] = renames[node_name]
    if span is not None:
        span.annotate(nodes_in=n_in, nodes_out=len(nodes),
                      merges=n_in - len(nodes), buckets=buckets)
    return nodes


def coalesce_new_nodes(
    st,
    nodes: List[SimNode],
    used_rows: Dict[int, np.ndarray],  # id(node) -> used resource row [R]
    node_groups: Optional[Dict[int, set]] = None,  # id(node) -> {group idx}
) -> Tuple[List[SimNode], Dict[str, str], int]:
    """Merge mergeable new nodes; returns (new node list, renames, buckets)
    where ``renames`` maps absorbed old node names -> their replacement's
    name and ``buckets`` counts the (provisioner, zone, capacity-type)
    buckets the nodes fell in.  Every merge takes one node off the list.
    Pods are moved onto the replacement nodes; callers fix assignments via
    the rename map.  ``node_groups`` scopes the label-feasibility check to
    the groups actually placed on each node; without it (untracked solves)
    the merge target must be feasible for EVERY group in the solve."""
    # untracked solves can't scope the check per node: all-or-nothing
    if node_groups is None and hostname_constrained(st):
        return nodes, {}, 0
    # per-node hostname bookkeeping for capped solves: a merge is legal when,
    # for every hostname slot either node's groups cap, the COMBINED count of
    # slot-matching pods stays within the stricter cap (anti-affinity
    # cap 1/0, spread maxSkew).  Group labels are uniform, so counts come
    # from g_sel_match at group granularity — no per-pod selector matching.
    # This is what lets bench config 3 (every pod hostname-anti) coalesce its
    # 1-pod-per-service fragments into shared nodes at equal-or-lower price.
    # Positive hostname affinity (g_host_paff) needs no cap: it wants
    # matching pods together, and merging only ever ADDS co-residents (fuzz
    # seed 23: one paff group used to disable coalescing for the whole solve).
    g_hs = np.asarray(st.g_host_spread)
    g_hc = np.asarray(st.g_host_cap, dtype=np.float64)
    sel = np.asarray(st.g_sel_match)
    host_active = node_groups is not None and bool((g_hs >= 0).any())
    pod_group: Dict[str, int] = {}
    if host_active:
        for gi, g in enumerate(st.groups):
            for p in g.pods:
                pod_group[p.name] = gi
    F = label_feasibility(st)                             # [G, C]
    G = F.shape[0]
    F_distinct = np.array(list({row.tobytes(): row for row in F}.values()))
    all_groups = frozenset(range(G))
    R = np.asarray(st.cand_alloc).shape[1]

    by_prov: Dict[str, List[int]] = {}
    for ci, (prov, _it) in enumerate(st.cand_names):
        by_prov.setdefault(prov, []).append(ci)
    prov_index = {n: i for i, n in enumerate(st.prov_names)}

    buckets: Dict[tuple, List[SimNode]] = {}
    for n in nodes:
        buckets.setdefault((n.provisioner, n.zone, n.capacity_type), []).append(n)

    out: List[SimNode] = []
    renames: Dict[str, str] = {}
    for (prov, zone, ct), group in buckets.items():
        di = _domain_index(st, zone, ct)
        pi = prov_index.get(prov)
        cands = by_prov.get(prov, [])
        if di is None or pi is None or len(group) < 2 or not cands:
            out.extend(group)
            continue
        limited = bool((np.asarray(st.prov_limits)[pi] < _NO_LIMIT).any())
        # bucket-local candidate table (spot pricing is NOT linear in size —
        # zonal discounts vary per type — so the cheapest feasible
        # replacement can come from any family), in price order: the
        # cheapest feasible candidate is the FIRST feasible one, and a pair
        # only has to look at the candidates its two prices can pay for
        cand_ix = np.asarray([ci for ci in cands if st.cand_avail[ci, di]],
                             dtype=np.int64)
        if cand_ix.size == 0:
            out.extend(group)
            continue
        cand_ix = cand_ix[np.argsort(np.asarray(st.cand_price)[cand_ix, di],
                                     kind="stable")]
        # (float32 tables, compared with float64 rows: widened once, here)
        c_price = np.asarray(st.cand_price)[cand_ix, di].astype(np.float64)
        c_room = (np.asarray(st.cand_alloc)[cand_ix] + 1e-6   # [R, K]
                  ).T.astype(np.float64)
        c_cap = np.ascontiguousarray(np.asarray(st.cand_cap)[cand_ix].T)
        c_F = F[:, cand_ix]                                   # [G, K]
        # a candidate is never the first feasible one where an earlier one
        # has at least its room, at most its capacity and every group that
        # admits it: such candidates leave the table (425 -> ~50 types) —
        # where the first window alone holds more pairs than the table has
        # candidates; under that the pruning costs more than it saves
        n = len(group)
        if min(n, FRAG_WINDOW) ** 2 > 2 * cand_ix.size:
            admits = F_distinct[:, cand_ix].astype(np.float32)
            beaten = np.triu((1.0 - admits).T @ admits == 0, 1)  # [earlier, k]
            for r in range(R):
                beaten &= c_room[r][:, None] >= c_room[r]
                if limited:
                    beaten &= c_cap[r][:, None] <= c_cap[r]
            keep = ~beaten.any(axis=0)
            cand_ix, c_price, c_F = cand_ix[keep], c_price[keep], c_F[:, keep]
            c_room, c_cap = c_room[:, keep], c_cap[:, keep]

        # the bucket's dense state: rows 0..n-1 are the scan's nodes as they
        # arrive, every merge appends one (2n-1 at most)
        K, N = cand_ix.size, 2 * n - 1
        names = [x.name for x in group]
        used = np.empty((N, R))
        used[:n] = [used_rows[id(x)] for x in group]
        size = used[:n].sum(axis=1).tolist()              # the order's key
        price = np.empty(N)
        price[:n] = [x.price for x in group]
        # candidate feasibility: AND over the node's groups (c_F[union].all
        # == c_F[a].all & c_F[b].all, so a merged row is an AND of two)
        feas = np.empty((N, K), dtype=bool)
        if node_groups is None:
            feas[:n] = c_F.all(axis=0)
        else:
            # one reduceat over every node's rows; row G (all true) closes
            # each segment so none is empty, row G+1 is a node the caller
            # did not track: every group
            rows = np.vstack([c_F, np.ones((1, K), dtype=bool),
                              c_F.all(axis=0)[None]])
            segs = [(*gs, G) if gs is not None else (G + 1,)
                    for gs in (node_groups.get(id(x)) for x in group)]
            starts = np.cumsum([0] + [len(s) for s in segs[:-1]])
            feas[:n] = np.logical_and.reduceat(
                rows[np.fromiter(chain.from_iterable(segs), np.intp)],
                starts, axis=0)
        if limited:
            cap = np.empty((N, R), dtype=np.float32)
            cap[:n] = [st.capacity_row(x.instance_type, x.allocatable)
                       for x in group]
        if host_active:
            # (counts[S], caps[S]) per node; caps inf where unconstrained
            hcnt = np.zeros((N, sel.shape[0]), dtype=np.int64)
            hcap = np.full((N, sel.shape[0]), np.inf)
            for x, node in enumerate(group):
                took = Counter(pod_group.get(p.name) for p in node.pods)
                if None in took:
                    # a pod outside this solve (shouldn't happen for new
                    # nodes): be conservative, forbid merging this node
                    hcap[x] = -1.0
                    continue
                gs = np.fromiter(took, np.intp, len(took))
                hcnt[x] = sel[:, gs] @ np.fromiter(took.values(), np.int64,
                                                   len(took))
                capping = gs[g_hs[gs] >= 0]
                np.minimum.at(hcap[x], g_hs[capping], g_hc[capping])

        def verdicts(x: np.ndarray, y: np.ndarray) -> np.ndarray:
            """The cheapest candidate that can replace nodes x[p] and y[p]
            together, -1 where none can.  Symmetric, and unaffected by other
            merges: asked once per pair."""
            pay = price[x] + price[y]
            top = max(1, int(np.searchsorted(c_price, pay.max() + 1e-9,
                                             side="right")))
            need = used[x] + used[y]                              # [P, R]
            ok = feas[x, :top] & feas[y, :top]                    # [P, top]
            for r in range(R):
                ok &= c_room[r, :top] >= need[:, r, None]
            ok &= c_price[:top] <= pay[:, None] + 1e-9
            if limited:
                capb = cap[x] + cap[y]
                for r in range(R):
                    ok &= c_cap[r, :top] <= capb[:, r, None] + 1e-6
            if host_active:
                # hostname caps: combined slot-matching counts must respect
                # the stricter of the two nodes' caps on every slot
                ok &= (hcnt[x] + hcnt[y] <= np.minimum(hcap[x], hcap[y])
                       ).all(axis=1)[:, None]
            return np.where(ok.any(axis=1), ok.argmax(axis=1), -1)

        # Scan order.  Plain solves: smallest-first.  Hostname-capped
        # solves: same, but round-robin across group combinations — the
        # solver creates one group's fragments consecutively, so a
        # smallest-first window would fill with ONE service's nodes, whose
        # pairs all violate the per-node cap; rotating group combos puts
        # mergeable cross-service partners inside the window.  A key is
        # (rank inside its combination, size, name, row); names are unique,
        # so the order is total and a bisection keeps the list what a full
        # sort would give.
        keys = sorted((0, size[x], names[x], x) for x in range(n))
        if host_active:
            combo = [frozenset(node_groups.get(id(x), all_groups))
                     for x in group]
            members: Dict[frozenset, list] = {}  # -> [(size, name, row)]
            for at, (_r, s, name, x) in enumerate(keys):
                mem = members.setdefault(combo[x], [])
                keys[at] = (len(mem), s, name, x)
                mem.append((s, name, x))
            keys.sort()

        def rerank(mem: list, lo: int, was: int, now: int) -> None:
            """One combination's members from ``lo`` on change rank, from
            ``was`` to ``now`` past their place in ``mem``."""
            for q in range(lo, len(mem)):
                del keys[bisect_left(keys, (q + was, *mem[q]))]
                insort(keys, (q + now, *mem[q]))

        # smallest-first pair scan: any pair may merge (a cpu-heavy and a
        # mem-heavy fragment can share one node even when two same-size
        # fragments can't), so failure of one pair doesn't end the bucket.
        # The scan is windowed to the FRAG_WINDOW first nodes — fragments
        # live at the small end, and an unwindowed pair scan over a 50k-pod
        # solve's hundreds of nodes would cost more host time than the solve.
        # A node takes a slot of the verdict matrix when it first enters the
        # window and keeps it until it is absorbed (the capped order can
        # push a node out of the window and let it back in).
        verdict = np.full((FRAG_WINDOW, FRAG_WINDOW), -1, np.int32)
        later = np.triu(np.ones((FRAG_WINDOW, FRAG_WINDOW), dtype=bool), 1)
        slot = np.full(N, -1, dtype=np.intp)
        free = list(range(len(verdict)))
        merged: Dict[int, tuple] = {}  # row -> (row a, row b, candidate)
        while len(keys) >= 2:
            wid = np.array([key[3] for key in keys[:FRAG_WINDOW]])
            fresh = slot[wid] < 0
            if fresh.any():
                for row in wid[fresh].tolist():
                    if not free:  # nodes pushed out of the window hold slots
                        had = len(verdict)
                        verdict = np.pad(verdict, (0, had), constant_values=-1)
                        free.extend(range(had, 2 * had))
                    slot[row] = s = free.pop()
                    verdict[s] = verdict[:, s] = -1
                at = np.flatnonzero(fresh)
                # pairs that touch a fresh node, each once
                p, q = np.nonzero((np.arange(wid.size) > at[:, None]) | ~fresh)
                x, y = wid[at[p]], wid[q]
                verdict[slot[x], slot[y]] = verdict[slot[y], slot[x]] = (
                    verdicts(x, y))
            ws = slot[wid]
            hits = (verdict[ws[:, None], ws] >= 0) & later[:ws.size, :ws.size]
            i, j = divmod(int(hits.argmax()), wid.size)
            if not hits[i, j]:
                break
            a, b = int(wid[i]), int(wid[j])
            k = int(verdict[ws[i], ws[j]])
            m = len(names)
            merged[m] = (a, b, k)
            # names are a tie-break of the order and part of the answer:
            # one a merge, drawn at the merge
            names.append(next_node_name())
            used[m] = used[a] + used[b]
            size.append(float(used[m].sum()))
            price[m] = c_price[k]
            feas[m] = feas[a] & feas[b]
            if limited:
                cap[m] = st.capacity_row(st.cand_names[cand_ix[k]][1], None)
            # one hop each; an absorbed node may itself be a prior
            # replacement, so the chains are followed once, at the end
            renames[names[a]] = renames[names[b]] = names[m]
            free += (int(slot[a]), int(slot[b]))
            if not host_active:
                del keys[j], keys[i]  # i < j, both inside the window
                insort(keys, (0, size[m], names[m], m))
                continue
            hcnt[m] = hcnt[a] + hcnt[b]
            hcap[m] = np.minimum(hcap[a], hcap[b])
            combo.append(combo[a] | combo[b])
            for gone in (a, b):  # later members of its combination move up
                mem = members[combo[gone]]
                r = bisect_left(mem, (size[gone], names[gone], gone))
                del keys[bisect_left(keys, (r, *mem[r]))], mem[r]
                rerank(mem, r, 1, 0)
            mem = members.setdefault(combo[m], [])
            r = bisect_left(mem, (size[m], names[m], m))
            rerank(mem, r, 0, 1)  # ... and of the one it joins, down
            mem.insert(r, (size[m], names[m], m))
            insort(keys, (r, *mem[r]))

        def pods_of(x: int) -> list:
            """a's pods, then b's, down the tree of merges under row x."""
            pods, todo = [], [x]
            while todo:
                x = todo.pop()
                if x < n:
                    pods.extend(group[x].pods)
                else:
                    todo.extend(merged[x][1::-1])  # b under a: a pops first
            return pods

        for _r, _s, name, x in keys:
            if x < n:
                out.append(group[x])
                continue
            ci = int(cand_ix[merged[x][2]])
            node = SimNode(
                instance_type=st.cand_names[ci][1],
                provisioner=prov,
                zone=zone,
                capacity_type=ct,
                price=float(price[x]),
                allocatable={
                    st.vocab.resources[r]: float(st.cand_alloc[ci, r])
                    for r in range(R)
                },
                existing=False,
                name=name,
            )
            node.stamp_labels()
            node.pods = pods_of(x)
            out.append(node)
    # forward every absorbed name to the node that finally holds its pods:
    # a replacement absorbed later was entered later, so walking the map
    # backwards finds each target already resolved
    for old in reversed(renames):
        renames[old] = renames.get(renames[old], renames[old])
    return out, renames, len(buckets)
