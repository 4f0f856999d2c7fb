"""The merge loop ``coalesce_new_nodes`` had before PR 28, kept verbatim as
the reference of ``tests/test_coalesce_equivalence.py``: a Python pair scan
over dicts keyed by ``id()``, and in hostname-capped solves a full re-rank
of the bucket after every merge.  Nothing in ``karpenter_tpu/`` runs it."""

from __future__ import annotations

import copy
from bisect import insort
from typing import Dict, List, Optional, Tuple

import numpy as np

from karpenter_tpu.solver import coalesce
from karpenter_tpu.solver.coalesce import (
    _NO_LIMIT,
    _domain_index,
    hostname_constrained,
    label_feasibility,
)
from karpenter_tpu.solver.types import SimNode

#: the window of the loop under test (a test may narrow both together)
FRAG_WINDOW = coalesce.FRAG_WINDOW


def twin(st, nodes, used_rows, node_groups):
    """A private copy of one input of the pass: the old loop moves pods, adds
    to ``used_rows`` and ``node_groups``, and both key their rows by
    ``id()``."""
    twins = [copy.copy(n) for n in nodes]
    for n, t in zip(nodes, twins):
        t.pods = list(n.pods)
    return (st, twins,
            {id(t): used_rows[id(n)].copy() for n, t in zip(nodes, twins)},
            None if node_groups is None else {
                id(t): set(node_groups[id(n)])
                for n, t in zip(nodes, twins) if id(n) in node_groups})


def hostname_capped_groups(st) -> set:
    """Group indices whose hostname rules CAP pods per node (spread maxSkew,
    anti-affinity) — a merge combining two nodes' counts can violate these,
    so nodes holding them are frozen out of coalescing.  Positive hostname
    affinity (g_host_paff) is NOT capping: it wants matching pods together,
    and merging only ever adds pods to a node, so it cannot break (fuzz
    seed 23: one paff group used to disable coalescing for the whole solve,
    stranding mergeable fragments in every other group)."""
    return set(np.flatnonzero(np.asarray(st.g_host_spread) >= 0).tolist())


def _pkey(a: SimNode, b: SimNode) -> tuple:
    """Order-free identity key for the symmetric pair-feasibility cache."""
    ia, ib = id(a), id(b)
    return (ia, ib) if ia < ib else (ib, ia)


def reference_coalesce(
    st,
    nodes: List[SimNode],
    used_rows: Dict[int, np.ndarray],  # id(node) -> used resource row [R]
    node_groups: Optional[Dict[int, set]] = None,  # id(node) -> {group idx}
) -> Tuple[List[SimNode], Dict[str, str]]:
    """Merge mergeable new nodes; returns (new node list, renames) where
    ``renames`` maps absorbed old node names -> their replacement's name.
    Pods are moved onto the replacement nodes; callers fix assignments via
    the rename map.  ``node_groups`` scopes the label-feasibility check to
    the groups actually placed on each node; without it (untracked solves)
    the merge target must be feasible for EVERY group in the solve."""
    capped = hostname_capped_groups(st)
    if node_groups is None:
        # untracked solves can't scope the check per node: all-or-nothing
        if hostname_constrained(st):
            return nodes, {}
        capped = set()
    # per-node hostname bookkeeping for capped solves: a merge is legal when,
    # for every hostname slot either node's groups cap, the COMBINED count of
    # slot-matching pods stays within the stricter cap (anti-affinity
    # cap 1/0, spread maxSkew).  Group labels are uniform, so counts come
    # from g_sel_match at group granularity — no per-pod selector matching.
    # This is what lets bench config 3 (every pod hostname-anti) coalesce its
    # 1-pod-per-service fragments into shared nodes at equal-or-lower price.
    g_hs = np.asarray(st.g_host_spread)
    g_hc = np.asarray(st.g_host_cap)
    host_active = bool(capped) and (g_hs >= 0).any()
    pod_group: Dict[str, int] = {}
    if host_active:
        for gi, g in enumerate(st.groups):
            for p in g.pods:
                pod_group[p.name] = gi
    S_all = st.g_sel_match.shape[0]

    def _host_state(n: SimNode):
        """(counts[S], caps[S]) for one node; caps inf where unconstrained."""
        cnt = np.zeros(S_all, dtype=np.int64)
        cap = np.full(S_all, np.inf)
        for p in n.pods:
            gi = pod_group.get(p.name)
            if gi is None:
                # a pod outside this solve (shouldn't happen for new nodes):
                # be conservative, forbid merging this node
                cap[:] = -1.0
                return cnt, cap
            cnt += st.g_sel_match[:, gi]
            s = int(g_hs[gi])
            if s >= 0:
                cap[s] = min(cap[s], float(g_hc[gi]))
            # positive hostname affinity (g_host_paff) needs no cap: it wants
            # matching pods together, and merging only ever ADDS co-residents
        return cnt, cap
    F = label_feasibility(st)                             # [G, C]
    all_groups = frozenset(range(F.shape[0]))

    # candidate rows by provisioner, cheapest-capacity order is not needed:
    # we pick the cheapest feasible replacement by price
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
        # replacement can come from any family)
        cand_ix = np.asarray([ci for ci in cands if st.cand_avail[ci, di]],
                             dtype=np.int64)
        if cand_ix.size == 0:
            out.extend(group)
            continue
        c_alloc = np.asarray(st.cand_alloc)[cand_ix]          # [K, R]
        c_cap = np.asarray(st.cand_cap)[cand_ix]              # [K, R]
        c_price = np.asarray(st.cand_price)[cand_ix, di]      # [K]
        c_F = F[:, cand_ix]                                   # [G, K]

        def groups_of(n: SimNode) -> frozenset:
            if node_groups is None:
                return all_groups
            return frozenset(node_groups.get(id(n), all_groups))

        _hstate: Dict[int, tuple] = {}

        def host_state(n: SimNode) -> tuple:
            got = _hstate.get(id(n))
            if got is None:
                got = _host_state(n)
                _hstate[id(n)] = got
            return got

        def order_nodes(lst: List[SimNode]) -> List[SimNode]:
            """Scan order.  Plain solves: smallest-first.  Hostname-capped
            solves: same, but round-robin across group combinations — the
            solver creates one group's fragments consecutively, so a
            smallest-first window would fill with ONE service's nodes, whose
            pairs all violate the per-node cap; rotating group combos puts
            mergeable cross-service partners inside the window."""
            base = sorted(lst, key=plain_key)
            if not host_active:
                return base
            seen: Dict[frozenset, int] = {}
            ranked = []
            for n in base:
                key = frozenset(groups_of(n))
                r = seen.get(key, 0)
                seen[key] = r + 1
                ranked.append((r, size_of(n), n.name, n))
            ranked.sort(key=lambda t: t[:3])
            return [t[3] for t in ranked]

        # per-node precomputes, cached by identity (merged nodes get entries
        # as they're created): candidate-feasibility row (AND over the node's
        # groups — c_F[union].all == c_F[a].all & c_F[b].all, so pair
        # feasibility is a cheap elementwise AND) and the raw-capacity row
        # for limit-bound buckets
        c_F_all = c_F.all(axis=0)
        _nF: Dict[int, np.ndarray] = {}
        _ncap: Dict[int, np.ndarray] = {}

        def node_F(n: SimNode) -> np.ndarray:
            got = _nF.get(id(n))
            if got is None:
                gs = groups_of(n)
                got = c_F_all if gs == all_groups else c_F[sorted(gs)].all(axis=0)
                _nF[id(n)] = got
            return got

        def node_cap(n: SimNode) -> np.ndarray:
            got = _ncap.get(id(n))
            if got is None:
                got = st.capacity_row(n.instance_type, n.allocatable)
                _ncap[id(n)] = got
            return got

        # smallest-first pair scan: any pair may merge (a cpu-heavy and a
        # mem-heavy fragment can share one node even when two same-size
        # fragments can't), so failure of one pair doesn't end the bucket.
        # The scan is windowed to the FRAG_WINDOW smallest nodes — fragments
        # live at the small end, and an unwindowed pair scan over a 50k-pod
        # solve's hundreds of nodes would cost more host time than the solve.
        # Pair feasibility is symmetric and unaffected by OTHER merges, so
        # it's cached by node-identity pair and evaluated in one batched
        # numpy pass per scan (the round-4 cold-path regression was this
        # loop in per-pair Python).  Merge order is unchanged: first
        # (i, then smallest j) feasible pair, cheapest candidate, resort,
        # rescan.
        pair_best: Dict[tuple, Optional[tuple]] = {}  # (ida,idb) -> (price,k)|None
        partners: Dict[int, set] = {}  # node id -> ids with a feasible merge
        _seen: set = set()           # node ids whose window pairs are cached
        _size: Dict[int, float] = {}  # node id -> used magnitude (sort key)
        _pinned: List[SimNode] = []  # absorbed nodes held alive: cache keys are
        # id()s — a GC'd node's id could be reused by a later merged node

        def size_of(n: SimNode) -> float:
            got = _size.get(id(n))
            if got is None:
                got = float(used_rows[id(n)].sum())
                _size[id(n)] = got
            return got

        def plain_key(n: SimNode) -> tuple:
            return size_of(n), n.name

        def eval_pairs(window: List[SimNode]) -> None:
            """Fill pair_best for every uncached pair in the window.  Only
            pairs touching a node new to the window since the last eval can
            be uncached (pair feasibility is unaffected by other merges), so
            enumeration is O(new x W), not O(W^2) per scan."""
            w = len(window)
            ids = [id(n) for n in window]
            new_ix = [i for i in range(w) if ids[i] not in _seen]
            if not new_ix:
                return
            new_set = set(new_ix)
            fresh, keys = [], []  # (i, j) with i < j, and the pair's _pkey
            for i in new_ix:
                ia = ids[i]
                for j in range(w):
                    if j == i or (j in new_set and j < i):
                        continue
                    ib = ids[j]
                    key = (ia, ib) if ia < ib else (ib, ia)
                    if key not in pair_best:
                        fresh.append((i, j) if i < j else (j, i))
                        keys.append(key)
            _seen.update(ids[i] for i in new_ix)
            if not fresh:
                return
            ai = np.asarray([i for i, _ in fresh])
            bj = np.asarray([j for _, j in fresh])
            used_w = np.stack([used_rows[id(n)] for n in window])     # [W,R]
            price_w = np.asarray([n.price for n in window])
            F_w = np.stack([node_F(n) for n in window])               # [W,K]
            need = used_w[ai] + used_w[bj]                            # [P,R]
            ok = F_w[ai] & F_w[bj]                                    # [P,K]
            R = need.shape[1]
            for r in range(R):
                ok &= c_alloc[None, :, r] + 1e-6 >= need[:, r, None]
            ok &= c_price[None, :] <= (price_w[ai] + price_w[bj])[:, None] + 1e-9
            if limited:
                cap_w = np.stack([node_cap(n) for n in window])
                capb = cap_w[ai] + cap_w[bj]
                for r in range(R):
                    ok &= c_cap[None, :, r] <= capb[:, r, None] + 1e-6
            if host_active:
                # hostname caps: combined slot-matching counts must respect
                # the stricter of the two nodes' caps on every slot
                hcnt = np.stack([host_state(n)[0] for n in window])  # [W,S]
                hcap = np.stack([host_state(n)[1] for n in window])  # [W,S]
                pair_ok = (
                    hcnt[ai] + hcnt[bj]
                    <= np.minimum(hcap[ai], hcap[bj])
                ).all(axis=1)
                ok &= pair_ok[:, None]
            any_p = ok.any(axis=1)
            hits = np.flatnonzero(any_p)
            ks = np.empty(len(fresh), dtype=np.int64)
            if hits.size:
                ks[hits] = np.where(ok[hits], c_price[None, :], np.inf).argmin(axis=1)
            for p in np.flatnonzero(~any_p).tolist():
                pair_best[keys[p]] = None
            for p in hits.tolist():
                ia, ib = keys[p]
                pair_best[keys[p]] = (float(c_price[ks[p]]), int(ks[p]))
                partners.setdefault(ia, set()).add(ib)
                partners.setdefault(ib, set()).add(ia)

        group = order_nodes(group)
        while len(group) >= 2:
            win = min(len(group), FRAG_WINDOW)
            window = group[:win]
            eval_pairs(window)
            hit = None
            for i in range(win - 1):
                ps = partners.get(id(window[i]))
                if not ps:
                    continue
                for j in range(i + 1, win):
                    if id(window[j]) in ps:
                        best = pair_best[_pkey(window[i], window[j])]
                        hit = (i, j, best[1],
                               used_rows[id(window[i])] + used_rows[id(window[j])])
                        break
                if hit is not None:
                    break
            if hit is None:
                break
            i, j, k, need = hit
            a, b = group[i], group[j]
            _pinned.extend((a, b))
            ci = int(cand_ix[k])
            _prov, type_name = st.cand_names[ci]
            node = SimNode(
                instance_type=type_name,
                provisioner=prov,
                zone=zone,
                capacity_type=ct,
                price=float(c_price[k]),
                allocatable={
                    st.vocab.resources[r]: float(st.cand_alloc[ci, r])
                    for r in range(st.cand_alloc.shape[1])
                },
                existing=False,
            )
            node.stamp_labels()
            node.pods = list(a.pods) + list(b.pods)
            used_rows[id(node)] = need
            _nF[id(node)] = node_F(a) & node_F(b)
            if host_active:
                ca, pa = host_state(a)
                cb, pb = host_state(b)
                _hstate[id(node)] = (ca + cb, np.minimum(pa, pb))
            if node_groups is not None:
                node_groups[id(node)] = set(groups_of(a) | groups_of(b))
            # one hop each; an absorbed node may itself be a prior
            # replacement, so the chains are followed once, at the end
            renames[a.name] = node.name
            renames[b.name] = node.name
            # absorbed nodes leave the partner graph (their ids must not
            # surface as hits in later scans)
            for gone in (id(a), id(b)):
                for other in partners.pop(gone, ()):  # symmetric cleanup
                    partners.get(other, set()).discard(gone)
            del group[j], group[i]  # i < j, both inside the window
            if host_active:
                group = order_nodes(group + [node])
            else:
                # the plain order is a total one (names are unique), so
                # taking two out and putting one in keeps the list what a
                # full sort would give — at a bisection, not a sort of the
                # bucket, per merge (a long-tailed batch merges thousands
                # of fragments per bucket)
                insort(group, node, key=plain_key)
        out.extend(group)
    # forward every absorbed name to the node that finally holds its pods:
    # a replacement absorbed later was entered later, so walking the map
    # backwards finds each target already resolved
    for old in reversed(renames):
        renames[old] = renames.get(renames[old], renames[old])
    return out, renames
