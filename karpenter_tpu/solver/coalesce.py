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
are rows 0..n-1 of dense arrays (used resources and price, candidate
feasibility, raw capacity, hostname counts and caps) and every merge writes
ONE new row (sum / AND / min of two).  A pair's verdict — its cheapest
candidate, or none — does not change with other merges, so it is computed
when the rule needs it and kept: each merge walks the window as the rule
reads it, row by row and partners in order, past the pairs known to hold
nothing, and asks the pairs it meets that have no verdict yet in one
broadcast over the candidates in price order (less those an earlier one
beats on every count) — as many in a round as finding the last hit took,
twice that if none of them holds, never the whole window because it is
there.  The pairs that hold nothing are one whole number per row, bit y for
row y — the rows it is dead to; the hits are a dict.  A pair may be asked only
if the later of the two first entered the window while the other was in it: a
row the capped order pushes out is, on its way back, dead to the rows that
came in meanwhile (the rule of the loop this replaced, kept so that the
answers are its answers).  The order is a sorted list of ``(rank, size,
name)`` keys kept by bisection, where a merge re-ranks only the later
members of the combinations it takes from and adds to.  Merged nodes are
rows and a name until the bucket is done: only the survivors become
``SimNode`` objects.  The per-node state is read off the scan's take matrix:
the callers hand over how many pods of which group each node took and what
it has in use, and a bucket's hostname counts and caps are one product and
one minimum over those entries.

``TpuSolver._extract`` wraps the pass in a ``coalesce`` span (``nodes_in``,
``nodes_out``, ``merges``, ``buckets``, ``pairs``) and counts it in
``karpenter_solver_coalesce_total{what="nodes_in"|"merges"|"pairs"}``
(``pairs``: verdicts computed, the walk's work count).
"""

from __future__ import annotations

from bisect import bisect_left, insort
from functools import reduce
from itertools import chain
from operator import or_
from typing import Dict, List, Optional

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
    # groups that ask the same of a node's labels share a row (a long tail
    # of deployments is a handful of requirement sets): the test runs once
    # for each group that is the first to ask what it asks
    seen: Dict[bytes, int] = {}
    same = np.fromiter((seen.setdefault(row.tobytes(), g)
                        for g, row in enumerate(pm.reshape(G, -1))),
                       np.intp, G)
    first = np.flatnonzero(same == np.arange(G))
    lab = np.ones((first.size, C), dtype=bool)
    for k in range(K):
        if not kc[k]:
            continue
        words = pm[first, k, :][:, vw[:, k]]  # [distinct, C]
        lab &= ((words >> vb[None, :, k]) & 1).astype(bool)
    lab = lab[np.searchsorted(first, same)]   # [G, C]
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


def _rows(mask: int):
    """The rows whose bits are set in ``mask``, lowest first."""
    while mask:
        low = mask & -mask
        yield low.bit_length() - 1
        mask ^= low


def _domain_index(st, zone: str, ct: str) -> Optional[int]:
    try:
        zi = st.zone_names.index(zone)
        ci = st.ct_names.index(ct)
    except ValueError:
        return None
    return zi * max(1, len(st.ct_names)) + ci


class Coalesced(tuple):
    """``(nodes, renames, buckets)``, what the pass has always returned, and
    beside them ``pairs``: how many pair verdicts it computed."""

    pairs = 0


def apply_coalesce(st, nodes, used_rows, node_groups, assignments, span=None):
    """Shared tier epilogue: run the merge pass and repoint the pods of
    absorbed nodes at their replacements.  Both the device tier
    (tpu._extract) and the native tier (native.solve_tensors_native) call
    this so the cold-start answer and the warm answer stay the same
    coalescing contract.  Returns ``(nodes, pairs)``; ``span``, where given,
    is told what the pass did."""
    n_in, buckets, pairs = len(nodes), 0, 0
    if n_in >= 2:
        done = coalesce_new_nodes(st, nodes, used_rows,
                                  node_groups=node_groups)
        (nodes, renames, buckets), pairs = done, done.pairs
        # an absorbed node's pods are on the node its name now leads to
        holders = set(renames.values())
        for node in nodes:
            if node.name in holders:
                for pod in node.pods:
                    assignments[pod.name] = node.name
    if span is not None:
        span.annotate(nodes_in=n_in, nodes_out=len(nodes),
                      merges=n_in - len(nodes), buckets=buckets, pairs=pairs)
    return nodes, pairs


def coalesce_new_nodes(
    st,
    nodes: List[SimNode],
    used_rows: Dict[int, np.ndarray],  # id(node) -> used resource row [R]
    # id(node) -> {group idx: pods of it on the node}, off the take matrix
    node_groups: Optional[Dict[int, Dict[int, int]]] = None,
) -> Coalesced:
    """Merge mergeable new nodes; returns (new node list, renames, buckets)
    where ``renames`` maps absorbed old node names -> their replacement's
    name and ``buckets`` counts the (provisioner, zone, capacity-type)
    buckets the nodes fell in.  Every merge takes one node off the list.
    Pods are moved onto the replacement nodes.  ``node_groups`` scopes the
    label-feasibility check to the groups actually placed on each node, and
    its counts are what the hostname caps are held against; without it
    (untracked solves) the merge target must be feasible for EVERY group in
    the solve."""
    # untracked solves can't scope the check per node: all-or-nothing
    if node_groups is None and hostname_constrained(st):
        return Coalesced((nodes, {}, 0))
    # per-node hostname bookkeeping for capped solves: a merge is legal when,
    # for every hostname slot either node's groups cap, the COMBINED count of
    # slot-matching pods stays within the stricter cap (anti-affinity
    # cap 1/0, spread maxSkew).  Group labels are uniform, so counts come
    # from g_sel_match at group granularity.  This is what lets bench config
    # 3 (every pod hostname-anti) coalesce its 1-pod-per-service fragments
    # into shared nodes at equal-or-lower price.  Positive hostname affinity
    # (g_host_paff) needs no cap: merging only ever ADDS co-residents.
    g_hs = np.asarray(st.g_host_spread)
    g_hc = np.asarray(st.g_host_cap, dtype=np.float64)
    sel = np.asarray(st.g_sel_match)
    host_active = node_groups is not None and bool((g_hs >= 0).any())
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
    pairs = 0
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
        # cheapest feasible candidate is the FIRST feasible one
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
        # arrive, every merge appends one (2n-1 at most).  A row holds its
        # used resources and, last, its price with the sign turned: "the
        # candidate has the room and is no dearer" is one comparison with
        # c_tab, whose last row is the candidates' prices, turned too
        K, N = cand_ix.size, 2 * n - 1
        names = [x.name for x in group]
        tab = np.empty((N, R + 1))
        tab[:n, :R] = [used_rows[id(x)] for x in group]
        tab[:n, R] = [-x.price for x in group]
        size = tab[:n, :R].sum(axis=1).tolist()           # the order's key
        # (column K is no candidate: it holds every pair, after all others)
        c_tab = np.full((R + 1, K + 1), np.inf)
        c_tab[:R, :K], c_tab[R, :K] = c_room, -c_price
        # candidate feasibility: AND over the node's groups (c_F[union].all
        # == c_F[a].all & c_F[b].all, so a merged row is an AND of two)
        feas = np.ones((N, K + 1), dtype=bool)
        if node_groups is None:
            feas[:n, :K] = c_F.all(axis=0)
        else:
            # one reduceat over every node's rows; row G (all true) closes
            # each segment so none is empty, row G+1 is a node the caller
            # did not track: every group
            rows = np.vstack([c_F, np.ones((1, K), dtype=bool),
                              c_F.all(axis=0)[None]])
            took = [node_groups.get(id(x)) for x in group]
            segs = [(*gs, G) if gs is not None else (G + 1,) for gs in took]
            starts = np.cumsum([0] + [len(s) for s in segs[:-1]])
            feas[:n, :K] = np.logical_and.reduceat(
                rows[np.fromiter(chain.from_iterable(segs), np.intp)],
                starts, axis=0)
        selective = not feas[:n].all()  # some group does not admit some type
        if limited:
            cap = np.empty((N, R), dtype=np.float32)
            cap[:n] = [st.capacity_row(x.instance_type, x.allocatable)
                       for x in group]
        if host_active:
            # (counts[S], caps[S]) per node, caps inf where unconstrained,
            # from the bucket's (node, group, pods) entries: the counts as
            # one product with the selector matrix, entry by matching slot
            # (whole numbers: exact), the caps as one minimum
            held = [gs or {} for gs in took]
            at = np.repeat(np.arange(n), [len(gs) for gs in held])
            gs = np.fromiter(chain.from_iterable(held), np.intp, at.size)
            pods = np.fromiter(chain.from_iterable(h.values() for h in held),
                               np.float64, at.size)
            S = sel.shape[0]
            entry, slot = np.nonzero(sel.T[gs])
            hcnt = np.zeros((N, S))
            hcnt[:n] = np.bincount(at[entry] * S + slot, weights=pods[entry],
                                   minlength=n * S).reshape(n, S)
            hcap = np.full((N, S), np.inf)
            capping = g_hs[gs] >= 0
            np.minimum.at(hcap, (at[capping], g_hs[gs[capping]]),
                          g_hc[gs[capping]])
            for x in range(n):  # a node the caller did not track: no merge
                if took[x] is None:
                    hcap[x] = -1.0

        def verdicts(x: np.ndarray, y: np.ndarray) -> np.ndarray:
            """The cheapest candidate that can replace nodes x[p] and y[p]
            together, K where none can.  Symmetric, and unaffected by other
            merges: asked once per pair."""
            if host_active:
                # hostname caps: combined slot-matching counts must respect
                # the stricter of the two nodes' caps on every slot; only
                # the pairs that do need a candidate
                got = np.full(x.size, K)
                fit = np.flatnonzero((hcnt[x] + hcnt[y] <= np.minimum(
                    hcap[x], hcap[y])).all(axis=1))
                x, y = x[fit], y[fit]
            need = tab[x] + tab[y]                                # [P, R+1]
            need[:, R] -= 1e-9  # c_price <= pay + 1e-9, both sides turned
            ok = (c_tab >= need[:, :, None]).all(axis=1)          # [P, K+1]
            if selective:
                ok &= feas[x] & feas[y]
            if limited:
                capb = cap[x] + cap[y]
                for r in range(R):
                    ok[:, :K] &= c_cap[r] <= capb[:, r, None] + 1e-6
            if not host_active:
                return ok.argmax(axis=1)
            got[fit] = ok.argmax(axis=1)
            return got

        # Scan order.  Plain solves: smallest-first.  Hostname-capped
        # solves: same, but round-robin across group combinations — the
        # solver creates one group's fragments consecutively, so a
        # smallest-first window would fill with ONE service's nodes, whose
        # pairs all violate the per-node cap; rotating group combos puts
        # mergeable cross-service partners inside the window.  A key is
        # (rank inside its combination, size, name, row): names are unique,
        # so a bisection keeps the list what a full sort would give.
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
        # A row has a bit from its first entry to the window until absorbed
        bit: Dict[int, int] = {}
        dead: Dict[int, int] = {}  # row -> rows it cannot, or may not, take
        best: Dict[tuple, int] = {}  # (row, row) -> their cheapest candidate
        left: Dict[int, int] = {}  # row pushed out -> `entered` as it went
        window = entered = absent = 0
        merged: Dict[int, tuple] = {}  # row -> (row a, row b, candidate)
        ask = 1  # pairs asked in a round: what the last hit took to find
        while len(keys) >= 2:
            wid = [key[3] for key in keys[:FRAG_WINDOW]]
            fresh = [x for x in wid if x not in bit]
            for x in fresh:
                bit[x] = 1 << x
            was, window = window, reduce(or_, map(bit.__getitem__, wid))
            # a pair may be asked if the later of the two first entered the
            # window while the other was in it: a row the capped order pushes
            # out and lets back in is dead to the rows that entered meanwhile
            if was & ~window:
                for z in _rows(was & ~window):
                    left[z] = entered
                absent |= was & ~window
            if absent & window:
                for z in _rows(absent & window):
                    dead[z] |= entered & ~left.pop(z)
                absent &= ~window
            for x in fresh:
                dead[x] = absent
                entered |= bit[x]
            # the walk: the window's pairs row by row, partners in order, up
            # to the first that a candidate holds.  The pairs not asked yet
            # that it meets on the way are asked, `ask` of them in one round,
            # and the walk goes on from the top only if none of them is held
            asked = 0
            while True:
                seen, todo, found = 0, [], None
                for i, x in enumerate(wid):
                    seen |= bit[x]
                    live = window & ~(seen | dead[x])
                    if not live:
                        continue
                    if live.bit_count() ** 2 > len(wid) - i:
                        # many: meet them on the way down the window
                        after = (y for y in wid[i + 1:] if live & bit[y])
                    else:  # few: read them off, put them in order
                        after = sorted(_rows(live), key=wid.index)
                    for y in after:
                        if (x, y) in best:
                            found = (x, y)
                            break
                        todo.append((x, y))
                        if len(todo) == ask:
                            break
                    else:
                        continue
                    break
                if todo:
                    got = verdicts(*np.array(todo).T).tolist()
                    for (x, y), k in zip(todo, got):
                        if k == K:
                            dead[x] |= bit[y]
                            dead[y] |= bit[x]
                        else:
                            best[x, y] = best[y, x] = k
                    if min(got) < K:  # the first held in order: what it took
                        at = next(i for i, k in enumerate(got) if k < K)
                        found, ask = todo[at], asked + at + 1
                    asked += len(todo)
                if found is not None or len(todo) < ask:
                    break  # a pair to merge, or no pair left to ask
                ask *= 2
            pairs += asked
            if found is None:
                break
            a, b = found
            k = best[found]
            m = len(names)
            merged[m] = (a, b, k)
            # names break ties of the order: one a merge, drawn at the merge
            names.append(next_node_name())
            tab[m] = tab[a] + tab[b]
            tab[m, R] = -c_price[k]
            size.append(float(tab[m, :R].sum()))
            if selective:
                feas[m] = feas[a] & feas[b]
            if limited:
                cap[m] = st.capacity_row(st.cand_names[cand_ix[k]][1], None)
            # one hop each: the chains are followed once, at the end
            renames[names[a]] = renames[names[b]] = names[m]
            window &= ~(bit.pop(a) | bit.pop(b))
            if not host_active:
                i, j = wid.index(a), wid.index(b)
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
            node = SimNode.bought(
                st.provisioners[pi], instance_type=st.cand_names[ci][1],
                zone=zone, capacity_type=ct, price=float(-tab[x, R]),
                allocatable={st.vocab.resources[r]: float(st.cand_alloc[ci, r])
                             for r in range(R)},
                name=name)
            node.pods = pods_of(x)
            out.append(node)
    # forward every absorbed name to the node that finally holds its pods:
    # a replacement absorbed later was entered later, so walking the map
    # backwards finds each target already resolved
    for old in reversed(renames):
        renames[old] = renames.get(renames[old], renames[old])
    done = Coalesced((out, renames, len(buckets)))
    done.pairs = pairs
    return done
