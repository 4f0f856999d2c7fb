"""The plain reference, the validator and the comparison that decides ``correct``.

Nothing here imports the program or takes anything the program made: inputs
are the generator's plain clusters (``gen.Cluster``), the configuration's
provisioners as plain dicts and the catalog rows of ``catalogs/<name>.json``;
an answer is plain tuples read off what the client decoded.

* :func:`ffd` — a straightforward sequential first-fit-decreasing pack, the
  cost base of BASELINE's guarantee ("node cost within 1.02x of FFD"): groups
  in non-increasing order of size, each pod first-fit onto the earliest
  opened node that takes it, else a new node of the (provisioner, type,
  offering) with the lowest price per pod still to place.  It is also the
  CONTROL when told to break a guarantee (``break_rule``).
* :func:`validate` — the configuration's guarantees judged on an answer:
  every pod placed, resource fit, zone-spread skew, hostname anti-affinity,
  taints, provisioner filter, offerings and prices of the catalog.
* :func:`compare` — the numbers compared, each beside its limit.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

ZONE_SPREAD = "zone_spread"
HOST_ANTI = "hostname_anti_affinity"
RES = ("cpu", "memory", "pods")

#: the limits of the comparison (PERF.md section 2 gives the readings each
#: was set from); the cost ceiling is the configuration's own
LIMITS = {"unanswered": 0, "unplaced": 0, "violations": 0}


class Answer:
    """One decoded reply as plain data: ``nodes`` is a list of
    ``(name, type, provisioner, zone, capacity_type, price, [pod names])``,
    ``assignments`` maps pod name -> node name."""

    def __init__(self, nodes: list, assignments: Dict[str, str],
                 infeasible: Dict[str, str]) -> None:
        self.nodes = nodes
        self.assignments = assignments
        self.infeasible = infeasible

    @classmethod
    def of_result(cls, res) -> "Answer":
        """Read the plain fields off a decoded ``SolveResult``."""
        return cls([(n.name, n.instance_type, n.provisioner, n.zone,
                     n.capacity_type, float(n.price),
                     [p.name for p in n.pods]) for n in res.nodes],
                   dict(res.assignments), dict(res.infeasible))


# ---------------------------------------------------------------------------
# candidates: (provisioner, catalog row) pairs the provisioner's filter admits
# ---------------------------------------------------------------------------


def admits(flt: dict, row: dict) -> bool:
    return (row["os"] in flt["os"] and row["arch"] in flt["arch"]
            and row["category"] in flt["category"]
            and row["generation"] > flt["generation_gt"])


def candidates(provisioners: Sequence[dict], rows: Sequence[dict]) -> list:
    """``[(provisioner, row, [(zone, capacity_type, price)])]``, provisioners
    by weight (highest first), rows in catalog order."""
    out = []
    for prov in sorted(provisioners, key=lambda p: (-p["weight"], p["name"])):
        cts = set(prov["filter"]["capacity_type"])
        for row in rows:
            if not admits(prov["filter"], row):
                continue
            offers = [(z, ct, price) for z, ct, price in row["offerings"]
                      if ct in cts]
            if offers:
                out.append((prov, row, offers))
    return out


def tolerated(taints: Sequence[dict], tolerations: Sequence[dict]) -> bool:
    for t in taints:
        if not any((not tol.get("effect") or tol["effect"] == t["effect"])
                   and (tol["key"] == t["key"] if tol.get("operator")
                        == "Exists" else tol["key"] == t["key"]
                        and tol.get("value", "") == t.get("value", ""))
                   for tol in tolerations):
            return False
    return True


def magnitude(g: dict) -> float:
    """Size key of the decreasing order: cores + memory at 4 GiB a core."""
    return g["cpu"] + g["memory"] / (4.0 * 2 ** 30)


# ---------------------------------------------------------------------------
# the plain sequential first-fit-decreasing pack
# ---------------------------------------------------------------------------


class _Node:
    __slots__ = ("name", "prov", "row", "zone", "ct", "price", "by_group",
                 "index")

    def __init__(self, index, prov, row, zone, ct, price) -> None:
        self.index = index
        self.name = f"ref-{index}"
        self.prov, self.row, self.zone, self.ct, self.price = (
            prov, row, zone, ct, price)
        self.by_group: Dict[int, List[str]] = {}

    def room(self, groups: list, req: dict) -> int:
        """How many more pods of shape ``req`` fit beside what is here."""
        alloc = self.row["allocatable"]
        used = {"cpu": 0.0, "memory": 0.0, "pods": 0.0}
        for gi, names in self.by_group.items():
            g = groups[gi]
            used["cpu"] += g["cpu"] * len(names)
            used["memory"] += g["memory"] * len(names)
            used["pods"] += len(names)
        return int(min((alloc[k] - used[k]) // req[k] for k in RES))


def ffd(groups: List[dict], provisioners: Sequence[dict],
        rows: Sequence[dict], zones: Sequence[str],
        break_rule: Optional[str] = None) -> Answer:
    """Sequential FFD of ``groups``.  ``break_rule`` makes it the control:
    ``"spread"`` ignores the zone spread, ``"anti"`` the hostname
    anti-affinity, ``"taints"`` the provisioners' taints, ``"price"`` opens
    the dearest admissible node instead of the cheapest per pod."""
    cands = candidates(provisioners, rows)
    order = sorted((gi for gi, g in enumerate(groups) if g["pods"]),
                   key=lambda gi: (-magnitude(groups[gi]),
                                   groups[gi]["pods"][0]))
    nodes: List[_Node] = []
    assignments: Dict[str, str] = {}
    infeasible: Dict[str, str] = {}
    for gi in order:
        g = groups[gi]
        req = {"cpu": g["cpu"], "memory": g["memory"], "pods": 1.0}
        spread = _rule(g, "zone_spread") and break_rule != "spread"
        anti = _rule(g, "hostname_anti") and break_rule != "anti"
        mine = [c for c in cands if break_rule == "taints"
                or tolerated(c[0]["taints"], g["tolerations"])]
        heaps: Dict[str, list] = {z: [] for z in zones}
        for node in nodes:
            if break_rule != "taints" and not tolerated(
                    node.prov["taints"], g["tolerations"]):
                continue
            cap = node.room(groups, req)
            if anti:
                cap = min(cap, 1)
            if cap > 0:
                heapq.heappush(heaps[node.zone], [node.index, cap, node])
        count = {z: 0 for z in zones}
        best_new: Dict[str, Optional[tuple]] = {}
        todo = len(g["pods"])
        for name in g["pods"]:
            low = min(count.values())
            allowed = ([z for z in zones if count[z] + 1 - low <= 1]
                       if spread else list(zones))
            chosen = None
            for z in allowed:
                h = heaps[z]
                if h and (chosen is None or h[0][0] < chosen[0]):
                    chosen = h[0]
            if chosen is None:
                best = None
                for z in allowed:
                    if z not in best_new:
                        best_new[z] = _best_in_zone(mine, req, z, todo,
                                                    break_rule)
                    b = best_new[z]
                    if b is not None and (best is None or b[0] < best[0]):
                        best = b
                if best is None:
                    infeasible[name] = "no (provisioner, type, offering) fits"
                    todo -= 1
                    continue
                _, prov, row, (zone, ct, price) = best
                node = _Node(len(nodes), prov, row, zone, ct, price)
                nodes.append(node)
                cap = node.room(groups, req)
                if anti:
                    cap = min(cap, 1)
                chosen = [node.index, cap, node]
                if cap > 1:
                    heapq.heappush(heaps[zone], chosen)
            node = chosen[2]
            node.by_group.setdefault(gi, []).append(name)
            assignments[name] = node.name
            count[node.zone] += 1
            todo -= 1
            chosen[1] -= 1
            if chosen[1] <= 0 and heaps[node.zone] and \
                    heaps[node.zone][0] is chosen:
                heapq.heappop(heaps[node.zone])
    return Answer([(n.name, n.row["name"], n.prov["name"], n.zone, n.ct,
                    n.price, [nm for names in n.by_group.values()
                              for nm in names]) for n in nodes],
                  assignments, infeasible)


def _best_in_zone(cands: list, req: dict, zone: str, remaining: int,
                  break_rule: Optional[str]) -> Optional[tuple]:
    best = None
    for ci, (prov, row, offers) in enumerate(cands):
        alloc = row["allocatable"]
        ppn = int(min(alloc[k] // req[k] for k in RES))
        if ppn < 1:
            continue
        denom = max(1, min(ppn, remaining))
        for oi, (z, ct, price) in enumerate(offers):
            if z != zone:
                continue
            score = ((-price, ci, oi) if break_rule == "price"
                     else (price / denom, price, ci, oi))
            if best is None or score < best[0]:
                best = (score, prov, row, (z, ct, price))
    return best


# ---------------------------------------------------------------------------
# the validator: the configuration's guarantees judged on an answer
# ---------------------------------------------------------------------------


def validate(groups: List[dict], provisioners: Sequence[dict],
             rows: Sequence[dict], zones: Sequence[str],
             ans: Answer) -> Tuple[int, List[str]]:
    """``(unplaced, violations)`` — pods of ``groups`` without a node, and
    every broken rule in words."""
    errs: List[str] = []
    row_by = {r["name"]: r for r in rows}
    prov_by = {p["name"]: p for p in provisioners}
    pod_group: Dict[str, int] = {}
    for gi, g in enumerate(groups):
        for name in g["pods"]:
            pod_group[name] = gi
    unplaced = sum(1 for name in pod_group
                   if name not in ans.assignments or name in ans.infeasible)
    ghosts = sum(1 for name in ans.assignments if name not in pod_group)
    if ghosts:
        errs.append(f"{ghosts} assigned pod(s) that the request does not hold")
    node_by = {}
    for n in ans.nodes:
        if n[0] in node_by:
            errs.append(f"node name {n[0]} twice")
        node_by[n[0]] = n
    listed = 0
    zone_count: Dict[int, Dict[str, int]] = {}
    for name, typ, prov_name, zone, ct, price, pods in ans.nodes:
        row, prov = row_by.get(typ), prov_by.get(prov_name)
        if row is None or prov is None:
            errs.append(f"{name}: unknown type {typ} or provisioner "
                        f"{prov_name}")
            continue
        if not admits(prov["filter"], row):
            errs.append(f"{name}: {typ} is outside provisioner {prov_name}")
        offer = [p for z, c, p in row["offerings"] if z == zone and c == ct]
        if not offer or ct not in prov["filter"]["capacity_type"]:
            errs.append(f"{name}: no offering {typ}/{zone}/{ct}")
        elif abs(offer[0] - price) > 1e-6 * max(1.0, offer[0]):
            errs.append(f"{name}: price {price} is not the catalog's "
                        f"{offer[0]}")
        used = {"cpu": 0.0, "memory": 0.0, "pods": 0.0}
        per_group: Dict[int, int] = {}
        for pn in pods:
            listed += 1
            gi = pod_group.get(pn)
            if gi is None:
                errs.append(f"{name} holds {pn}, which the request does not")
                continue
            if ans.assignments.get(pn) != name:
                errs.append(f"{pn} is on {name} but assigned to "
                            f"{ans.assignments.get(pn)}")
            g = groups[gi]
            used["cpu"] += g["cpu"]
            used["memory"] += g["memory"]
            used["pods"] += 1.0
            per_group[gi] = per_group.get(gi, 0) + 1
        for k in RES:
            if used[k] > row["allocatable"][k] * (1 + 1e-9) + 1e-6:
                errs.append(f"{name} overcommitted on {k}: {used[k]} > "
                            f"{row['allocatable'][k]}")
        for gi, n_here in per_group.items():
            g = groups[gi]
            if not tolerated(prov["taints"], g["tolerations"]):
                errs.append(f"{name}: {g['name']} does not tolerate "
                            f"{prov_name}'s taints")
            if _rule(g, "hostname_anti") and n_here > 1:
                errs.append(f"{name} holds {n_here} pods of {g['name']}")
            zc = zone_count.setdefault(gi, {})
            zc[zone] = zc.get(zone, 0) + n_here
    placed = sum(1 for name in ans.assignments if name in pod_group)
    if listed != placed:
        errs.append(f"{placed} pods assigned but {listed} listed on nodes")
    for name, node in ans.assignments.items():
        if node not in node_by:
            errs.append(f"{name} assigned to {node}, which is not in the "
                        "answer")
            break
    for gi, g in enumerate(groups):
        if not _rule(g, "zone_spread") or not g["pods"]:
            continue
        zc = zone_count.get(gi, {})
        counts = [zc.get(z, 0) for z in zones]
        if max(counts) - min(counts) > 1:
            errs.append(f"{g['name']} zone skew {counts}")
    return unplaced, errs


def _rule(g: dict, rule: str) -> bool:
    """Whether group ``g``'s constraint kind carries ``rule``
    (``zone_spread`` / ``hostname_anti``)."""
    kind = g["constraint"]
    if kind == ZONE_SPREAD:
        return rule == "zone_spread"
    if kind == HOST_ANTI:
        return rule == "hostname_anti"
    if kind == "none":
        return False
    from gen import constraint_plugin

    return bool(constraint_plugin(kind).plain(g).get(rule))


def cost(ans: Answer, rows: Sequence[dict]) -> float:
    """$/hr of the answer's nodes at the catalog's prices."""
    price = {(r["name"], z, ct): p for r in rows
             for z, ct, p in r["offerings"]}
    return sum(price.get((typ, zone, ct), float(p))
               for _, typ, _, zone, ct, p, _ in ans.nodes)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


#: what an answer's $ are compared for (a case's optional third field):
#: ``METRIC`` they enter ``cost_ratio`` and are held to the ceiling,
#: ``CEILING`` they are held to the ceiling alone, ``None`` they are not
#: compared and the reference is not packed (the validator judges the answer)
METRIC, CEILING = "metric", "ceiling"


def compare(cases: list, provisioners: Sequence[dict], rows: Sequence[dict],
            zones: Sequence[str], ceiling: float, unanswered: int,
            unplaced_on_the_way: int = 0) -> dict:
    """``cases`` is ``[(groups, Answer)]`` or ``[(groups, Answer, cost)]`` —
    the answers compared, each with the plain cluster it answers and what its
    $ are compared for (``METRIC`` where it is not said; the reference packs
    a cluster once, however many answers to it there are).
    ``unplaced_on_the_way`` counts pods that answers NOT among the cases left
    without a node; they are ``unplaced`` like the cases' own.  Returns
    ``{correct, numbers, cost_ratio, per_case, ...}``; ``numbers`` holds each
    number compared beside its limit, ``per_case`` every case's own
    ``{unplaced, violations, cost, ffd}`` (``ffd`` where it was packed)."""
    unplaced, violations = unplaced_on_the_way, 0
    got = base = 0.0
    worst = 0.0
    first_errs: List[str] = []
    refs: dict = {}
    per_case: List[dict] = []
    for groups, ans, *what in cases:
        what = what[0] if what else METRIC
        u, errs = validate(groups, provisioners, rows, zones, ans)
        unplaced += u
        violations += len(errs)
        first_errs.extend(errs[:3])
        c = cost(ans, rows)
        per_case.append({"unplaced": u, "violations": len(errs), "cost": c})
        if what is None:
            continue
        if id(groups) not in refs:
            refs[id(groups)] = cost(ffd(groups, provisioners, rows, zones),
                                    rows)
        b = per_case[-1]["ffd"] = refs[id(groups)]
        worst = max(worst, c / b if b else float("inf"))
        if what == METRIC:
            got += c
            base += b
    numbers = {
        "unanswered": [unanswered, LIMITS["unanswered"]],
        "unplaced": [unplaced, LIMITS["unplaced"]],
        "violations": [violations, LIMITS["violations"]],
        "cost_ratio_max": [worst, ceiling],
    }
    correct = bool(cases) and all(v <= lim for v, lim in numbers.values())
    return {"correct": correct, "numbers": numbers,
            "cost_ratio": got / base if base else None,
            "compared": len(cases), "per_case": per_case,
            "first_violations": first_errs[:6]}
