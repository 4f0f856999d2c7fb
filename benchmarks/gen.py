"""Traffic generation: configuration file + traffic file + seed -> requests.

Everything a cell sends is made here from data files and ``--seed``:

* ``configs/<name>.json`` — the deployment: catalog, provisioners and a list
  of deployment templates (count, replicas, request shapes, constraint,
  tolerations).
* ``traffic/<name>.json`` — the mix: ``kind`` ``burst`` (each request one
  fresh ``Solve`` of the whole cluster) or ``reconcile`` (one session, each
  request one ``solve_delta`` step).

Requests are PLAIN data (dicts and lists; :class:`Cluster`) so that the
plain reference (``plainref.py``) never sees an object of the program.
:class:`ProgramInputs` turns plain clusters into the program's input types
for the client — the one place here that imports ``karpenter_tpu``.

Every seed gets the same work: a burst pool, the standing cluster of a
session and the step stream over it are fixed by the configuration (the
stream by the traffic file too); the seed salts the names, orders the
deployments inside a request and the requests of a burst pass, and nothing
else (a seed that changes the work shows up as run-to-run spread).  A
constraint kind this file lacks arrives as ``constraints/<kind>.py`` with ``plain(template) -> dict`` (what the plain
reference and validator enforce) and ``program(pod_kwargs, group) -> None``
(what the client sends).
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
GIB = float(2 ** 30)

#: constraint kinds understood without a plug-in file
BUILTIN_CONSTRAINTS = ("none", "zone_spread", "hostname_anti_affinity")


def _load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        doc = json.load(f)
    doc.setdefault("name", name)
    return doc


def load_config(name: str) -> dict:
    return _load("configs", name)


def load_traffic(name: str) -> dict:
    return _load("traffic", name)


def load_catalog(name: str) -> dict:
    return _load("catalogs", name)


def constraint_plugin(kind: str):
    """``constraints/<kind>.py`` for a kind that is not built in."""
    path = os.path.join(HERE, "constraints", f"{kind}.py")
    if not os.path.exists(path):
        raise ValueError(f"unknown constraint kind {kind!r}: not built in "
                         f"and no {path}")
    spec = importlib.util.spec_from_file_location(f"constraint_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# plain clusters
# ---------------------------------------------------------------------------


class Cluster:
    """One request's pod set as plain data: ``groups`` is a list of dicts
    ``{name, cpu, memory, constraint, tolerations, pods: [names]}`` — every
    pod of a group has the group's shape, labels ``{"app": name}`` and a
    constraint on that selector."""

    def __init__(self, groups: List[dict], key: tuple) -> None:
        self.groups = groups
        #: what makes this request differ from the others of its pool
        self.key = key

    @property
    def n_pods(self) -> int:
        return sum(len(g["pods"]) for g in self.groups)


def _deltas(n: int, base: int, spread: float) -> List[int]:
    """``n`` whole replica offsets around ``base`` that sum to 0 — a fixed
    function of the configuration, the same for every seed."""
    if n == 1:
        return [0]
    out = [round(spread * base * (2.0 * k / (n - 1) - 1.0)) for k in range(n)]
    out[n // 2] -= sum(out)
    return out


def _replicas(t: dict, scale: float) -> int:
    """A template's replica count at ``scale`` (the CPU rehearsal's tiny
    sizes; 1.0 on the chip)."""
    return max(1, round(t["replicas"] * scale))


def pods_per_request(cfg: dict, scale: float = 1.0) -> int:
    return sum(t["count"] * _replicas(t, scale) for t in cfg["deployments"])


def _shape(t: dict, d: int, rot: int) -> Tuple[float, float]:
    cpu, mem = t["cpu"], t["memory_gib"]
    return (cpu["base"] + cpu["step"] * ((d + rot) % cpu["mod"]),
            (mem["base"] + mem["step"] * ((d + rot) % mem["mod"])) * GIB)


def make_cluster(cfg: dict, rng: random.Random, rot: int,
                 scale: float = 1.0) -> Cluster:
    """One whole-cluster request: each template's fixed replica deltas
    permuted over its deployments by ``rng`` (the configuration's, not the
    run's seed), the request shapes rotated by ``rot``."""
    groups, counts = [], []
    for t in cfg["deployments"]:
        base = _replicas(t, scale)
        deltas = _deltas(t["count"], base, t.get("replica_spread", 0.0))
        rng.shuffle(deltas)
        every = t.get("tolerate_every", 1)
        for d, delta in enumerate(deltas):
            n = max(1, base + delta)
            cpu, mem = _shape(t, d, rot)
            name = f"{t['prefix']}{d}"
            groups.append({
                "name": name, "cpu": cpu, "memory": mem,
                "constraint": t["constraint"],
                "tolerations": list(t.get("tolerations", [])
                                    if d % every == every - 1 else []),
                "pods": [f"{name}-{i}" for i in range(n)],
                "next": n,
            })
            counts.append(n)
    return Cluster(groups, (rot, tuple(counts)))


def burst_pool(cfg: dict, n: int, first_rot: int = 0,
               scale: float = 1.0) -> List[Cluster]:
    """``n`` distinct whole-cluster requests, a fixed function of the
    configuration: request ``k`` has rotation ``first_rot + k`` and its own
    permutation of the replica deltas, drawn from the configuration's name.
    No two are equal (checked).  The seed of a run does not enter here: it
    salts the names and orders the deployments inside each request
    (:func:`salted`) and orders the requests of a pass, so every seed does
    the same work — on the chip the time of a c3 request followed its
    permutation and rotation with a spread of a third."""
    rng = random.Random(f"{cfg['name']}/{first_rot}")
    pool, seen = [], set()
    for rot in range(first_rot, first_rot + n):
        for _ in range(64):
            c = make_cluster(cfg, rng, rot, scale)
            if c.key not in seen:
                break
        else:
            raise RuntimeError("could not draw a request that differs from "
                               "the rest of its pool")
        seen.add(c.key)
        pool.append(c)
    return pool


def salted(cluster: Cluster, seed: int) -> Cluster:
    """The same request for this seed: every deployment renamed with a salt
    from the seed and the deployments listed in a seeded order.  Sizes,
    shapes and constraints are untouched, so every seed asks for the same
    work."""
    rng = random.Random(seed)
    salt = f"s{rng.randrange(16 ** 5):05x}"
    groups = []
    for g in cluster.groups:
        name = g["name"] + salt
        groups.append({**g, "name": name,
                       "pods": [f"{name}-{i}" for i in range(len(g["pods"]))]})
    rng.shuffle(groups)
    return Cluster(groups, cluster.key)


# ---------------------------------------------------------------------------
# reconcile: one standing cluster, a deck of steps
# ---------------------------------------------------------------------------


class Steps:
    """The step stream of a ``reconcile`` cell over one standing cluster.
    Cluster and stream are the CONFIGURATION's: the deck's order (a deck holds
    the traffic file's ``deck`` entries ``{kind, n, copies}``, shuffled anew
    each time it is exhausted), the deployment every ``scale_up`` grows and
    the pods every ``scale_down`` removes are drawn from a generator keyed by
    the configuration's and the traffic file's names, which runs on from pass
    to pass.  So the k-th pass of every run is the same steps, and the cluster
    at the k-th pass boundary the same problem.  ``seed`` does what it does
    in a burst cell (:func:`salted`): it names the deployments and orders
    them inside the cluster.

    ``scale_up`` adds ``n`` new pods to one deployment drawn uniformly;
    ``scale_down`` removes, uniformly among the live pods, as many as were
    added since the last ``scale_down``.  The live set is the generator's own
    ledger, never read back from the client: ``(deployment, ordinal)`` pairs,
    the deployment by its place in the configuration, in an order no seed
    touches.  ``stream`` names another stream over a ledger of its own (the
    warm-up's)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 scale: float = 1.0, stream: str = "steps") -> None:
        plain = make_cluster(
            cfg, random.Random(f"{cfg['name']}/standing"), 0, scale)
        for d, g in enumerate(plain.groups):
            g["deployment"] = d
        #: the standing cluster as this seed names and orders it
        self.cluster = salted(plain, seed)
        #: deployment -> its group's index in this seed's order
        self.at = sorted(range(len(plain.groups)), key=lambda i:
                         self.cluster.groups[i]["deployment"])
        self.rng = random.Random(
            f"{cfg['name']}/{traffic['name']}/{stream}")
        self.deck_def = [(e["kind"], int(e.get("n", 0)))
                         for e in traffic["deck"]
                         for _ in range(int(e.get("copies", 1)))]
        self.deck: List[Tuple[str, int]] = []
        self.decks_dealt = 0
        self.added_since_down = 0
        #: steps taken since the last ``scale_down``
        self.since_down = 0
        self.live: List[Tuple[int, int]] = [
            (d, i) for d, g in enumerate(plain.groups)
            for i in range(len(g["pods"]))]
        self.kinds: Dict[str, int] = {}

    def _name(self, d: int, i: int) -> str:
        return f"{self.cluster.groups[self.at[d]]['name']}-{i}"

    def next(self, forced: Optional[Tuple[str, int]] = None) -> dict:
        """``{kind, group, added: [names], removed: [names]}`` (``group``
        indexes ``cluster.groups``) — and the ledger already holds the step.
        ``forced`` is a ``(kind, n)`` taken in place of the deck's next card
        (the warm-up's steps)."""
        if forced is None and not self.deck:
            self.deck = list(self.deck_def)
            self.rng.shuffle(self.deck)
            self.decks_dealt += 1
        kind, n = forced or self.deck.pop()
        self.kinds[kind] = self.kinds.get(kind, 0) + 1
        if kind == "scale_up":
            d = self.rng.randrange(len(self.at))
            g = self.cluster.groups[self.at[d]]
            new = [(d, g["next"] + i) for i in range(n)]
            g["next"] += n
            self.live.extend(new)
            self.added_since_down += n
            self.since_down += 1
            return {"kind": kind, "group": self.at[d],
                    "added": [self._name(*pod) for pod in new],
                    "removed": []}
        if kind == "scale_down":
            gone = []
            for _ in range(min(self.added_since_down, len(self.live) - 1)):
                i = self.rng.randrange(len(self.live))
                self.live[i], self.live[-1] = self.live[-1], self.live[i]
                gone.append(self.live.pop())
            self.added_since_down = self.since_down = 0
            return {"kind": kind, "group": -1, "added": [],
                    "removed": [self._name(*pod) for pod in gone]}
        raise ValueError(f"unknown step kind {kind!r}")

    def settle(self, live: Optional[List[Tuple[int, int]]] = None) -> Cluster:
        """The standing cluster as the ledger has it now, or had it when
        ``live`` was copied off it (a pass boundary): the pods of every group
        are its live ones, for the comparison after the window."""
        by_group: Dict[int, List[str]] = {}
        for d, i in self.live if live is None else live:
            by_group.setdefault(self.at[d], []).append(self._name(d, i))
        return Cluster([{**g, "pods": by_group.get(gi, [])}
                        for gi, g in enumerate(self.cluster.groups)],
                       ("settled",))


def boundary_costs(boundaries: int, cost_passes: int) -> List[Optional[str]]:
    """What the $ of a session's view at each of a window's pass boundaries
    are compared for (``plainref.compare``'s third field of a case).  The
    FIRST ``cost_passes`` make ``cost_ratio``, however many more a faster
    program's window holds: the metric reads the same problems in every run.
    A window that held fewer makes none (a traced run's; an untraced one's
    runs on to the ``cost_passes``-th).  The configuration's cost ceiling is
    held on those and on the LAST boundary, where what incremental steps
    cost in $ has had the longest to grow; the boundaries between are the
    validator's alone, so the reference is packed ``cost_passes`` + 1 times
    a run at most."""
    what: List[Optional[str]] = [None] * boundaries
    for k in range(min(boundaries, cost_passes)):
        what[k] = "metric" if boundaries >= cost_passes else "ceiling"
    if boundaries and what[-1] is None:
        what[-1] = "ceiling"
    return what


# ---------------------------------------------------------------------------
# plain -> the program's input types (the client side's objects)
# ---------------------------------------------------------------------------


def provisioners_plain(cfg: dict) -> List[dict]:
    """The configuration's provisioners with the AWS-overlay defaults the
    operator applies (``Provisioner.with_defaults``) spelled out as the
    filter the plain reference applies to catalog rows."""
    out = []
    for p in cfg["provisioners"]:
        out.append({
            "name": p["name"], "weight": int(p.get("weight", 0)),
            "taints": [dict(t) for t in p.get("taints", [])],
            "filter": dict(cfg["provisioner_defaults"]),
        })
    return out


class ProgramInputs:
    """Builds the program's objects for the client: the catalog and the
    provisioners once, pods per request."""

    def __init__(self, cfg: dict) -> None:
        from karpenter_tpu.models import labels as L
        from karpenter_tpu.models.catalog import generate_catalog
        from karpenter_tpu.models.pod import Taint
        from karpenter_tpu.models.provisioner import Provisioner

        if cfg["catalog"] != "full":
            raise ValueError(f"unknown catalog {cfg['catalog']!r}")
        self.L = L
        self.catalog = generate_catalog(full=True)
        self.provisioners = [
            Provisioner(
                name=p["name"], weight=int(p.get("weight", 0)),
                taints=[Taint(key=t["key"], value=t.get("value", ""),
                              effect=t["effect"])
                        for t in p.get("taints", [])]).with_defaults()
            for p in cfg["provisioners"]]

    def check_catalog(self, rows: dict) -> Optional[str]:
        """The catalog the client sends must be the one the plain reference
        reads from ``catalogs/<name>.json``; returns what differs."""
        mine = {r["name"]: r for r in rows["types"]}
        if len(mine) != len(self.catalog):
            return f"{len(self.catalog)} types vs {len(mine)} rows"
        for it in self.catalog:
            r = mine.get(it.name)
            if r is None:
                return f"type {it.name} not in the data file"
            for k in ("cpu", "memory", "pods"):
                if abs(it.allocatable[k] - r["allocatable"][k]) > 1e-6 * max(
                        1.0, abs(r["allocatable"][k])):
                    return f"{it.name} allocatable {k} differs"
            offers = [[o.zone, o.capacity_type, o.price]
                      for o in it.offerings if o.available]
            if offers != r["offerings"]:
                return f"{it.name} offerings differ"
        return None

    def pods(self, groups: List[dict], names: Optional[Dict[int, List[str]]]
             = None) -> list:
        """``PodSpec`` objects for every pod of ``groups`` (or only for
        ``names[group_index]``), fresh dicts per pod as a decoded API object
        has them."""
        from karpenter_tpu.models.pod import (
            LabelSelector,
            PodAffinityTerm,
            PodSpec,
            Toleration,
            TopologySpreadConstraint,
        )

        L = self.L
        out = []
        for gi, g in enumerate(groups):
            want = g["pods"] if names is None else names.get(gi, ())
            if not want:
                continue
            app = g["name"]
            sel = LabelSelector.of({"app": app})
            tols = [Toleration(key=t["key"], operator=t.get("operator",
                                                           "Equal"),
                               value=t.get("value", ""), effect=t["effect"])
                    for t in g["tolerations"]]
            kind = g["constraint"]
            plugin = (None if kind in BUILTIN_CONSTRAINTS
                      else constraint_plugin(kind))
            cpu, mem = g["cpu"], g["memory"]
            for name in want:
                kw = dict(name=name, labels={"app": app},
                          requests={"cpu": cpu, "memory": mem},
                          tolerations=list(tols), owner_key=app)
                if kind == "zone_spread":
                    kw["topology_spread"] = [TopologySpreadConstraint(
                        1, L.ZONE, "DoNotSchedule", sel)]
                elif kind == "hostname_anti_affinity":
                    kw["affinity_terms"] = [PodAffinityTerm(
                        sel, L.HOSTNAME, anti=True)]
                elif plugin is not None:
                    plugin.program(kw, g)
                out.append(PodSpec(**kw))
        return out
