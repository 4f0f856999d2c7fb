#!/usr/bin/env python3
"""The control of the comparison, at a cell's own size.

    python3 benchmarks/control.py --workload c2.burst --seeds 11,2147483659,977
    python3 benchmarks/control.py --config c2-50k-3az --traffic reconcile \
        --seeds 11,977

A cell is named as ``BENCHMARK.json`` names it, or, before it is in there (a
PR that adds a cell proves its control first), by its configuration and its
traffic mix.  For each seed it builds what a run of the cell compares (burst:
every request of the pool as that seed sends it; reconcile: the standing
cluster at the pass boundaries of a window of ``cost_passes`` + 2 passes of
the configuration's step stream, under that seed's names, each compared for
what ``gen.boundary_costs`` says), puts the PLAIN REFERENCE in the program's
place — once sound, once for each guarantee of the configuration broken — and
prints the numbers compared beside their limits.  A reconcile cell gets each
guarantee broken a second time at the LAST boundary alone (``<rule>@last``):
what a session that drifts does, sound while ``cost_ratio`` is read and
broken when the window closes.  The system states no precision, so the
control breaks a guarantee: the sound reference has to read correct, every
broken one not correct.  No chip is needed and none is touched; the
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import gen  # noqa: E402
import plainref  # noqa: E402

RULES = {"zone_spread": ["spread", "price"],
         "hostname_anti_affinity": ["anti", "taints", "price"]}


def cases(cell: dict, seed: int, scale: float = 1.0) -> tuple:
    """``(configuration, [(cluster, what its $ are compared for)])``."""
    cfg = gen.load_config(cell["config"])
    traffic = gen.load_traffic(cell["traffic"])
    if traffic["kind"] == "burst":
        pool = gen.burst_pool(cfg, int(traffic["pool"]), 0, scale)
        return cfg, [(gen.salted(c, seed).groups, plainref.METRIC)
                     for c in pool]
    steps, clusters = gen.Steps(cfg, traffic, seed, scale), []
    costs = gen.boundary_costs(int(traffic["cost_passes"]) + 2,
                               int(traffic["cost_passes"]))
    while len(clusters) < len(costs):
        steps.next()
        if not steps.deck:
            clusters.append(steps.settle().groups)
    return cfg, list(zip(clusters, costs))


def verdicts(cell: dict, seed: int, scale: float = 1.0):
    """``(broken, verdict)`` of the sound reference (``broken`` None) and of
    every control of the cell, under one seed."""
    cfg, clusters = cases(cell, seed, scale)
    rows = gen.load_catalog(cfg["catalog"])
    provs = gen.provisioners_plain(cfg)
    kinds = {t["constraint"] for t in cfg["deployments"]}
    rules = [None] + sorted({r for k in kinds for r in RULES.get(k, [])})
    if gen.load_traffic(cell["traffic"])["kind"] == "reconcile":
        rules += [f"{rule}@last" for rule in rules[1:]]
    for rule in rules:
        name, _, where = (rule or "").partition("@")
        answers = [(g, plainref.ffd(
            g, provs, rows["types"], rows["zones"], break_rule=name
            if name and (not where or k == len(clusters) - 1) else None),
            what) for k, (g, what) in enumerate(clusters)]
        yield rule, plainref.compare(
            answers, provs, rows["types"], rows["zones"],
            float(cfg["guarantees"]["cost_ceiling"]), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.config and args.traffic):
        ap.error("name the cell: --workload, or --config and --traffic")
    if args.workload:
        with open(os.path.join(os.path.dirname(HERE),
                               "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    else:
        cell = {"name": f"{args.config}/{args.traffic}",
                "config": args.config, "traffic": args.traffic}
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for rule, v in verdicts(cell, seed):
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "broken": rule, "correct": v["correct"],
                              "cost_ratio": v["cost_ratio"],
                              "numbers": v["numbers"]}), flush=True)
            ok &= v["correct"] == (rule is None)
    print("control " + ("holds: sound reads correct, every broken guarantee "
                        "not correct" if ok else "FAILED"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
