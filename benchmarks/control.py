#!/usr/bin/env python3
"""The control of the comparison, at a cell's own size.

    python3 benchmarks/control.py --workload c2.burst --seeds 11,2147483659,977

For each seed it builds what a run of the cell compares (burst: every
request of the pool as that seed sends it; reconcile: the standing cluster after 150
steps of the deck), puts the PLAIN REFERENCE in the program's place — once
sound, once for each guarantee of the configuration broken — and prints the
numbers compared beside their limits.  The system states no precision, so the
control breaks a guarantee: the sound reference has to read correct, every
broken one not correct.  No chip is needed and none is touched; the
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import gen  # noqa: E402
import plainref  # noqa: E402

RULES = {"zone_spread": ["spread", "price"],
         "hostname_anti_affinity": ["anti", "taints", "price"]}


def cases(cell: dict, seed: int) -> tuple:
    cfg = gen.load_config(cell["config"])
    traffic = gen.load_traffic(cell["traffic"])
    if traffic["kind"] == "burst":
        pool = gen.burst_pool(cfg, int(traffic["pool"]))
        return cfg, [gen.salted(c, seed).groups for c in pool]
    cluster = gen.make_cluster(
        cfg, random.Random(f"{cfg['name']}/standing"), 0)
    steps = gen.Steps(cluster, traffic, seed)
    for _ in range(150):
        steps.next()
    return cfg, [steps.settle().groups]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg, clusters = cases(cell, seed)
        rows = gen.load_catalog(cfg["catalog"])
        provs = gen.provisioners_plain(cfg)
        kinds = {t["constraint"] for t in cfg["deployments"]}
        rules = [None] + sorted({r for k in kinds for r in RULES.get(k, [])})
        for rule in rules:
            answers = [(g, plainref.ffd(g, provs, rows["types"],
                                        rows["zones"], break_rule=rule))
                       for g in clusters]
            v = plainref.compare(answers, provs, rows["types"], rows["zones"],
                                 float(cfg["guarantees"]["cost_ceiling"]), 0)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "broken": rule, "correct": v["correct"],
                              "numbers": v["numbers"]}), flush=True)
            ok &= v["correct"] == (rule is None)
    print("control " + ("holds: sound reads correct, every broken guarantee "
                        "not correct" if ok else "FAILED"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
