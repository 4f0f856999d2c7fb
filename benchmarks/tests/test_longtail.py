"""``longtail.burst`` (PR 27): the cell's CPU rehearsal through ``run_cell``,
traced and untraced, and the control of its configuration — the plain
reference with one guarantee broken has to read not correct.

The configuration's deployment COUNT is not scaled (a request keeps its 1,640
groups: the group axis is what the cell is for); the replica scale makes
them 50 / 6 / 1 pods, 3,000 a request."""

import json
import os

import pytest

import gen
import lastline
import plainref
import run

CELL = "longtail.burst"
CONFIG = "longtail-15k"
SCALE = 0.2
SEED = 2 ** 31 + 4099  # the driver's seeds pass 32 signed bits
#: this PR's per-layer metrics are sums over a request's device scans: the
#: batch's own (1,640 groups on the 2,240 rung) and, where the relax rung
#: re-seats stranded pods, its repair solve's few groups on the smallest rung
SCAN = {"scan_groups": (1_640, 1_700), "scan_groups_padded": (2_240, 2_300),
        "scan_slot_retries": (0, 0)}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_its_last_line_meets_the_contract(bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    line = run.run_cell(bench, CELL, SEED, 2.0, trace, platform="cpu",
                        scale=SCALE, sidecar_env=env)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    assert lastline.violations(json.dumps(line), CELL, trace, bench) == []
    declared = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    missing = declared - set(line["metrics"])
    assert missing == (set() if trace else {"device_busy_ms"}), missing
    assert "pods_per_s" in line["metrics"]
    pool = gen.load_traffic("burst")["pool"]
    assert line["attempted"] % pool == 0
    earlier = [json.loads(e) for e in run.EARLIER]
    generator = [e for e in earlier if e["info"] == "generator"][-1]
    assert generator["pods_per_request"] == 3_000
    assert generator["distinct"] == generator["pool"] + generator["warm_pool"]
    compared = [e for e in earlier if e["info"] == "comparison"][-1]
    assert compared["compared"] == line["attempted"]
    # the scan ran at the dims of 1,640 groups; nothing cold, nothing
    # compiled, no slot retry inside the window
    for name, (low, high) in SCAN.items():
        assert low <= line["metrics"][name]["value"] <= high, name
    slots = line["metrics"]["scan_node_slots"]["value"]
    used = line["metrics"]["scan_nodes_used"]["value"]
    assert 0 < used <= slots
    for name in ("cold_served", "compiles", "jit_programs"):
        assert line["metrics"][name]["value"] == 0, name


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
@pytest.mark.parametrize("rule,number", [("spread", "violations"),
                                         ("price", "cost_ratio_max")])
def test_control_is_not_correct(rule, number, seed):
    cfg = gen.load_config(CONFIG)
    rows = gen.load_catalog(cfg["catalog"])
    provs = gen.provisioners_plain(cfg)
    cluster = gen.salted(gen.burst_pool(cfg, 1, seed % 24, SCALE)[0], seed)
    assert len(cluster.groups) == 1_640

    def verdict(answer):
        return plainref.compare(
            [(cluster.groups, answer)], provs, rows["types"], rows["zones"],
            float(cfg["guarantees"]["cost_ceiling"]), 0)

    sound = verdict(plainref.ffd(cluster.groups, provs, rows["types"],
                                 rows["zones"]))
    assert sound["correct"] and sound["cost_ratio"] == pytest.approx(1.0)
    broken = verdict(plainref.ffd(cluster.groups, provs, rows["types"],
                                  rows["zones"], break_rule=rule))
    assert not broken["correct"]
    value, limit = broken["numbers"][number]
    assert value > limit, broken["numbers"]


def test_the_control_script_knows_the_configurations_rules(bench):
    import control

    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = gen.load_config(cell["config"])
    kinds = {t["constraint"] for t in cfg["deployments"]}
    assert kinds == {"zone_spread", "none"}
    assert sorted({r for k in kinds for r in control.RULES.get(k, [])}) == [
        "price", "spread"]
