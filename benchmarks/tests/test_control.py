"""The comparison that decides ``correct`` fails its control: the plain
reference put in the program's place with one guarantee of the configuration
broken.  Sizes a test run can hold; the readings at the cells' own sizes are
in PERF.md (``control.py`` prints them)."""

import json
import os

import pytest

import gen
import plainref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: (configuration, guarantee broken, the number that has to fail)
CONTROLS = [
    ("c2-50k-3az", "spread", "violations"),
    ("c2-50k-3az", "price", "cost_ratio_max"),
    ("c3-10k-antiaffinity", "anti", "violations"),
    ("c3-10k-antiaffinity", "taints", "violations"),
    ("c3-10k-antiaffinity", "price", "cost_ratio_max"),
]


def setting(config, scale, seed):
    cfg = gen.load_config(config)
    rows = gen.load_catalog(cfg["catalog"])
    cluster = gen.burst_pool(cfg, 2, seed % 24, scale)[0]
    return cfg, rows, cluster


def verdict(cfg, rows, cluster, answer, unanswered=0):
    return plainref.compare(
        [(cluster.groups, answer)], gen.provisioners_plain(cfg),
        rows["types"], rows["zones"],
        float(cfg["guarantees"]["cost_ceiling"]), unanswered)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 977])
@pytest.mark.parametrize("config,rule,number", CONTROLS)
def test_control_is_not_correct(config, rule, number, seed):
    cfg, rows, cluster = setting(config, 0.1, seed)
    provs = gen.provisioners_plain(cfg)
    sound = plainref.ffd(cluster.groups, provs, rows["types"], rows["zones"])
    v = verdict(cfg, rows, cluster, sound)
    assert v["correct"] and v["cost_ratio"] == pytest.approx(1.0)
    broken = plainref.ffd(cluster.groups, provs, rows["types"],
                          rows["zones"], break_rule=rule)
    v = verdict(cfg, rows, cluster, broken)
    assert not v["correct"]
    value, limit = v["numbers"][number]
    assert value > limit, v["numbers"]


def test_an_unanswered_request_is_not_correct():
    cfg, rows, cluster = setting("c2-50k-3az", 0.02, 5)
    sound = plainref.ffd(cluster.groups, gen.provisioners_plain(cfg),
                         rows["types"], rows["zones"])
    assert not verdict(cfg, rows, cluster, sound, unanswered=1)["correct"]


def test_every_seed_sends_the_same_requests_salted_and_reordered():
    cfg = gen.load_config("c2-50k-3az")
    n = gen.load_traffic("burst")["pool"]
    pool = gen.burst_pool(cfg, n)
    assert [c.key for c in pool] == [c.key for c in gen.burst_pool(cfg, n)]
    for c in pool:
        assert c.n_pods == 50_000 and len(c.groups) == 20
        assert sorted(c.key[1]) == sorted(pool[0].key[1])
    assert len({c.key for c in pool}) == n
    warm = gen.burst_pool(cfg, 3, n)
    assert not {c.key for c in warm} & {c.key for c in pool}
    a, b = gen.salted(pool[0], 1), gen.salted(pool[0], 2 ** 31 + 5)
    assert [g["name"] for g in a.groups] != [g["name"] for g in b.groups]
    shape = lambda c: sorted((g["cpu"], g["memory"], len(g["pods"]))  # noqa
                             for g in c.groups)
    assert shape(a) == shape(b) == shape(pool[0])
    again = gen.salted(pool[0], 1)
    assert [g["pods"] for g in a.groups] == [g["pods"] for g in again.groups]


def test_reconcile_deck_keeps_the_cluster_standing():
    cfg = gen.load_config("c2-50k-3az")
    traffic = gen.load_traffic("reconcile")
    import random

    cluster = gen.make_cluster(cfg, random.Random(9), 0)
    steps = gen.Steps(cluster, traffic, 9)
    names = {nm for _, nm in steps.live}
    for _ in range(600):
        s = steps.next()
        names.difference_update(s["removed"])
        assert not names.intersection(s["added"])
        names.update(s["added"])
        assert 50_000 <= len(steps.live) <= 50_000 + 54 * 63
    assert names == {nm for _, nm in steps.live}
    assert steps.kinds["scale_down"] * 9 == steps.kinds["scale_up"]
    assert steps.settle().n_pods == len(names)


def test_files_are_found_by_name_and_agree_with_benchmark_json(bench):
    for c in bench["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", f"{w['traffic']}.json"))
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reader']}.py"))
