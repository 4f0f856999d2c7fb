"""lastline.violations: a good and a bad line for each trace mode."""

import copy
import json

import pytest

import lastline

W = "c2.burst"


def good(bench, trace):
    metrics = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and W not in m["workloads"]:
            continue
        if m["name"] == "device_busy_ms" and not trace:
            continue  # the reader finds no trace and returns nothing
        metrics[m["name"]] = {"value": 1.5, "unit": m["unit"]}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 13958643712}
    line = {"correct": True, "attempted": 9, "failed": 0, "metrics": metrics,
            "device": device}
    if trace:
        device.update(busy_s=0.0171, window_s=12.4)
        line["breakdown"] = {"device_ops": [["fusion.1", 0.01]],
                             "idle_gaps": [["host:unattributed", 3.9]]}
    line["compared"] = {"unplaced": [0, 0]}
    return line


def check(line, trace, bench):
    return lastline.violations(json.dumps(line), W, trace, bench)


@pytest.mark.parametrize("trace", [0, 1])
def test_good_line_passes(bench, trace):
    assert check(good(bench, trace), trace, bench) == []


def _drop_metric(name):
    def f(line):
        del line["metrics"][name]
    return f


def _set(path, value):
    def f(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        if value is KeyError:
            del d[path[-1]]
        else:
            d[path[-1]] = value
    return f


BAD = [
    (0, _drop_metric("solve_ms"), "metrics.solve_ms is missing"),
    (0, _drop_metric("setup_s"), "metrics.setup_s is missing"),
    (1, _drop_metric("wire_ms"), "metrics.wire_ms is missing"),
    (1, _drop_metric("device_busy_ms"), "metrics.device_busy_ms is missing"),
    (1, _set(["device", "busy_s"], 0), "busy_s"),
    (1, _set(["device", "busy_s"], 0.0), "busy_s"),
    (1, _set(["device", "busy_s"], 99.0), "over window_s"),
    (1, _set(["device", "window_s"], KeyError), "window_s"),
    (0, _set(["device", "memory_peak_bytes"], KeyError), "memory_peak_bytes"),
    (1, _set(["device", "memory_peak_bytes"], 0), "memory_peak_bytes"),
    (0, _set(["device", "count"], 0), "device.count"),
    (0, _set(["device", "kind"], ""), "device.kind"),
    (0, _set(["metrics", "solve_ms"], 3.2), "not {value, unit}"),
    (0, _set(["metrics", "solve_ms", "value"], None), "finite number"),
    (0, _set(["metrics", "solve_ms", "value"], 0.0), "never 0"),
    (0, _set(["metrics", "solve_ms", "unit"], "s"), "declared 'ms'"),
    (0, _set(["metrics", "made_up"], {"value": 1, "unit": "x"}),
     "not declared"),
    (0, _set(["metrics", "solve_p95_ms"], {"value": 1, "unit": "ms"}),
     "not declared"),  # declared, but for c2.reconcile only
    (0, _set(["correct"], "yes"), "correct"),
    (0, _set(["failed"], 12), "more than attempted"),
    (0, _set(["attempted"], KeyError), "'attempted' is missing"),
    (0, _set(["compared"], KeyError), "'compared'"),
    (1, _set(["breakdown", "device_ops"], [["a", 1.0]] * 11), "at most 10"),
]


@pytest.mark.parametrize("trace,breaker,says", BAD)
def test_bad_line_is_named(bench, trace, breaker, says):
    line = copy.deepcopy(good(bench, trace))
    breaker(line)
    wrong = check(line, trace, bench)
    assert wrong and any(says in w for w in wrong), wrong


def test_compared_has_to_come_last(bench):
    line = good(bench, 0)
    line["later"] = 1
    assert any("last key" in w for w in check(line, 0, bench))


def test_not_json_and_unknown_workload(bench):
    assert lastline.violations("nope", W, 0, bench)
    assert lastline.violations("{}", "c9.none", 0, bench)
