"""``c3.reconcile`` on the CPU at 200 standing pods: one session
over c3's standing cluster, rehearsed for at least four passes on two seeds,
traced once, and the control of its comparison."""

import json
import os

import pytest

import control
import lastline
import run

CELL = "c3.reconcile"
SCALE = 0.02  # 200 standing pods: c3's 100 services, two pods each
SEEDS = (2 ** 31 + 4099, 3 * 2 ** 31 + 17)
#: the three metrics of the existing-node seed
SEED_METRICS = ("seed_existing_ms", "scan_existing_nodes",
                "scan_existing_taint_closed")


def rehearse(bench, seed, trace, seconds):
    # the cell asks for four chips: the CPU sidecar shows four devices
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    line = run.run_cell(bench, CELL, seed, seconds, trace, platform="cpu",
                        scale=SCALE, sidecar_env=env)
    earlier = [json.loads(e) for e in run.EARLIER]
    return line, earlier


@pytest.fixture(scope="module")
def untraced(bench):
    # a step takes ~0.25 s on the CPU: 60 s hold four passes of 60 steps
    return [rehearse(bench, seed, 0, 60.0) for seed in SEEDS]


def test_every_step_of_four_passes_is_valid(bench, untraced):
    for line, earlier in untraced:
        assert lastline.violations(json.dumps(line), CELL, 0, bench) == []
        assert line["correct"] is True, line["compared"]
        assert line["failed"] == 0
        assert line["compared"]["unplaced"] == [0, 0]
        assert line["compared"]["violations"] == [0, 0]
        marks = [e for e in earlier if e["info"] == "boundaries"][-1]
        assert len(marks["passes"]) >= 4
        assert marks["unplaced_on_the_way"] == 0
        assert line["metrics"]["cold_served"]["value"] == 0.0
        assert line["metrics"]["heap_kept_blocks"]["value"] >= 0.0


def test_cost_ratio_is_the_configurations_whatever_the_seed(untraced):
    ratios = {line["metrics"]["cost_ratio"]["value"] for line, _ in untraced}
    assert len(ratios) == 1 and 0 < ratios.pop() <= 1.02


def test_a_traced_window_carries_the_seed_metrics(bench):
    line, _ = rehearse(bench, SEEDS[0], 1, 2.0)
    assert lastline.violations(json.dumps(line), CELL, 1, bench) == []
    assert line["correct"] is True, line["compared"]
    for name in SEED_METRICS:
        assert line["metrics"][name]["value"] > 0, name


def test_the_control_holds_for_the_cell(bench):
    """The sound reference reads correct, every broken guarantee of the
    configuration (each also at the last boundary alone) not correct."""
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    got = dict(control.verdicts(cell, SEEDS[1], SCALE))
    assert set(got) == {None, "anti", "taints", "price", "anti@last",
                        "taints@last", "price@last"}
    assert {rule: v["correct"] for rule, v in got.items()} == {
        rule: rule is None for rule in got}
