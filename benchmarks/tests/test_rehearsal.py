"""run.py's Python entry on the CPU at tiny sizes: every cell, both trace
modes — and the same entry with the timed path broken underneath, which has
to come out as not correct.

The tiny sizes and the platform are ARGUMENTS of ``run_cell``; the command
itself has no flag or variable that lets it pass on a CPU
(``test_the_command_refuses_a_cpu``).  A cell needs > 256 pods a request, or
the router serves it from the host oracle by policy."""

import json
import os
import statistics
import subprocess
import sys

import pytest

import gen
import lastline
import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: replica scale per configuration: ~400 pods a request
SCALE = {"c2-50k-3az": 0.008, "c3-10k-antiaffinity": 0.04}
SEED = 2 ** 31 + 4099  # the driver's seeds pass 32 signed bits


def rehearse(bench, workload, trace, tamper=None, seconds=2.0, seed=SEED):
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return run.run_cell(bench, workload, seed, seconds, trace,
                        platform="cpu", scale=SCALE[cell["config"]],
                        tamper=tamper, sidecar_env=env)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["c2.burst", "c3.burst", "c2.reconcile"])
def test_cell_runs_and_its_last_line_meets_the_contract(bench, workload,
                                                       trace):
    line = rehearse(bench, workload, trace)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert lastline.violations(json.dumps(line), workload, trace, bench) == []
    declared = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "workloads" not in m or workload in m["workloads"]}
    missing = declared - set(line["metrics"])
    # device busy time exists only in a traced run
    assert missing == (set() if trace else {"device_busy_ms"}), missing
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    # the window is whole passes, and every answer in it was compared
    earlier = [json.loads(e) for e in run.EARLIER]
    tiers = [e for e in earlier if e["info"] == "tiers"][-1]
    assert tiers["window_s"] >= 2.0
    # its passes are in the run's output, every one, beside the median pass;
    # solve_ms and pods_per_s are the whole window's
    window = [e for e in earlier if e["info"] == "window"][-1]
    assert len(window["pass_s"]) == window["passes"] >= 1
    assert window["requests"] == line["attempted"]
    assert sum(window["pass_s"]) <= tiers["window_s"]
    assert line["metrics"]["solve_ms"]["value"] == pytest.approx(
        tiers["window_s"] / line["attempted"] * 1000.0)
    assert window["median_solve_ms"] == pytest.approx(
        statistics.median(window["pass_s"]) * window["passes"]
        / line["attempted"] * 1000.0)
    assert 0 <= window["stalled_passes"] < window["passes"]
    if workload.endswith(".burst"):
        pool = gen.load_traffic("burst")["pool"]
        assert line["attempted"] == pool * window["passes"]
        assert line["metrics"]["pods_per_s"]["value"] == window["pods_per_s"]
        compared = [e for e in earlier if e["info"] == "comparison"][-1]
        assert compared["compared"] == line["attempted"]
    else:
        deck = sum(e["copies"] for e in gen.load_traffic("reconcile")["deck"])
        assert line["attempted"] == deck * window["passes"]


# ---- the timed path broken underneath: correct has to come out false ----


def half_of_the_batch(remote):
    """The server is handed half of the pods; the rest get no node."""
    inner = remote.solve

    def solve(pods, provisioners, catalog, **kw):
        return inner(pods[:len(pods) // 2], provisioners, catalog, **kw)

    remote.solve = solve
    return remote


def answer_altered(remote):
    """One pod of each answer is re-assigned to another node's name where the
    answer is produced."""
    inner = remote.solve

    def solve(pods, provisioners, catalog, **kw):
        res = inner(pods, provisioners, catalog, **kw)
        name = pods[0].name
        other = next(n.name for n in res.nodes
                     if n.name != res.assignments[name])
        res.assignments[name] = other
        return res

    remote.solve = solve
    return remote


def state_unchanged(sess):
    """Every step returns the session's view as it was."""
    sess.last_mode = "none"

    def solve_delta(added=(), removed=(), **kw):
        return sess.result()

    sess.solve_delta = solve_delta
    return sess


def view_altered(sess):
    """A step's merged view gets one pod assigned to a node that does not
    list it."""
    inner = sess.solve_delta

    def solve_delta(added=(), removed=(), **kw):
        res = inner(added=added, removed=removed, **kw)
        name = next(iter(sess._assignments))
        sess._assignments[name] = next(
            n.name for n in sess._nodes.values()
            if all(p.name != name for p in n.pods))
        return res

    sess.solve_delta = solve_delta
    return sess


FAULTS = [
    ("c2.burst", half_of_the_batch, "unplaced"),
    ("c3.burst", half_of_the_batch, "unplaced"),
    ("c2.burst", answer_altered, "violations"),
    ("c3.burst", answer_altered, "violations"),
    ("c2.reconcile", state_unchanged, "unplaced"),
    ("c2.reconcile", view_altered, "violations"),
]


@pytest.mark.parametrize("workload,fault,number", FAULTS)
def test_a_broken_timed_path_is_not_correct(bench, workload, fault, number):
    line = rehearse(bench, workload, 0, tamper=fault, seconds=1.5)
    assert line["correct"] is False
    value, limit = line["compared"][number]
    assert value > limit, line["compared"]


def test_the_command_refuses_a_cpu(tmp_path):
    """``python3 benchmarks/run.py ...`` on a machine whose jax finds no TPU
    exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "c3.burst", "--seed", "7", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing to serve" in p.stderr


def test_the_command_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "c2.burst",
         "--seed", "7", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
