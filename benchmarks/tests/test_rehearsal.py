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
    earlier = [json.loads(e) for e in run.EARLIER]
    # device busy time exists only in a traced run; cost_ratio only where
    # the window held the passes it is read off (an untraced one always did)
    marks = [e for e in earlier if e["info"] == "boundaries"]
    short = bool(marks) and not marks[-1]["in_cost_ratio"]
    assert not (short and not trace)
    assert missing == ({"cost_ratio"} if short else
                       set() if trace else {"device_busy_ms"}), missing
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    # the window is whole passes, and every answer in it was compared
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
        traffic = gen.load_traffic("reconcile")
        deck = sum(e["copies"] for e in traffic["deck"])
        assert line["attempted"] == deck * window["passes"]
        # every pass boundary was compared: the first cost_passes for the
        # metric, the last for the ceiling, no other priced
        compared = [e for e in earlier if e["info"] == "comparison"][-1]
        costs = gen.boundary_costs(window["passes"], traffic["cost_passes"])
        assert compared["compared"] == window["passes"] == len(
            marks[-1]["passes"])
        assert [p["cost_compared_for"] for p in marks[-1]["passes"]] == costs
        assert ["ffd" in c for c in compared["per_case"]] == [
            c is not None for c in costs]
        assert trace or window["passes"] >= traffic["cost_passes"]
        assert marks[-1]["unplaced_on_the_way"] == 0
        assert marks[-1]["between_share"] < 0.005


def test_a_reconcile_cell_reads_one_cost_ratio_whatever_seed_and_window(
        bench):
    """The step stream is the configuration's and ``cost_ratio`` is taken at
    the first ``cost_passes`` pass boundaries: two seeds read it equal, and
    so does a window of twice the passes.  (The long window is not held to
    ``correct``: from the third pass on, the program under test leaves a pod
    without a node in some ``scan`` steps; PERF.md section 7.)"""
    n = gen.load_traffic("reconcile")["cost_passes"]

    def read(seed, seconds):
        line = rehearse(bench, "c2.reconcile", 0, seconds=seconds, seed=seed)
        earlier = [json.loads(e) for e in run.EARLIER]
        marks = [e for e in earlier if e["info"] == "boundaries"][-1]
        cases = [e for e in earlier if e["info"] == "comparison"][-1][
            "per_case"]
        assert marks["in_cost_ratio"] == n and line["failed"] == 0
        return (line["metrics"]["cost_ratio"]["value"], len(cases),
                [(p["pods"], c["cost"], c["ffd"])
                 for p, c in zip(marks["passes"][:n], cases)], line)

    # no time at all: the window runs on to the cost_passes-th boundary
    ratio, passes, priced, line = read(SEED, 0.1)
    assert passes == n
    assert line["correct"] is True, line["compared"]
    assert sum(c for _, c, _ in priced) / sum(
        f for _, _, f in priced) == pytest.approx(ratio, rel=1e-12)
    other = read(977, 0.1)
    assert other[3]["correct"] is True, other[3]["compared"]
    longer = read(SEED, 2.0 * line["attempted"]
                  * line["metrics"]["solve_ms"]["value"] / 1000.0)
    assert longer[1] > passes
    for got in (other, longer):
        assert got[2] == priced
        assert got[0] == pytest.approx(ratio, abs=0.0025)


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


def numbered(sess, fault):
    """``fault(k, added)`` after the k-th step since the session was last
    established, which is where the window starts."""
    establish, step = sess.solve, sess.solve_delta
    steps = [0]

    def solve(*args, **kw):
        steps[0] = 0
        return establish(*args, **kw)

    def solve_delta(added=(), removed=(), **kw):
        res = step(added=added, removed=removed, **kw)
        steps[0] += 1
        fault(steps[0], added)
        return res

    sess.solve, sess.solve_delta = solve, solve_delta
    return sess


DECK = sum(e["copies"] for e in gen.load_traffic("reconcile")["deck"])


def view_altered_at_the_first_boundary(sess):
    """Only the step that ends the first pass leaves a pod assigned to a node
    that does not list it; the steps after it mend the view."""
    def fault(k, added):
        name = next(iter(sess._assignments))
        if k == DECK:
            sess.was = (name, sess._assignments[name])
            sess._assignments[name] = next(
                n.name for n in sess._nodes.values()
                if all(p.name != name for p in n.pods))
        elif k == DECK + 1 and sess._assignments.get(sess.was[0]) not in (
                None, sess.was[1]):
            sess._assignments[sess.was[0]] = sess.was[1]

    return numbered(sess, fault)


def pod_left_out_for_one_step(sess):
    """The second step after an establishment (the deck's first four cards
    add pods, and so do the warm-up's) leaves one of its pods without a node;
    the third seats it."""
    held = []

    def fault(k, added):
        if k == 2:
            name = added[0].name
            held[:] = [name, sess._assignments.pop(name)]
            sess._infeasible[name] = "left out"
        elif k == 3:
            del sess._infeasible[held[0]]
            sess._assignments[held[0]] = held[1]

    return numbered(sess, fault)


FAULTS = [
    ("c2.burst", half_of_the_batch, "unplaced"),
    ("c3.burst", half_of_the_batch, "unplaced"),
    ("c2.burst", answer_altered, "violations"),
    ("c3.burst", answer_altered, "violations"),
    ("c2.reconcile", state_unchanged, "unplaced"),
    ("c2.reconcile", view_altered, "violations"),
    ("c2.reconcile", view_altered_at_the_first_boundary, "violations"),
    ("c2.reconcile", pod_left_out_for_one_step, "unplaced"),
]


@pytest.mark.parametrize("workload,fault,number", FAULTS)
def test_a_broken_timed_path_is_not_correct(bench, workload, fault, number):
    # (a reconcile window runs on to its second boundary, so the fault at
    # the first has a pass behind it)
    line = rehearse(bench, workload, 0, tamper=fault, seconds=1.5)
    assert line["correct"] is False
    value, limit = line["compared"][number]
    assert value > limit, line["compared"]
    if fault in (view_altered_at_the_first_boundary,
                 pod_left_out_for_one_step):
        # no other number fails, and the view the window closed on is sound:
        # judged on that alone, as before, the run would have read correct
        assert all(v <= lim for name, (v, lim) in line["compared"].items()
                   if name != number), line["compared"]
        earlier = [json.loads(e) for e in run.EARLIER]
        marks = [e for e in earlier if e["info"] == "boundaries"][-1]
        cases = [e for e in earlier if e["info"] == "comparison"][-1][
            "per_case"]
        assert cases[-1]["unplaced"] == cases[-1]["violations"] == 0
        if fault is pod_left_out_for_one_step:
            assert marks["unplaced_on_the_way"] == 1 == line["compared"][
                number][0]
        else:
            assert cases[0]["violations"] > 0 and len(cases) > 1


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
