"""The nine ``delta_*`` metrics of ``standing.reconcile`` (ISSUE 39), each
through its own file and the reader that was there
(``counter_per_request``): 0.0 and no raise on a program without the
families (the parent of the PR is measured with these files laid over it),
the entries ``BENCHMARK.json`` declares for them, and a real scrape of a
served session, so that a renamed span, family or label cannot turn one of
them into a silent 0.0."""

import json
import os

import harness_path
import pytest
import run
import scrape
from test_standing_cluster import web_pod

from karpenter_tpu.metrics import Registry
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.service.client import DeltaSession
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver.scheduler import BatchScheduler

bench = harness_path.bench  # the harness's fixture
BENCH_DIR = os.path.dirname(harness_path.HARNESS_TESTS)
CELL = "standing.reconcile"

#: name -> (unit, better)
NEW = {
    "delta_ms": ("ms", "lower"),
    "delta_bookkeep_ms": ("ms", "lower"),
    "delta_host_ms": ("ms", "lower"),
    "delta_adopt_ms": ("ms", "lower"),
    "delta_full_steps": ("steps", "lower"),
    "delta_scan_steps": ("steps", "lower"),
    "delta_host_steps": ("steps", "higher"),
    "delta_displaced_pods": ("pods", "lower"),
    "delta_unplaced_pods": ("pods", "lower"),
}


def spec(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name: str, ctx: dict):
    s = spec(name)
    reader = run._module(os.path.join(BENCH_DIR, "readers",
                                      f"{s['reader']}.py"),
                         f"reader_{s['reader']}")
    return reader.read(ctx, **s["args"])


def ctx(before, after, requests=3):
    return {"before": before, "after": after, "requests": requests}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_families_reads_zero_and_never_raises(name):
    other = [("karpenter_solver_cold_start_fallbacks_total", {}, 2.0)]
    assert read(name, ctx(other, other)) == 0.0
    assert read(name, ctx([], [])) == 0.0
    assert read(name, ctx(other, other, requests=0)) is None


def test_the_declared_entries_are_the_files_appended_at_the_end(bench):
    names = [m["name"] for m in bench["per_layer"]]
    # appended together, in this order; later metrics may follow them
    at = names.index("delta_ms")
    block = bench["per_layer"][at:at + len(NEW)]
    assert [m["name"] for m in block] == list(NEW)
    earlier = {m["layer"] for m in bench["per_layer"][:at]}
    for decl in block:
        s = spec(decl["name"])
        assert decl == {**{k: s[k] for k in ("name", "unit", "better",
                                             "source", "layer", "moves")},
                        "workloads": [CELL]}
        assert (decl["unit"], decl["better"], decl["moves"]) == (
            *NEW[decl["name"]], "solve_ms")
        assert decl["layer"] in earlier  # a layer the file already names
        assert s["reader"] == "counter_per_request"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "standing-50k-3az", "reconcile", 1)


def test_only_the_session_cell_reads_them(bench):
    got = run.read_layer_metrics(bench, CELL, ctx([], []))
    assert {n: got[n]["value"] for n in NEW} == {n: 0.0 for n in NEW}
    assert not set(NEW) & set(run.read_layer_metrics(bench, "c2.burst",
                                                     ctx([], [])))


def test_a_served_session_reads_numbers_through_the_files(small_catalog):
    """One step of each tier (``host``, ``scan``, ``full``) of a served
    session, between two scrapes of the sidecar's own registry."""
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    srv, port = make_server(svc, port=0)
    sess = DeltaSession(f"127.0.0.1:{port}", registry=Registry())
    prov = Provisioner(name="default").with_defaults()
    try:
        sess.solve([web_pod(f"web-{i}") for i in range(6)]
                   + [PodSpec(name=f"big-{i}", requests={"cpu": 3.0},
                              owner_key="big") for i in range(3)],
                   [prov], small_catalog)
        before = scrape.parse_metrics(reg.expose())
        sess.solve_delta(added=[PodSpec(name=f"tiny-{i}", owner_key="tiny",
                                        requests={"cpu": 0.01})
                                for i in range(2)])
        sess.solve_delta(added=[web_pod("web-6"), web_pod("web-7")])
        sess.solve_delta(removed=["web-0"])
        after = scrape.parse_metrics(reg.expose())
    finally:
        sess.close()
        srv.stop(grace=None)
        svc.close()
    got = {name: read(name, ctx(before, after)) for name in NEW}
    third = pytest.approx(1.0 / 3.0)
    assert (got["delta_full_steps"], got["delta_scan_steps"],
            got["delta_host_steps"]) == (third, third, third)
    assert got["delta_displaced_pods"] == pytest.approx(4.0 / 3.0)
    assert got["delta_unplaced_pods"] == 0.0
    for name in ("delta_ms", "delta_bookkeep_ms", "delta_host_ms",
                 "delta_adopt_ms"):
        assert got[name] > 0.0, name
    # the three leaves lie inside the step's own span
    assert got["delta_ms"] > (got["delta_bookkeep_ms"] + got["delta_host_ms"]
                              + got["delta_adopt_ms"])
