"""``benchmarks/metrics/decode_catalog_held.json`` (ISSUE 33) through the
harness's own reader, beside ``test_harness_readers.py``: a reading on a
plain context, 0.0 and no raise on a program without the family, the entry
``BENCHMARK.json`` declares for it, and a real scrape of a served sidecar so
that a renamed family or label cannot turn the metric into a silent 0.0."""

import json
import os

import harness_path
import pytest
import run
import scrape
from test_codec_templates import default_prov, deployments

from karpenter_tpu.metrics import (
    REQUEST_CATALOG,
    REQUEST_CATALOG_HOW,
    Registry,
)
from karpenter_tpu.service.client import RemoteScheduler
from karpenter_tpu.service.server import SolverService, make_server
from karpenter_tpu.solver.scheduler import BatchScheduler

bench = harness_path.bench  # the harness's fixture
BENCH_DIR = os.path.dirname(harness_path.HARNESS_TESTS)
NAME = "decode_catalog_held"
M = "karpenter_solver_request_catalog_total"
M_OTHER = "karpenter_solver_request_decode_pods_total"


@pytest.fixture(scope="module")
def spec() -> dict:
    with open(os.path.join(BENCH_DIR, "metrics", f"{NAME}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reader(spec):
    return run._module(os.path.join(BENCH_DIR, "readers",
                                    f"{spec['reader']}.py"),
                       f"reader_{spec['reader']}")


def ctx(before, after, requests=4):
    return {"before": before, "after": after, "requests": requests}


BEFORE = [(M, {"how": "held"}, 2.0), (M, {"how": "decoded"}, 3.0),
          (M, {"how": "unknown"}, 0.0), (M_OTHER, {"how": "templated"}, 7.0)]


@pytest.mark.parametrize("held,decoded,unknown,want", [
    (6.0, 3.0, 0.0, 1.0),     # every request of the window by name
    (2.0, 7.0, 0.0, 0.0),     # every request in full
    (5.0, 4.0, 1.0, 0.75),    # one refused and sent again with its list
], ids=["all_held", "none_held", "one_resent"])
def test_a_reading_on_a_plain_context(spec, reader, held, decoded, unknown,
                                      want):
    after = [(M, {"how": "held"}, held), (M, {"how": "decoded"}, decoded),
             (M, {"how": "unknown"}, unknown),
             (M_OTHER, {"how": "templated"}, 99.0)]
    assert reader.read(ctx(BEFORE, after), **spec["args"]) == want


def test_a_program_without_the_family_reads_zero_and_never_raises(
        spec, reader):
    """The parent of this PR is traced with this file laid over it: its
    line has to carry the metric."""
    old = [s for s in BEFORE if s[0] != M]
    assert reader.read(ctx(old, old), **spec["args"]) == 0.0
    assert reader.read(ctx([], []), **spec["args"]) == 0.0
    assert reader.read(ctx(BEFORE, BEFORE, requests=0), **spec["args"]) is None


def test_the_declared_entry_is_the_files(bench, spec):
    names = [m["name"] for m in bench["per_layer"]]
    # appended after what PR 28 left (later PRs append on), nothing moved
    assert names.index(NAME) == names.index("coalesce_merges") + 1
    decl = bench["per_layer"][names.index(NAME)]
    assert decl == {k: spec[k] for k in ("name", "unit", "better", "source",
                                         "layer", "moves")}
    assert (decl["unit"], decl["better"], decl["layer"], decl["moves"]) == (
        "requests", "higher", "server parse + decode", "solve_ms")
    # no `workloads`: every cell that reports solve_ms reports it
    assert "workloads" not in decl
    assert spec["args"] == {"metric": REQUEST_CATALOG,
                            "labels": [{"how": REQUEST_CATALOG_HOW[0]}]}
    assert decl["layer"] in {m["layer"] for m in bench["per_layer"]
                             if m["name"] != NAME}  # a layer the file names


def test_read_layer_metrics_puts_it_on_the_line(bench):
    after = [(M, {"how": "held"}, 6.0), (M, {"how": "decoded"}, 3.0)]
    only = {**bench, "per_layer": [m for m in bench["per_layer"]
                                   if m["name"] == NAME]}
    assert run.read_layer_metrics(only, "c3.burst", ctx(BEFORE, after)) == {
        NAME: {"value": 1.0, "unit": "requests"}}
    assert run.read_layer_metrics(only, "c2.burst", ctx([], [])) == {
        NAME: {"value": 0.0, "unit": "requests"}}


def test_the_file_reads_what_the_door_counts(spec, reader, small_catalog):
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    srv, port = make_server(svc, port=0)
    remote = RemoteScheduler(f"127.0.0.1:{port}", backend="oracle",
                             registry=Registry())
    try:
        # the warm-up's first request goes in full; the window's by name
        remote.solve(deployments(2, 5, "w"), [default_prov()], small_catalog)
        before = scrape.parse_metrics(reg.expose())
        for k in range(3):
            remote.solve(deployments(2, 5, f"m{k}"), [default_prov()],
                         small_catalog)
        after = scrape.parse_metrics(reg.expose())
    finally:
        remote.close()
        srv.stop(grace=None)
        svc.close()
    assert reader.read(ctx(before, after, requests=3), **spec["args"]) == 1.0
    assert reader.read(ctx([], before, requests=1), **spec["args"]) == 0.0
