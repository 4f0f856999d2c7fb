"""Tests of the benchmark's own files (run them with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``).  They are not
part of the repo's tier-1 suite, which collects ``tests/`` only."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


#: the session cell is out of BENCHMARK.json (the program leaves added pods
#: infeasible in delta mode ``scan``; PERF.md section 7), but its traffic
#: kind stays rehearsed: the tests put the cell and its tail metric back
RECONCILE_CELL = {
    "name": "c2.reconcile", "config": "c2-50k-3az", "traffic": "reconcile",
    "chips": 1, "why": "one DeltaSession over 50,000 standing pods, each "
    "request a solve_delta step"}
RECONCILE_P95 = {
    "name": "solve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
    "source": "host_clock", "workloads": ["c2.reconcile"]}


@pytest.fixture(scope="session")
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    if all(w["name"] != "c2.reconcile" for w in doc["workloads"]):
        doc["workloads"].append(dict(RECONCILE_CELL))
        doc["end_to_end"].append(dict(RECONCILE_P95))
    return doc
