"""The convex-relaxation refinement rung (solver/relax.py, ISSUE 11).

Coverage map:
- never-worse invariant on adversarial scenarios: all-constrained batch
  (rung skips), single-type catalog (no mixing win available — the rung
  must tie or fall back, never ship costlier), already-optimal scan
  (one-shape batch the scan packs perfectly);
- byte-validity of rounded solutions (ground-truth validator + the exact
  schedulable-pod set);
- KT_RELAX=0 byte-parity with the scan path (the kill switch);
- delta chains skip the rung unless KT_RELAX_DELTA=1 opts full-solve
  boundaries in;
- megabatch slots skip the rung;
- precompile grid coverage (warm_startup / precompile_buckets warm the
  relax program; readiness keys on relax_signature);
- metrics zero-init (KT003) + the outcome partition.
"""

import os
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from karpenter_tpu.metrics import (  # noqa: E402
    RELAX_DURATION,
    RELAX_IMPROVEMENT,
    RELAX_OUTCOMES,
    RELAX_TOTAL,
    Registry,
)
from karpenter_tpu.models import labels as L  # noqa: E402
from karpenter_tpu.models.catalog import generate_catalog  # noqa: E402
from karpenter_tpu.models.instancetype import GIB  # noqa: E402
from karpenter_tpu.models.pod import (  # noqa: E402
    LabelSelector,
    PodSpec,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner  # noqa: E402
from karpenter_tpu.models.tensorize import tensorize  # noqa: E402
from karpenter_tpu.solver import relax  # noqa: E402
from karpenter_tpu.solver.scheduler import BatchScheduler  # noqa: E402
from karpenter_tpu.solver.tpu import TpuSolver  # noqa: E402
from karpenter_tpu.solver.validate import validate_solution  # noqa: E402


@pytest.fixture(scope="module")
def catalog():
    return generate_catalog(full=False)


@pytest.fixture(scope="module")
def full_catalog():
    return generate_catalog(full=True)


def provs():
    return [Provisioner(name="default").with_defaults()]


def mix_pods(n_per=40, n_dep=6, spread_deps=0, tag="rx"):
    """Complementary cpu-heavy / memory-heavy / balanced deployments —
    the mixing shape the rung wins on; the first ``spread_deps`` carry a
    hard zone spread (constraint-bearing boundary conditions)."""
    pods = []
    for d in range(n_dep):
        kind = d % 3
        if kind == 0:
            cpu, mem = 1.0 + (d % 3) * 0.5, 0.25 * GIB
        elif kind == 1:
            cpu, mem = 0.1 + 0.05 * d, (6.0 + 2 * (d % 2)) * GIB
        else:
            cpu, mem = 0.5 * (1 + d % 2), 2.0 * GIB
        sel = LabelSelector.of({"app": f"{tag}{d}"})
        tsc = ([TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)]
               if d < spread_deps else [])
        for i in range(n_per):
            pods.append(PodSpec(
                name=f"{tag}{d}-{i}", labels={"app": f"{tag}{d}"},
                requests={"cpu": cpu, "memory": mem},
                topology_spread=list(tsc),
                owner_key=f"{tag}{d}",
            ))
    return pods


def scan_solve(st, solver=None):
    solver = solver or TpuSolver()
    return solver.solve(st, track_assignments=True).result


class TestNeverWorse:
    """The min-of-two select on adversarial inputs: the shipped solution
    must NEVER cost more than the scan's, whatever the rung does."""

    def test_all_constrained_batch_skips(self, full_catalog):
        pods = mix_pods(n_per=30, spread_deps=6)
        st = tensorize(pods, provs(), full_catalog)
        res = scan_solve(st)
        cost0 = res.new_node_cost
        nodes0 = [n.name for n in res.nodes]
        reg = Registry()
        out, outcome = relax.refine(res, st, registry=reg)
        assert outcome == "skipped"
        assert out.new_node_cost == cost0
        assert [n.name for n in out.nodes] == nodes0

    def test_single_type_catalog_never_worse(self, full_catalog):
        # one instance type: no mixing win exists; the rung must tie or
        # fall back, and the shipped cost can never exceed the scan's
        one_type = [full_catalog[0]]
        pods = mix_pods(n_per=30)
        st = tensorize(pods, provs(), one_type)
        res = scan_solve(st)
        cost0 = res.new_node_cost
        out, outcome = relax.refine(res, st, registry=Registry())
        assert outcome in ("tied", "fallback", "improved", "skipped")
        assert out.new_node_cost <= cost0 + 1e-9
        errs = validate_solution(pods, provs(), out, one_type)
        assert not errs, errs

    def test_already_optimal_scan_never_worse(self, catalog):
        # ONE shape exactly filling its density-best candidate: the scan
        # is optimal, so the rung cannot improve — and must not regress
        pods = [PodSpec(name=f"u-{i}", labels={"app": "u"},
                        requests={"cpu": 1.0, "memory": 1.0 * GIB},
                        owner_key="u") for i in range(64)]
        st = tensorize(pods, provs(), catalog)
        res = scan_solve(st)
        cost0 = res.new_node_cost
        out, _outcome = relax.refine(res, st, registry=Registry())
        assert out.new_node_cost <= cost0 + 1e-9
        errs = validate_solution(pods, provs(), out, catalog)
        assert not errs, errs

    def test_mixed_batch_keeps_constrained_seats(self, full_catalog):
        """Constraint-bearing pods keep their scan seats as boundary
        conditions: the rung only re-seats pods from freed all-eligible
        nodes, so every spread pod's assignment survives verbatim."""
        pods = mix_pods(n_per=40, spread_deps=2)
        st = tensorize(pods, provs(), full_catalog)
        res = scan_solve(st)
        spread_names = {p.name for p in pods
                        if p.topology_spread}
        before = {n: res.assignments[n] for n in spread_names
                  if n in res.assignments}
        cost0 = res.new_node_cost
        out, _outcome = relax.refine(res, st, registry=Registry())
        assert out.new_node_cost <= cost0 + 1e-9
        for n, node in before.items():
            assert out.assignments[n] == node
        errs = validate_solution(pods, provs(), out, full_catalog)
        assert not errs, errs


class TestRoundedValidity:
    def test_improved_solution_is_valid_and_complete(self, full_catalog):
        # the rung's home turf: many complementary deployments at a node
        # count where the per-candidate ceil slack is noise (small
        # batches fall back — the scan's 4-node pack IS optimal there)
        pods = mix_pods(n_per=250, n_dep=20)
        st = tensorize(pods, provs(), full_catalog)
        res = scan_solve(st)
        cost0 = res.new_node_cost
        scheduled0 = set(res.assignments)
        reg = Registry()
        out, outcome = relax.refine(res, st, registry=reg)
        assert outcome == "improved", outcome
        assert out.new_node_cost < cost0 - 1e-9
        assert set(out.assignments) == scheduled0
        assert not out.infeasible
        errs = validate_solution(pods, provs(), out, full_catalog)
        assert not errs, errs
        # every shipped node is internally consistent: seated pods within
        # allocatable (the byte-validity of the rounded build)
        for n in out.nodes:
            rem = n.remaining()
            assert all(v >= -1e-6 for v in rem.values()), (n.name, rem)
        assert reg.gauge(RELAX_IMPROVEMENT).get() < 1.0

    def test_partition_lifts_only_clean_nodes(self, full_catalog):
        pods = mix_pods(n_per=40, spread_deps=2)
        st = tensorize(pods, provs(), full_catalog)
        res = scan_solve(st)
        elig, freed, lifted, seats = relax.eligible_partition(st, res)
        by_name = {n.name: n for n in res.nodes}
        spread_names = {p.name for p in pods if p.topology_spread}
        for name in freed:
            for q in by_name[name].pods:
                assert q.name not in spread_names
        assert set(seats) == freed
        for gi, pool in lifted.items():
            assert not st.groups[gi].pods[0].topology_spread
            assert len(pool) == sum(c.get(gi, 0) for c in seats.values())


class TestSchedulerRouting:
    def _warm_sched(self, pods, catalog, reg=None):
        sched = BatchScheduler(backend="tpu", registry=reg or Registry())
        sched.solve(pods, provs(), catalog)  # compiles scan + warms relax
        t0 = time.time()
        while not sched._tpu.warm_idle() and time.time() - t0 < 120:
            time.sleep(0.05)
        return sched

    def test_kt_relax_off_is_byte_parity_with_scan(self, full_catalog,
                                                   monkeypatch):
        pods = mix_pods(n_per=250, n_dep=20)
        sched = self._warm_sched(pods, full_catalog)
        monkeypatch.setenv("KT_RELAX", "0")
        called = []
        orig_refine = relax.refine
        monkeypatch.setattr(relax, "refine",
                            lambda *a, **k: called.append(1))
        off1 = sched.solve(pods, provs(), full_catalog)
        off2 = sched.solve(pods, provs(), full_catalog)
        assert not called  # the kill switch never reaches the rung
        assert off1.new_node_cost == off2.new_node_cost
        assert off1.assignments.keys() == off2.assignments.keys()
        monkeypatch.setattr(relax, "refine", orig_refine)
        monkeypatch.delenv("KT_RELAX")
        on = sched.solve(pods, provs(), full_catalog)
        assert on.new_node_cost < off1.new_node_cost - 1e-9

    def test_small_batches_skip_everywhere(self, catalog, monkeypatch):
        # <= native_batch_limit pods: the rung never runs (forced-tpu
        # small-batch tests and fuzz keep byte-stable scan results)
        pods = mix_pods(n_per=10)  # 60 pods
        sched = BatchScheduler(backend="tpu", registry=Registry())
        called = []
        monkeypatch.setattr(relax, "refine",
                            lambda *a, **k: called.append(1))
        sched.solve(pods, provs(), catalog)
        assert not called

    def test_first_solve_skips_and_warms_behind(self, full_catalog):
        pods = mix_pods(n_per=250, n_dep=20)
        reg = Registry()
        sched = BatchScheduler(backend="tpu", registry=reg)
        sched.solve(pods, provs(), full_catalog)
        c = reg.counter(RELAX_TOTAL)
        assert c.get({"outcome": "skipped"}) == 1.0
        assert c.get({"outcome": "improved"}) == 0.0
        t0 = time.time()
        while not sched._tpu.warm_idle() and time.time() - t0 < 120:
            time.sleep(0.05)
        st, _ = sched._tensorize(pods, provs(), full_catalog, (), None)
        assert sched._tpu.ready(relax.relax_signature(st))
        sched.solve(pods, provs(), full_catalog)
        assert c.get({"outcome": "improved"}) == 1.0

    def test_delta_chain_skips_rung_by_default(self, full_catalog,
                                               monkeypatch):
        pods = mix_pods(n_per=60)
        sched = self._warm_sched(pods, full_catalog)
        seen = []
        real_submit = sched._submit

        def spy(*a, **kw):
            seen.append(kw.get("relax"))
            return real_submit(*a, **kw)

        monkeypatch.setattr(sched, "_submit", spy)
        prev = sched.solve(pods, provs(), full_catalog)
        assert seen[-1] is None  # plain solve: policy defers to KT_RELAX
        add = [PodSpec(name="d-extra", labels={"app": "rx0"},
                       requests={"cpu": 1.0, "memory": 0.25 * GIB},
                       owner_key="rx0")]
        # force the full path: a huge delta trips the threshold guard
        sched.solve_delta(
            prev, added=add * 1,
            removed=[p.name for p in pods[: len(pods) // 2]],
            provisioners=provs(), instance_types=full_catalog)
        assert seen[-1] is False  # delta chains: rung off by default

    def test_kt_relax_delta_opts_full_boundaries_in(self, full_catalog,
                                                    monkeypatch):
        pods = mix_pods(n_per=60)
        sched = self._warm_sched(pods, full_catalog)
        seen = []
        real_submit = sched._submit

        def spy(*a, **kw):
            seen.append(kw.get("relax"))
            return real_submit(*a, **kw)

        monkeypatch.setattr(sched, "_submit", spy)
        monkeypatch.setenv("KT_RELAX_DELTA", "1")
        prev = sched.solve(pods, provs(), full_catalog)
        sched.solve_delta(
            prev, added=[],
            removed=[p.name for p in pods[: len(pods) // 2]],
            provisioners=provs(), instance_types=full_catalog)
        # the full-solve boundary defers to KT_RELAX (None), not False
        assert seen[-1] is None

    def test_megabatch_slots_skip_rung(self, full_catalog, monkeypatch):
        pods = mix_pods(n_per=60)
        sched = self._warm_sched(pods, full_catalog)
        seen = []
        real_submit = sched._submit

        def spy(*a, **kw):
            seen.append(kw.get("relax"))
            return real_submit(*a, **kw)

        monkeypatch.setattr(sched, "_submit", spy)
        reqs = [dict(pods=pods, provisioners=provs(),
                     instance_types=full_catalog)]
        for p in sched.submit_many(reqs):
            p.result()
        assert seen[-1] is False


class TestPrecompileCoverage:
    def test_warm_startup_covers_the_relax_program(self, catalog):
        sched = BatchScheduler(backend="tpu", registry=Registry())
        accepted = []
        sched._tpu.warm_async = lambda *a, **kw: True
        sched._tpu.warm_custom = (
            lambda sig, thunk, on_done=None: accepted.append(sig) or True)
        sched.warm_startup(provs(), catalog)
        warmed = set(accepted)
        for st in sched._profile_tensors(provs(), catalog, ()):
            assert relax.relax_signature(st) in warmed

    def test_warm_relax_marks_dispatch_key_ready(self, catalog):
        solver = TpuSolver()
        pods = mix_pods(n_per=5)
        st = tensorize(pods, provs(), catalog)
        sig = relax.relax_signature(st)
        assert not solver.ready(sig)
        assert relax.warm_relax(solver, st)
        t0 = time.time()
        while not solver.warm_idle() and time.time() - t0 < 120:
            time.sleep(0.05)
        assert solver.ready(sig)

    def test_iter_rung_buckets_onto_the_ladder(self):
        assert relax.iter_rung(1) == relax.RELAX_ITER_RUNGS[0]
        assert relax.iter_rung(64) == 64
        assert relax.iter_rung(65) == 128
        assert relax.iter_rung(10_000) == relax.RELAX_ITER_RUNGS[-1]
        for n in (relax.DEFAULT_RELAX_ITERS, 1, 37, 256):
            assert relax.iter_rung(n) in relax.RELAX_ITER_RUNGS

    def test_signature_keys_on_dims_and_iters(self, catalog):
        pods = mix_pods(n_per=5)
        st = tensorize(pods, provs(), catalog)
        s64 = relax.relax_signature(st, 64)
        s128 = relax.relax_signature(st, 128)
        assert s64 != s128
        assert ("relax_iters", 64) in s64
        dims = relax.relax_dims(st)
        assert set(dims) == {"G", "C", "R"}


class TestRelaxMetrics:
    def test_zero_init_full_population(self):
        reg = Registry()
        relax.zero_init_metrics(reg)
        for outcome in RELAX_OUTCOMES:
            assert reg.counter(RELAX_TOTAL).has({"outcome": outcome})
            assert reg.counter(RELAX_TOTAL).get({"outcome": outcome}) == 0.0
        assert RELAX_DURATION in reg.histograms
        assert RELAX_IMPROVEMENT in reg.gauges

    def test_scheduler_zero_inits_at_construction(self):
        reg = Registry()
        BatchScheduler(backend="oracle", registry=reg)
        for outcome in RELAX_OUTCOMES:
            assert reg.counter(RELAX_TOTAL).has({"outcome": outcome})

    def test_refine_counts_every_outcome_once(self, full_catalog):
        pods = mix_pods(n_per=30, spread_deps=6)  # all constrained
        st = tensorize(pods, provs(), full_catalog)
        res = scan_solve(st)
        reg = Registry()
        relax.refine(res, st, registry=reg)
        c = reg.counter(RELAX_TOTAL)
        total = sum(c.get({"outcome": o}) for o in RELAX_OUTCOMES)
        assert total == 1.0
        assert reg.histogram(RELAX_DURATION).count() == 1
