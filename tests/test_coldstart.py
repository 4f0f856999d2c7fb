"""Compile-behind: cold device shapes are served by the warm tier while the
XLA program compiles in the background.

The reference bar is the Go FFD's zero-warmup ms-scale first solve
(designs/bin-packing.md:28-43): a reconcile loop must never stall on an XLA
compile.  The scheduler's auto policy therefore routes a solve whose shape
signature is not compiled yet to the native C++ tier (or the CPU oracle when
the batch has device-only constraints), kicks the compile off on a background
thread, and moves that shape on-device once the compile lands.
"""

import time

from karpenter_tpu.metrics import (
    SOLVER_BACKEND_DURATION,
    SOLVER_COLD_FALLBACKS,
    SOLVER_COMPILE_DURATION,
    SOLVER_COMPILE_IN_PROGRESS,
    Registry,
)
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.pod import LabelSelector, PodAffinityTerm, PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.solver.scheduler import BatchScheduler


def _wait_warm(sched: BatchScheduler, timeout: float = 180.0) -> None:
    t0 = time.time()
    while not sched._tpu.warm_idle():
        if time.time() - t0 > timeout:
            raise AssertionError("background compile did not finish in time")
        time.sleep(0.05)


class TestCompileBehind:
    def test_cold_shape_served_by_native_then_on_device(self, small_catalog):
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg, native_batch_limit=8)
        prov = Provisioner(name="default").with_defaults()
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(32)]

        r1 = sched.solve(pods, [prov], small_catalog)
        assert not r1.infeasible
        # the caller was served by the warm tier; no device execution happened
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "native"}) == 1
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "tpu"}) == 0

        _wait_warm(sched)
        assert reg.histogram(SOLVER_COMPILE_DURATION).count() == 1
        assert reg.gauge(SOLVER_COMPILE_IN_PROGRESS).get() == 0

        # same shape again: now solved on-device, no new fallback
        pods2 = [PodSpec(name=f"q{i}", requests={"cpu": 1.0}) for i in range(32)]
        r2 = sched.solve(pods2, [prov], small_catalog)
        assert not r2.infeasible
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "tpu"}) == 1
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "native"}) == 1

    def test_cold_device_only_batch_falls_back_to_oracle(self, small_catalog):
        """Positive pod-affinity is inexpressible in the native tier
        (native.has_topology), so its cold fallback is the CPU oracle."""
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg, native_batch_limit=8)
        prov = Provisioner(name="default").with_defaults()
        sel = LabelSelector.of({"app": "x"})
        pods = [
            PodSpec(name=f"p{i}", labels={"app": "x"}, requests={"cpu": 1.0},
                    affinity_terms=[PodAffinityTerm(sel, L.ZONE, anti=False)])
            for i in range(16)
        ]
        r = sched.solve(pods, [prov], small_catalog)
        assert not r.infeasible
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "oracle"}) == 1
        # placements must all share one zone (the affinity contract held)
        zones = {n.zone for n in r.nodes}
        assert len(zones) == 1
        _wait_warm(sched)

    def test_operator_warms_solver_on_election(self, small_catalog, monkeypatch):
        """Election-gated startup warmup (the LT-hydration analog,
        launchtemplate.go:77-88): the operator precompiles the solver shape
        ladder in the background before the reconcile loop needs it."""
        from karpenter_tpu.cloud.fake import FakeCloudProvider
        from karpenter_tpu.operator import Operator
        from karpenter_tpu.utils.clock import FakeClock

        monkeypatch.setattr(BatchScheduler, "WARM_PROFILES", ((4, 8, False),))
        clock = FakeClock()
        cloud = FakeCloudProvider(small_catalog, clock=clock)
        op = Operator(cloud, clock=clock, scheduler_backend="auto",
                      registry=Registry())
        op.state.apply_provisioner(Provisioner(name="default"))
        op.tick()  # elects -> hydrate -> warm_startup
        _wait_warm(op.scheduler)
        assert op.scheduler._tpu._ready  # at least one shape compiled
        assert op.registry.histogram(SOLVER_COMPILE_DURATION).count() >= 1
        assert op.registry.gauge(SOLVER_COMPILE_IN_PROGRESS).get() == 0

    def test_warm_queue_drains_beyond_concurrency_cap(self, small_catalog, monkeypatch):
        from karpenter_tpu.solver.tpu import TpuSolver

        # scan-warm queue semantics in isolation: the relax rung's extra
        # warms (tests/test_relax.py covers them) would shift the counts
        monkeypatch.setenv("KT_RELAX", "0")
        monkeypatch.setattr(TpuSolver, "MAX_CONCURRENT_WARMS", 1)
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)
        prov = Provisioner(name="default").with_defaults()
        accepted = sched.warm_startup([prov], small_catalog,
                                      profiles=((2, 4, False), (40, 80, False)))
        assert accepted == 2  # distinct G rungs: one runs, one queues
        _wait_warm(sched)
        assert len(sched._tpu._ready) == 2

    def test_stop_warms_drops_queue(self, small_catalog, monkeypatch):
        """Operator shutdown must wait only for in-flight compiles, never
        the queued ones: stop_warms clears the queue and blocks new spawns."""
        from karpenter_tpu.solver.tpu import TpuSolver

        monkeypatch.setenv("KT_RELAX", "0")  # scan warms only (count-exact)
        monkeypatch.setattr(TpuSolver, "MAX_CONCURRENT_WARMS", 1)
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)
        prov = Provisioner(name="default").with_defaults()
        accepted = sched.warm_startup([prov], small_catalog,
                                      profiles=((2, 4, False), (40, 80, False)))
        assert accepted == 2
        sched._tpu.stop_warms()
        _wait_warm(sched)
        assert len(sched._tpu._ready) <= 1  # queued warm never ran
        assert not sched._tpu._queued

    def test_failed_compile_backs_off(self, small_catalog, monkeypatch):
        """A shape whose compile fails is not hot-recompiled on every solve
        of that shape, and failures stay out of the duration histogram."""
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)

        def boom(*a, **k):
            raise RuntimeError("simulated XLA compile failure")

        monkeypatch.setattr(sched._tpu, "solve", boom)
        prov = Provisioner(name="default").with_defaults()
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(300)]
        r = sched.solve(pods, [prov], small_catalog)  # cold -> native fallback
        assert not r.infeasible
        _wait_warm(sched)
        assert reg.histogram(SOLVER_COMPILE_DURATION).count() == 0
        # within the backoff window no new warm is accepted for this shape
        from karpenter_tpu.models.tensorize import tensorize

        st = tensorize(pods, [prov], small_catalog)
        assert not sched._tpu.warm_async(st)
        assert sched._tpu._failed_until  # backoff armed

    def test_blocking_precompile_raises_when_a_compile_failed(
            self, small_catalog, monkeypatch):
        """``precompile_buckets(wait=True)`` is what ``serve --warmup``
        blocks on: a warm-up compile that failed must not come back as
        "N programs accepted" (the shape would be served from the host
        tiers indefinitely) — it raises, naming the failure.  The relax
        program (one more warm per profile shape) is part of the grid and
        compiles fine here: only the two scan programs fail."""
        import pytest

        from karpenter_tpu.solver.scheduler import WarmupFailed

        prov = Provisioner(name="default").with_defaults()

        def boom(*a, **k):
            raise RuntimeError("simulated XLA compile failure")

        def failing_scheduler():
            sched = BatchScheduler(backend="auto", registry=Registry())
            monkeypatch.setattr(sched._tpu, "solve", boom)
            monkeypatch.setattr(sched._tpu, "solve_many", boom)
            return sched

        with pytest.raises(WarmupFailed, match="2 of 3 bucket compiles "
                                               "failed.*simulated XLA"):
            failing_scheduler().precompile_buckets(
                [prov], small_catalog, profiles=((4, 64, False),),
                mega_slots=(2,), wait=True, timeout=120)
        # without wait the contract is unchanged: accepted, failing behind
        sched = failing_scheduler()
        assert sched.precompile_buckets(
            [prov], small_catalog, profiles=((4, 64, False),),
            mega_slots=(2,)) == 3
        _wait_warm(sched)

    def test_blocking_precompile_raises_when_the_relax_compile_failed(
            self, small_catalog, monkeypatch):
        """The relax program rides ``warm_custom``, not ``warm_async``: its
        failure must reach ``_warm_done`` all the same — logged, counted,
        and fatal to ``--warmup`` — while the scan programs compile."""
        import pytest

        from karpenter_tpu.solver import relax
        from karpenter_tpu.solver.scheduler import WarmupFailed

        def boom(*a, **k):
            raise RuntimeError("simulated relax lowering failure")

        monkeypatch.setattr(relax, "relax_jit", boom)
        sched = BatchScheduler(backend="auto", registry=Registry())
        prov = Provisioner(name="default").with_defaults()
        with pytest.raises(WarmupFailed, match="1 of 3 bucket compiles "
                                               "failed.*relax lowering"):
            sched.precompile_buckets(
                [prov], small_catalog, profiles=((4, 64, False),),
                mega_slots=(2,), wait=True, timeout=300)
        # the two scan programs landed and were recorded; relax was not
        assert sched.registry.histogram(
            SOLVER_COMPILE_DURATION).count() == 2

    def test_blocking_precompile_counts_compiled_programs(
            self, small_catalog):
        from karpenter_tpu.solver import relax

        sched = BatchScheduler(backend="auto", registry=Registry())
        prov = Provisioner(name="default").with_defaults()
        n = sched.precompile_buckets(
            [prov], small_catalog, profiles=((4, 64, False),),
            mega_slots=(2,), wait=True, timeout=300)
        # single-solve + relax + the 2-slot mega rung, every one through
        # _warm_done: the compile histogram counts what compiled
        assert n == 3 and sched._tpu.warm_idle()
        assert sched.registry.histogram(
            SOLVER_COMPILE_DURATION).count() == 3
        (st,) = sched._profile_tensors([prov], small_catalog, (),
                                       ((4, 64, False),))
        assert sched._tpu.ready(relax.relax_signature(st))

    def test_warm_startup_uses_cluster_size(self, small_catalog, monkeypatch):
        """The warmed signatures must reflect the live cluster's NE/NR rungs
        — an operator restarting over a populated cluster warms the shapes
        its solves will actually hit."""
        from karpenter_tpu.solver.tpu import SimNode

        # scan signatures only: relax signatures carry no NE_pad and the
        # count below is exact (the rung's warms have their own tests)
        monkeypatch.setenv("KT_RELAX", "0")

        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)
        prov = Provisioner(name="default").with_defaults()
        existing = [
            SimNode(instance_type="c5.2xlarge", provisioner="default",
                    zone="zone-1a", capacity_type="on-demand", price=0.34,
                    allocatable={"cpu": 8.0, "pods": 58.0}, existing=True)
            for _ in range(120)
        ]
        accepted = sched.warm_startup(
            [prov], small_catalog, existing_nodes=existing,
            profiles=((2, 400, False),),
        )
        # provisioning shape (NR covers existing+batch) and consolidation
        # shape (NR covers existing+1) land on distinct NR rungs
        assert accepted == 2
        _wait_warm(sched)
        ne_pads = {dict(sig)["NE_pad"] for sig in sched._tpu._ready}
        from karpenter_tpu.solver.tpu import _rung

        assert _rung(120, 16, 64) in ne_pads  # cluster-sized rung, not 16

    def test_explicit_tpu_backend_compiles_synchronously(self, small_catalog):
        """backend="tpu" (benchmarks, parity tests) keeps the synchronous
        compile-and-run behavior — no fallback, deterministic device path."""
        reg = Registry()
        sched = BatchScheduler(backend="tpu", registry=reg)
        prov = Provisioner(name="default").with_defaults()
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}) for i in range(8)]
        r = sched.solve(pods, [prov], small_catalog)
        assert not r.infeasible
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "native"}) == 0
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "oracle"}) == 0
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "tpu"}) == 1


class TestSlotExhaustion:
    def test_exhausted_shape_warms_full_program_behind(self, small_catalog):
        """NR-estimate lifecycle (tpu._nr_estimate): an anti-affinity-heavy
        shape the estimate undershoots is served by the warm tier while the
        background warm compiles the estimated program, DETECTS the
        exhaustion itself, and compiles the full-budget program too — so
        steady-state solves land directly on the program that actually
        serves the shape, and no caller ever eats a cold compile."""
        from karpenter_tpu.models.tensorize import tensorize
        from karpenter_tpu.solver.tpu import _node_budget, solve_dims

        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg,
                               native_batch_limit=8)
        prov = Provisioner(name="default").with_defaults()
        sel = LabelSelector.of({"app": "x"})

        def batch(tag):
            return [
                PodSpec(name=f"{tag}{i}", labels={"app": "x"},
                        requests={"cpu": 0.05},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                        owner_key="x")
                for i in range(3000)
            ]

        st = tensorize(batch("probe"), [prov], small_catalog)
        nb = _node_budget(st, 0, None)
        est = solve_dims(st, NE=0, node_budget=nb)["NR"]
        full = solve_dims(st, NE=0, node_budget=nb, full_nr=True)["NR"]
        assert est < 3000 <= full  # the shape really undershoots

        # solve 1: estimated program cold -> warm tier serves; the warm
        # compiles est, exhausts, and compiles the full program too
        r1 = sched.solve(batch("a"), [prov], small_catalog)
        assert not r1.infeasible
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "native"}) == 1
        _wait_warm(sched)
        assert sched._tpu._nr_exhausted  # the warm recorded the exhaustion

        # solve 2: signature now resolves to the full program -> on-device,
        # no new fallback
        r2 = sched.solve(batch("b"), [prov], small_catalog)
        assert not r2.infeasible
        assert reg.counter(SOLVER_COLD_FALLBACKS).get({"backend": "native"}) == 1
        assert reg.histogram(SOLVER_BACKEND_DURATION).count({"backend": "tpu"}) == 1
        for r in (r1, r2):
            for n in r.nodes:
                assert sum(1 for p in n.pods
                           if p.labels.get("app") == "x") <= 1

    def test_raise_on_exhaust_contract(self, small_catalog):
        """Direct solver contract: raise_on_exhaust surfaces SlotsExhausted
        when the estimate runs dry and the full program is cold, instead of
        inline-compiling it on the caller's thread."""
        import pytest as _pytest

        from karpenter_tpu.models.tensorize import tensorize
        from karpenter_tpu.solver.tpu import SlotsExhausted, TpuSolver

        prov = Provisioner(name="default").with_defaults()
        sel = LabelSelector.of({"app": "x"})
        pods = [PodSpec(name=f"p{i}", labels={"app": "x"},
                        requests={"cpu": 0.05},
                        affinity_terms=[PodAffinityTerm(sel, L.HOSTNAME, anti=True)],
                        owner_key="x")
                for i in range(3000)]
        st = tensorize(pods, [prov], small_catalog)
        solver = TpuSolver()
        with _pytest.raises(SlotsExhausted):
            solver.solve(st, raise_on_exhaust=True)
        assert solver._nr_exhausted
        # without the flag the same solver inline-retries and places all pods
        out = solver.solve(st)
        assert out.result.infeasible == {}
