"""Device-tier hang protection (solver/guard.py).

A device call is a call into the PJRT runtime and can hang forever with the
backend otherwise initialized; the reconcile loop must degrade to the warm
host tiers (the RemoteScheduler's health-gate contract, applied to the
in-process device tier), never freeze.  Hangs are simulated with a patched
solve that blocks; no real device is involved.
"""

import threading
import time

import pytest

from karpenter_tpu.metrics import (
    Registry,
    SOLVER_DEGRADED_SOLVES,
    SOLVER_DEVICE_HANGS,
    SOLVER_DEVICE_HEALTHY,
)
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.solver.guard import DeviceGuard, DeviceHang
from karpenter_tpu.solver.scheduler import BatchScheduler


class TestDeviceGuard:
    def test_disabled_runs_inline(self):
        g = DeviceGuard(timeout_s=0)
        assert not g.enabled
        assert g.run(lambda x: x + 1, 41) == 42

    def test_passthrough_value_and_exception(self):
        g = DeviceGuard(timeout_s=5.0)
        assert g.run(lambda: "ok") == "ok"

        class Boom(RuntimeError):
            pass

        with pytest.raises(Boom):
            g.run(lambda: (_ for _ in ()).throw(Boom("x")))
        assert g.healthy  # exceptions are not hangs

    def test_timeout_latches_unhealthy_and_probe_recovers(self):
        events = []
        release = threading.Event()
        probe_ok = threading.Event()

        def probe():
            if not probe_ok.is_set():
                raise RuntimeError("still down")

        g = DeviceGuard(timeout_s=0.1, probe_interval_s=0.05,
                        probe_fn=probe, on_health_change=events.append)
        with pytest.raises(DeviceHang):
            g.run(release.wait, 5.0)  # blocks past the 0.1 s deadline
        assert not g.healthy
        assert events == [False]

        # probe failing -> stays unhealthy
        time.sleep(0.2)
        assert not g.healthy

        # probe succeeding -> recovery flips the latch exactly once
        probe_ok.set()
        deadline = time.time() + 5.0
        while not g.healthy and time.time() < deadline:
            time.sleep(0.02)
        assert g.healthy
        assert events == [False, True]
        release.set()  # unblock the abandoned worker thread
        g.stop()

    def test_second_hang_does_not_stack_probes(self):
        events = []
        g = DeviceGuard(timeout_s=0.05, probe_interval_s=30.0,
                        probe_fn=lambda: None, on_health_change=events.append)
        with pytest.raises(DeviceHang):
            g.run(time.sleep, 1.0)
        with pytest.raises(DeviceHang):
            g.run(time.sleep, 1.0)
        # one unhealthy transition, one probe thread
        assert events == [False]
        assert sum(1 for t in threading.enumerate()
                   if t.name == "kt-device-probe") == 1
        g.stop()


class TestSchedulerDegradation:
    def _scenario(self, small_catalog):
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d")
                for i in range(300)]  # > NATIVE_BATCH_LIMIT: routes to device
        provs = [Provisioner(name="default").with_defaults()]
        return pods, provs, small_catalog

    def test_hang_degrades_to_warm_tier_and_recovers(self, small_catalog, monkeypatch):
        reg = Registry()
        sched = BatchScheduler(backend="auto", registry=reg)
        # device program "ready" so the dispatch path is the guarded call
        monkeypatch.setattr(sched, "_device_ready", lambda *a: True)
        sched._guard.timeout_s = 0.1
        sched._guard.probe_interval_s = 3600.0  # recovery driven manually

        hang = threading.Event()

        def hanging_solve(*a, **k):
            hang.wait(10.0)
            raise AssertionError("abandoned solve result must be discarded")

        monkeypatch.setattr(sched._tpu, "solve", hanging_solve)
        pods, provs, cat = self._scenario(small_catalog)

        res = BatchScheduler.solve(sched, pods, provs, cat)
        # the batch was still answered — by a warm host tier
        assert res.n_scheduled == 300 and not res.infeasible
        assert not sched._guard.healthy
        assert reg.counter(SOLVER_DEVICE_HANGS).get() == 1
        assert reg.gauge(SOLVER_DEVICE_HEALTHY).get() == 0
        assert sum(reg.counter(SOLVER_DEGRADED_SOLVES).values.values()) >= 1

        # while unhealthy: the device is never dispatched again
        def must_not_run(*a, **k):
            raise AssertionError("device dispatched while unhealthy")

        monkeypatch.setattr(sched._tpu, "solve", must_not_run)
        res2 = BatchScheduler.solve(sched, pods, provs, cat)
        assert res2.n_scheduled == 300
        hangs_before = reg.counter(SOLVER_DEVICE_HANGS).get()

        # warms are gated while unhealthy
        assert sched.warm_startup(provs, cat) == 0

        # manual recovery (what the probe does) -> device serves again
        called = {}

        def healthy_solve(st, **k):
            called["yes"] = True
            from karpenter_tpu.solver.tpu import TpuSolver

            return TpuSolver().solve(st, **k)

        monkeypatch.setattr(sched._tpu, "solve", healthy_solve)
        # flip via the same path the probe uses; restore a sane deadline so
        # the recovered solve's inline compile isn't re-abandoned (and no
        # XLA thread is left hanging into interpreter teardown)
        sched._guard.timeout_s = 120.0
        with sched._guard._lock:
            sched._guard._healthy = True
            sched._guard._probing = False
        sched._device_health_changed(True)

        res3 = BatchScheduler.solve(sched, pods, provs, cat)
        assert res3.n_scheduled == 300 and called.get("yes")
        assert reg.gauge(SOLVER_DEVICE_HEALTHY).get() == 1
        assert reg.counter(SOLVER_DEVICE_HANGS).get() == hangs_before
        hang.set()

    def test_reseat_skips_cold_fallback_keeps_degraded(self, small_catalog, monkeypatch):
        """The reseat epilogue is skipped for transient cold-fallback solves
        (compile-behind: the device program supersedes the answer, so the
        cold path keeps its latency contract) but NOT for device-unhealthy
        degraded solves, whose nodes are real and long-lived."""
        calls = []

        def spy(self, result, *a, **k):
            # served_cold rides on the RESULT (pipelined solves in flight
            # together must not clobber a shared scheduler flag)
            calls.append(result.served_cold)
            return None

        monkeypatch.setattr(BatchScheduler, "_reseat_capped", spy)
        pods, provs, cat = self._scenario(small_catalog)

        # cold path: device not ready -> _cold_solve -> flagged, reseat sees
        # served_cold=True (the real method would return immediately)
        sched = BatchScheduler(backend="auto", registry=Registry())
        monkeypatch.setattr(sched, "_device_ready", lambda *a: False)
        monkeypatch.setattr(sched, "_start_warm", lambda *a, **k: None)
        BatchScheduler.solve(sched, pods, provs, cat)
        assert calls and calls[-1] is True

        # degraded path: unhealthy latch -> warm tier serves, but the solve
        # is NOT marked cold — the reseat polish applies
        sched2 = BatchScheduler(backend="auto", registry=Registry())
        monkeypatch.setattr(sched2, "_device_ready", lambda *a: True)
        with sched2._guard._lock:
            sched2._guard._healthy = False
            sched2._guard._probing = True  # no probe thread in this test
        BatchScheduler.solve(sched2, pods, provs, cat)
        assert calls[-1] is False

    def test_forced_tpu_backend_is_unguarded(self, small_catalog, monkeypatch):
        sched = BatchScheduler(backend="tpu", registry=Registry())
        sched._guard.timeout_s = 0.05
        pods, provs, cat = self._scenario(small_catalog)
        # a slow-but-legitimate inline path must NOT be abandoned: forced
        # backends bypass the guard entirely (inline compiles can exceed any
        # reasonable hang deadline)
        real = sched._tpu.solve
        slow = {}

        def slow_solve(*a, **k):
            time.sleep(0.2)  # beyond the guard deadline
            slow["ran"] = True
            return real(*a, **k)

        monkeypatch.setattr(sched._tpu, "solve", slow_solve)
        res = BatchScheduler.solve(sched, pods, provs, cat)
        assert res.n_scheduled == 300 and slow.get("ran")
        assert sched._guard.healthy
