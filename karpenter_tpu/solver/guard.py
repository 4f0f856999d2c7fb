"""Hang protection for the in-process device tier.

A device call is a call into the PJRT C++ runtime, and it can fail by not
returning: a runtime or driver fault, a chip another process took, a
program that livelocks.  The gRPC solver sidecar's CLIENTS already degrade
through a health gate (``service/client.py``: fall back to the local
oracle, reconnect in the background), but a process running the device
tier IN-PROCESS — the sidecar itself, or an operator without one — has no
equivalent: one hung solve would wedge the whole reconcile loop forever,
which is strictly worse than the reference's Go controller can fail.

jax offers no deadline primitive — a hung PJRT call never returns to
bytecode — so the guard dispatches device calls on an expendable daemon
thread and abandons it on timeout:

- the device tier is latched **unhealthy** and the scheduler serves every
  subsequent batch from the warm host tiers (native C++ / CPU oracle, the
  same degradation contract as the remote client's health gate);
- a background probe thread re-runs a tiny device op until it answers,
  then re-enables the device tier;
- the abandoned call thread cannot be killed (it is blocked inside the
  PJRT C++ runtime); it is daemonized so it never pins process exit, and
  the unhealthy latch bounds the leak at one abandoned solve thread plus
  one probe thread per outage.

Snapshot isolation makes abandonment safe: solvers place pods on their own
snapshots of the caller's nodes (``SimNode.snapshot``, tested invariant),
so a timed-out solve that completes later mutates nothing the live
scheduler still reads.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Callable, List, Optional

logger = logging.getLogger(__name__)

#: abandoned call threads, joined briefly at interpreter exit: a daemon
#: thread killed mid-XLA prints "FATAL: exception not rethrown" during
#: teardown — give a just-slow call a moment to drain, but never pin exit
#: on a call that is truly hung (that is the guard's whole point).
_ABANDONED: List[threading.Thread] = []
_EXIT_GRACE_S = 5.0


@atexit.register
def _drain_abandoned() -> None:
    deadline = _EXIT_GRACE_S
    for t in _ABANDONED:
        if deadline <= 0:
            break
        import time as _time

        # ktlint: allow[KT002] interpreter-exit drain deadline: runs from
        # atexit after the controllers (and their injected clocks) are gone,
        # and a fake-advanced clock must never shorten the real join grace
        t0 = _time.monotonic()
        t.join(deadline)
        deadline -= _time.monotonic() - t0  # ktlint: allow[KT002] see above

#: default guard timeout.  The guard covers only warm-tier device solves
#: (the ``auto`` policy never compiles inline — compile-behind serves cold
#: shapes from the host tiers), so legitimate calls finish in milliseconds
#: to a few seconds; 180 s is two orders of magnitude of margin while still
#: giving up on a hung call in bounded time.  Override with
#: ``KT_DEVICE_SOLVE_TIMEOUT_S``; 0 disables the guard.
DEFAULT_TIMEOUT_S = 180.0


class DeviceHang(Exception):
    """A guarded device call exceeded its deadline."""


def _default_probe() -> None:
    import jax.numpy as jnp

    jnp.zeros(4).sum().block_until_ready()


class DeviceGuard:
    def __init__(
        self,
        timeout_s: Optional[float] = None,
        probe_interval_s: float = 30.0,
        probe_fn: Callable[[], None] = _default_probe,
        on_health_change: Optional[Callable[[bool], None]] = None,
    ) -> None:
        if timeout_s is None:
            timeout_s = float(
                os.environ.get("KT_DEVICE_SOLVE_TIMEOUT_S", DEFAULT_TIMEOUT_S)
            )
        self.timeout_s = timeout_s
        self.probe_interval_s = probe_interval_s
        self.probe_fn = probe_fn
        self.on_health_change = on_health_change
        self._lock = threading.Lock()
        self._healthy = True
        self._probing = False
        self._stop = threading.Event()

    @property
    def enabled(self) -> bool:
        return self.timeout_s > 0

    @property
    def healthy(self) -> bool:
        return self._healthy

    def run(self, fn, *args, **kwargs):
        """Run ``fn`` with the hang deadline; raise :class:`DeviceHang` on
        timeout (latching unhealthy), else return/raise exactly what ``fn``
        did."""
        return self._run(self.timeout_s, fn, args, kwargs)

    def run_budgeted(self, fn, *args, budget_frac: float = 1.0, **kwargs):
        """Like :meth:`run` with ``budget_frac`` of the deadline.  The
        hierarchical solver dispatches up to ``1 + KT_HIER_PRICE_ITERS``
        block waves per batch; splitting the whole-solve deadline across
        them keeps a hung device latching in the same bounded time as one
        flat solve instead of ``waves ×`` longer."""
        frac = min(max(budget_frac, 0.0), 1.0)
        return self._run(self.timeout_s * frac, fn, args, kwargs)

    def _run(self, timeout_s: float, fn, args, kwargs):
        if not self.enabled or timeout_s <= 0:
            return fn(*args, **kwargs)
        box: dict = {}
        done = threading.Event()

        def work():
            try:
                box["val"] = fn(*args, **kwargs)
            # ktlint: allow[KT005] the expendable call thread boxes EVERY
            # outcome (incl. KeyboardInterrupt) and run() re-raises it on
            # the caller thread — swallowing here would turn a device error
            # into a phantom hang
            except BaseException as e:  # noqa: BLE001 — re-raised in caller
                box["err"] = e
            finally:
                done.set()

        t = threading.Thread(target=work, daemon=True, name="kt-device-call")
        t.start()
        if not done.wait(timeout_s):
            _ABANDONED.append(t)
            self._mark_unhealthy()
            raise DeviceHang(
                f"device call exceeded {timeout_s:.0f}s; device tier "
                "latched unhealthy (warm host tiers serve until a probe "
                "succeeds)"
            )
        if "err" in box:
            raise box["err"]
        return box["val"]

    def stop(self) -> None:
        """Stop the recovery probe (operator shutdown)."""
        self._stop.set()

    # ---- internals -----------------------------------------------------
    def _mark_unhealthy(self) -> None:
        with self._lock:
            if not self._healthy:
                return
            self._healthy = False
            start_probe = not self._probing
            self._probing = True
            # callback under the lock: a recovery racing this transition
            # must not interleave its on_health_change(True) after ours and
            # leave the health gauge reading 1 through a real outage
            if self.on_health_change:
                self.on_health_change(False)
        logger.error(
            "device tier UNHEALTHY: a device call hung past %.0fs; solves "
            "degrade to the warm host tiers until a probe succeeds",
            self.timeout_s,
        )
        if start_probe:
            threading.Thread(
                target=self._probe_loop, daemon=True, name="kt-device-probe"
            ).start()

    def _probe_loop(self) -> None:
        # The probe op runs inline in this thread: if the device is still
        # wedged the op blocks HERE (no new probe threads pile up), and when
        # the device answers again the blocked op completes and recovery follows
        # on the next iteration — hung-then-recovered needs no extra timer.
        while not self._stop.wait(self.probe_interval_s):
            try:
                self.probe_fn()
            except Exception as e:  # noqa: BLE001 — probe failure = still down
                logger.debug("device probe failed: %r", e)
                continue
            with self._lock:
                self._healthy = True
                self._probing = False
                if self.on_health_change:
                    self.on_health_change(True)  # under the lock, see above
            logger.info(
                "device tier RECOVERED: probe op answered; device solves "
                "re-enabled"
            )
            return
