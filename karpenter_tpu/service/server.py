"""Solver sidecar — gRPC server wrapping the batch scheduler.

The reconciler-facing service boundary (SURVEY.md §2.3 component (1)).
Stubs are registered manually via a generic handler since grpc_tools isn't in
the image; the method table matches the comment block in solver.proto.

Run standalone:  python -m karpenter_tpu.service.server --port 50151
"""

from __future__ import annotations

import argparse
import logging
import os
import queue
import signal
import sys
import threading
import time
import uuid
from collections import OrderedDict
from concurrent import futures
from concurrent.futures import Future
from typing import Optional, Sequence, Tuple

import grpc

from .. import faults as faults_mod
from .. import gang as gangmod
from ..admission import (
    AdmissionControl,
    SolveDeadlineError,
    SolveShedError,
    admission_enabled,
    parse_class,
)
from ..batcher import InflightQueue, SlotCoalescer
from ..metrics import (
    DELTA_RPC,
    DELTA_RPC_DURATION,
    INFLIGHT_DEPTH,
    MEGABATCH_FLUSH,
    MEGABATCH_FLUSH_REASONS,
    MEGABATCH_SLOTS,
    MULTIHOST_FENCE_BYTES,
    MULTIHOST_FENCE_SCOPES,
    MULTIHOST_SLOT_OWNERSHIP,
    MULTIHOST_SLOTS,
    MULTIHOST_UNIFIED,
    OCCUPANCY_DELTA_INLINE,
    OCCUPANCY_DEVICE_BUSY,
    OCCUPANCY_SLOT_FILL,
    REQUEST_CATALOG,
    REQUEST_CATALOG_HOW,
    REQUEST_DECODE_HOW,
    REQUEST_DECODE_PODS,
    Registry,
    registry as default_registry,
)
from ..obs import protocol, tracer_for
from ..obs.occupancy import OccupancyAccountant
from ..obs.slo import WINDOWS as SLO_WINDOWS, SloEngine
from ..obs.timeseries import sampler_for
from ..obs.trace import NULL_PHASE, NULL_TRACE, Tracer
from ..parallel.forward import ResultForwarder, SlotNotOwned
from ..solver import native as native_mod
from ..solver.guard import DeviceHang
from ..solver.scheduler import BatchScheduler, WarmupFailed
from ..solver.tpu import (
    MEGA_MAX_SLOTS,
    jit_cache_dir,
    jit_cache_entries,
    max_mega_slots,
    mesh_shardable,
)
from ..tuning import TuningController, global_knobs, tune_enabled
from ..tuning.controller import zero_init as tuning_zero_init
from ..tuning.knobs import Knobs
from ..utils.clock import Clock
from . import codec
from . import solver_pb2 as pb
from .delta import (
    DeltaReply,
    DeltaSessionTable,
    SessionEntry,
    delta_enabled,
)

SERVICE = "karpenter.tpu.Solver"

#: default megabatch request-slot cap per coalescer flush (KT_MAX_SLOTS /
#: --max-slots override; 1 disables cross-request batching)
DEFAULT_MAX_SLOTS = 8
#: default max-wait before a partially-filled batch flushes, milliseconds
#: (KT_MAX_WAIT_MS / --max-wait-ms).  0 = flush the moment the inbound
#: queue goes idle — single-request latency then matches the unbatched
#: path; coalescing engages exactly when requests actually queue up.
DEFAULT_MAX_WAIT_MS = 0.0


def _resolve(fut: Future, result=None, exc: Optional[BaseException] = None) -> None:
    """Resolve a future exactly once, tolerating the racer.  stop() and the
    dispatcher's _finalize can reach the same future concurrently (a fence
    completing at the instant the 5s join gives up); done()-check-then-set
    is not atomic, so the loser's set raises InvalidStateError — swallow it:
    either resolution unblocks the RPC thread, which is all that matters."""
    try:
        if not fut.done():
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)
    except futures.InvalidStateError:
        pass  # the other side resolved it first


def _full_reply(result, epoch: int, mode: str, state: str = "ok") -> DeltaReply:
    """Full-shaped, DETACHED DeltaReply (establish / reseed / guard-trip
    fallback): the client replaces its ledger wholesale.  Copies are taken
    HERE, on the dispatcher, because the session chain these containers
    belong to mutates under the very next delta while the RPC thread is
    still encoding."""
    return DeltaReply(
        state=state, epoch=epoch, mode=mode, full=True,
        assignments=dict(result.assignments),
        infeasible=dict(result.infeasible),
        nodes=[n.snapshot() for n in result.nodes],
        solve_ms=result.solve_ms,
    )


class SolvePipeline:
    """Double-buffered, cross-request-batching solve dispatch for one
    scheduler.

    All scheduler access funnels through ONE dispatcher thread (the
    scheduler is not re-entrant — concurrent RPC handlers previously raced
    on it).  Two throughput mechanisms compose behind it:

    - **Pipelining** (PR 1): ``scheduler.submit`` returns after the async
      device dispatch; the dispatcher tensorizes batch N+1 while batch N
      executes, fencing via the in-flight queue.  Serves the low-concurrency
      regime.
    - **Cross-request megabatching** (PR 4): a deadline-aware
      :class:`~karpenter_tpu.batcher.SlotCoalescer` drains concurrent RPCs
      into request slots (flush on max-slots, max-wait, or shape-bucket
      change) and ``scheduler.submit_many`` solves the whole flush in ONE
      vmapped device dispatch — service throughput stops being capped at
      one solve per device round trip.  Engages exactly when requests
      queue; a lone request flushes immediately (``max_wait=0`` default),
      so single-request latency matches the unbatched path.

    Mesh-configured schedulers ride the same path SHARDED: the flush's
    slot axis spreads one-slot-per-chip over the scheduler's (pods, types)
    mesh (solver/tpu.py ``solve_many_async(mesh=...)``), so a multi-chip
    host serves coalesced flushes at full device count — the pipeline
    floors ``max_slots`` at the mesh's device count so sharded flushes
    fill every chip.  Bucket keys carry the mesh signature, so requests
    against different meshes never coalesce.

    Responses keep arrival order (singles and megabatches share ONE
    FIFO in-flight queue), and every megabatched response carries the
    honest per-request ``solve_ms``: enqueue→respond wall time, NOT the
    megabatch-amortized device time.
    """

    def __init__(self, scheduler: BatchScheduler,
                 registry: Optional[Registry] = None, depth: int = 2,
                 max_slots: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 clock: Optional[Clock] = None,
                 admission: Optional[AdmissionControl] = None,
                 knobs: Optional[Knobs] = None) -> None:
        self.scheduler = scheduler
        self.registry = registry or default_registry
        # the live knob registry (ISSUE 19, docs/TUNING.md): construction
        # defaults read THROUGH it — an unset knob falls back to the env
        # (KT_MAX_SLOTS / KT_MAX_WAIT_MS) exactly as before, a tuned
        # override lands at the next _apply_knobs snapshot
        self.knobs = knobs if knobs is not None else global_knobs()
        if max_slots is None:
            max_slots = int(self.knobs.get("max_slots"))
        if max_wait_ms is None:
            max_wait_ms = float(self.knobs.get("max_wait_ms"))
        # meshed scheduler: the sharded megabatch pads its slot axis to the
        # mesh's device count (one slot per chip), so floor the flush size
        # there — a smaller cap would flush half-empty shards and serve the
        # mesh below its chip count — and CAP it at the mesh's largest
        # in-ladder rung (awkward device counts: 20 chips top out at a
        # 20-slot rung, so a 32-entry flush would overflow the sharded
        # program and degrade to serial on every full flush).
        # max_slots=1 (batching disabled) is honored; an unshardable mesh
        # (device count past the slot-rung ladder) keeps the configured
        # cap and rides the serial path.
        self.max_slots = self._clamp_slots(max_slots)
        #: an unshardable mesh on a megabatching backend serves every
        #: request as its own single-request serial flush: count those
        #: flushes under mesh_serial, not 'bucket', so degradation stays
        #: visible in flush units.  The verdict is the SCHEDULER's
        #: construction-time ``mega_unshardable`` (ISSUE 14 satellite:
        #: hoisted so the per-request bucket probe disappears —
        #: _bucket_of short-circuits on this flag without calling
        #: bucket_key at all); facades without the attribute fall back to
        #: the pipeline-side computation.
        mesh = getattr(scheduler, "mesh", None)
        sched_verdict = getattr(scheduler, "mega_unshardable", None)
        if sched_verdict is None:
            sched_verdict = mesh is not None and not mesh_shardable(mesh)
        self._mesh_unshardable = (
            bool(sched_verdict)
            and getattr(scheduler, "backend", None) in ("auto", "tpu"))
        self.max_wait = max(0.0, max_wait_ms) / 1000.0
        #: the per-iteration atomic knob snapshot (_apply_knobs, under
        #: _sched_lock); _inline_ok and _effective_max_wait read the
        #: IMMUTABLE object, so a mid-flight tuner update can never tear
        #: a flush or a brownout evaluation (ISSUE 19)
        self._knob_snap = self.knobs.snapshot()
        self._clock = clock or Clock()
        self._q: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._submit_lock = threading.Lock()  # makes stop-check + put atomic
        # scheduler-OWNERSHIP lock: every section that touches the (non-
        # re-entrant) scheduler or fences in-flight device work holds it —
        # the dispatcher's dispatch/finalize sections, and the delta fast
        # path's INLINE shortcut (_solve_inline: an idle pipeline serves a
        # sub-ms delta RPC directly on its RPC thread, skipping both
        # queue-handoff context switches).  Uncontended acquisition costs
        # the dispatcher ~1us per dispatch; re-entrant because _flush
        # nests _dispatch_single/_finalize under one flush.
        self._sched_lock = threading.RLock()
        #: futures the dispatcher has popped (from _q or _inflight) but not
        #: yet resolved — the dispatcher's hand.  Written by the dispatcher
        #: only; stop() snapshots it after the join times out so a wedge at
        #: ANY point between pop and resolution (inside submit's device
        #: dispatch, inside a fence, between an _inflight drain and its
        #: finalize) can't strand an RPC thread.  _resolve tolerates the
        #: benign race with a merely-slow dispatcher.  Coalesced-but-not-
        #: yet-flushed requests are in it too — a stop() mid-hold fails
        #: them instead of stranding them in the coalescer.
        self._in_hand: "list[Future]" = []
        gauge = self.registry.gauge(INFLIGHT_DEPTH)
        labels = {"backend": scheduler.backend}  # one series per backend
        if not gauge.has(labels):
            # only when absent: a second pipeline on a shared registry must
            # not zero a live series (same guard as BatchScheduler.__init__)
            gauge.set(0, labels)
        self._inflight: InflightQueue = InflightQueue(
            depth=depth, on_depth=lambda d: gauge.set(d, labels))
        #: dispatcher-owned: batch boundaries for the megabatch path.
        #: The scheduler's ``unify_buckets`` (when it has one) lets a held
        #: flush admit a dominated mixed-bucket request so both shapes
        #: share one mesh dispatch (ISSUE 14 host-aware coalescing)
        self._coal: SlotCoalescer = SlotCoalescer(
            max_slots=self.max_slots, max_wait=self.max_wait,
            clock=self._clock,
            # no on_unify counting here: the COLLECTOR counts unified
            # dispatches (submit_many's group merge re-derives the same
            # unification) — counting the coalescer join too would tally
            # one logical unification twice
            unify=getattr(scheduler, "unify_buckets", None))
        # zero-init every flush-reason series (KT003: a counter born at its
        # first increment loses that increment to rate()/increase())
        flush = self.registry.counter(MEGABATCH_FLUSH)
        for reason in MEGABATCH_FLUSH_REASONS:
            flush.inc({"reason": reason}, value=0.0)
        self.registry.histogram(MEGABATCH_SLOTS)
        # multi-host serving families at 0 from construction (KT003) —
        # the pipeline re-zero-inits like the flush reasons above, for
        # facade schedulers without the BatchScheduler init
        fence_c = self.registry.counter(MULTIHOST_FENCE_BYTES)
        for scope in MULTIHOST_FENCE_SCOPES:
            fence_c.inc({"scope": scope}, value=0.0)
        slots_c = self.registry.counter(MULTIHOST_SLOTS)
        for ownership in MULTIHOST_SLOT_OWNERSHIP:
            slots_c.inc({"ownership": ownership}, value=0.0)
        self.registry.counter(MULTIHOST_UNIFIED).inc(value=0.0)
        #: cross-host result-forwarding shim (ISSUE 14): a megabatch slot
        #: whose RPC arrived here but whose shards another host owns
        #: resolves SlotNotOwned; the shim re-routes it to the owning
        #: host's endpoint (KT_MULTIHOST_PEERS) over the fleet transport.
        #: Null-enabled by default — single-process serving never
        #: produces foreign slots.
        self._forwarder = ResultForwarder(registry=self.registry)
        self._forwarder.zero_init()
        #: lazily-built bounded pool for forwarding RPCs (foreign slots
        #: arrive per flush on a multi-host mesh — per-request thread
        #: spawn would churn unboundedly under burst); None until the
        #: first foreign slot, shut down in stop()
        self._fwd_pool = None
        #: dispatcher-owned: the admitted priority class per in-hand
        #: future, so a forwarded foreign slot re-dispatches in ITS class
        #: on the owning host (cleared by _unhand with the _in_hand entry)
        self._fwd_pclass: dict = {}
        # admission control (docs/ADMISSION.md): the bounded priority queue
        # + breaker + brownout front door.  None = construct from env
        # (KT_ADMISSION=0 disables); False = force off.
        # Disabled keeps the raw FIFO above verbatim — byte-identical to
        # the pre-admission path.
        if admission is None and admission_enabled():
            admission = AdmissionControl(
                registry=self.registry, clock=self._clock,
                flight=getattr(getattr(scheduler, "tracer", None),
                               "flight", None),
            )
        self._adm: Optional[AdmissionControl] = admission or None
        if self._adm is not None:
            # a preemption happens on the PREEMPTING request's RPC thread;
            # the victim's blocked RPC thread is unblocked right there
            self._adm.on_shed = lambda t, exc: _resolve(t.item[1], exc=exc)
        # delta serving (docs/ARCHITECTURE.md round 14): the bounded,
        # TTL-evicted table of live warm-start chains behind the session-
        # stateful SolveDelta protocol.  KT_DELTA=0 leaves it None and
        # every session-carrying request degrades to the classic full
        # path — byte-identical to pre-delta serving.  Table entries are
        # dispatcher-owned; the table's own lock only guards the dict.
        # fault-injection plane (ISSUE 12, docs/RESILIENCE.md): the
        # zero-cost null plane unless KT_FAULTS configures a chaos
        # schedule; shared with the session table so ONE seeded schedule
        # covers the delta path and the table/spool choke points
        self._faults = faults_mod.plane(
            self.registry,
            flight=getattr(getattr(scheduler, "tracer", None),
                           "flight", None))
        # session durability (ISSUE 12) + fleet handoff (ISSUE 13): with
        # KT_SESSION_DIR set, every session spools to its own record file
        # (graceful shutdown, drain handoff, and periodically at epoch
        # boundaries — KT_SESSION_SNAPSHOT_S), and any replica sharing the
        # volume rehydrates a session on demand (boot restore + adopt-on-
        # miss) under the exactly-one-owner lease protocol — a failed-over
        # session's next delta is served WARM by whichever replica the
        # client re-homes to.  A refused record (corrupt/version/catalog
        # skew) is a counted cold start.
        self._spool_dir = os.environ.get("KT_SESSION_DIR", "")
        if self._spool_dir:
            # records are namespaced PER BACKEND under the shared dir: the
            # service lazily builds a pipeline per requested backend, and
            # an auto-backend replica must never adopt (or clobber) an
            # oracle-backend chain — same-backend SIBLING replicas share
            # the namespace deliberately; the lease protocol arbitrates.
            self._spool_dir = os.path.join(
                self._spool_dir, getattr(scheduler, "backend", "") or "auto")
        self._delta_tab: Optional[DeltaSessionTable] = (
            DeltaSessionTable(registry=self.registry, clock=self._clock,
                              faults=self._faults,
                              spool_dir=self._spool_dir)
            if delta_enabled() else None)
        #: graceful-drain latch (SIGTERM / SolverService.drain): new
        #: session establishments are refused with a DRAINING hint, and
        #: every served delta hands its chain off to the shared spool so
        #: the client's next RPC lands warm on a sibling
        self._draining = False
        self._snap_interval = float(
            os.environ.get("KT_SESSION_SNAPSHOT_S", "30"))
        self._last_snap = self._clock.now()   # guarded-by: _sched_lock
        #: in-flight background spool write (the periodic snapshot runs
        #: OFF the serving paths — the table's torn-entry guard makes a
        #: lock-free write safe).  Written under _sched_lock
        #: (_maybe_snapshot); snapshot_sessions' shutdown read is
        #: deliberately lock-free — a dispatcher wedged inside the lock
        #: must not deadlock shutdown, and the unique write_atomic temp
        #: names make even a racing writer rename-safe.
        self._snap_worker: Optional[threading.Thread] = None
        if self._spool_dir and self._delta_tab is not None:
            cat = os.environ.get("KT_CATALOG_EPOCH", "")
            tracer = getattr(scheduler, "tracer", None)
            if tracer is not None:
                with tracer.start("restore", spool=self._spool_dir) as tr:
                    n = self._delta_tab.restore(
                        self._spool_dir,
                        expected_catalog_epoch=int(cat) if cat else None)
                    tr.annotate(sessions=n)
            else:
                self._delta_tab.restore(
                    self._spool_dir,
                    expected_catalog_epoch=int(cat) if cat else None)
        #: lazily-built host FFD scheduler for breaker-open / brownout
        #: routed solves (device capacity stays reserved for the classes
        #: that keep the device path)
        self._host_sched: Optional[BatchScheduler] = None
        #: dispatcher-owned: futures whose dispatch was host-routed — their
        #: outcomes must NOT feed the breaker's device-path probe accounting
        self._host_futs: set = set()
        self._thread = threading.Thread(
            target=self._loop, name="solve-pipeline", daemon=True)
        self._thread.start()

    def solve(self, kwargs: dict, pclass: Optional[str] = None,
              deadline_s: Optional[float] = None):
        """RPC-thread entry: enqueue and block for this request's result.

        With admission enabled, ``pclass``/``deadline_s`` route the request
        through the bounded priority queue — :class:`SolveShedError` /
        :class:`SolveDeadlineError` surface HERE (before any tensorize or
        device work happened for the request); disabled, both are ignored
        and the raw FIFO path is byte-identical to pre-admission."""
        # queue-wait attribution: stamp the enqueue on the request's trace
        # clock here (RPC thread); the dispatcher closes the "window" span
        # when it picks the request up — the cross-thread phase is recorded
        # as an already-closed span, so nothing can leak.  The perf_counter
        # stamp feeds the megabatch path's honest enqueue→respond solve_ms.
        trace = kwargs.get("trace") or NULL_TRACE
        t_enq = trace.now()
        t_wall = time.perf_counter()
        if "_delta" in kwargs and self._inline_ok():
            # delta fast path, idle-pipeline shortcut: serve the sub-ms
            # incremental step ON THIS RPC THREAD under the scheduler-
            # ownership lock — no queue handoff, no dispatcher wakeup, no
            # future wake: two context switches gone from the steady-state
            # path.  Non-blocking acquire: a busy dispatcher (or another
            # inline solve) sends the request down the normal queue path,
            # so class ordering under load is untouched.
            if self._sched_lock.acquire(blocking=False):
                try:
                    return self._solve_inline(kwargs, pclass, deadline_s,
                                              trace, t_enq, t_wall)
                finally:
                    self._sched_lock.release()
        fut: Future = Future()
        item = (kwargs, fut, t_enq, t_wall)
        # the stop-check and the put are one atomic step: a put that wins
        # the lock before stop()'s drain is guaranteed to be seen by the
        # drain; a put that loses sees _stop and refuses — either way no
        # future is ever left unresolved (an RPC thread blocked forever on
        # fut.result() would pin process exit)
        with self._submit_lock:
            if self._stop.is_set():
                raise RuntimeError("solve pipeline stopped")
            if self._adm is not None:
                pclass = parse_class(pclass or "")
                # the dispatcher pops this back out before the scheduler
                # sees kwargs (routing + slot-fill ordering read it)
                kwargs["_pclass"] = pclass
                t0 = trace.now()
                # raises the typed shed/deadline error straight to the RPC
                # thread — nothing was enqueued, nothing to clean up
                ticket = self._adm.admit(item, pclass,
                                         deadline_s=deadline_s)
                trace.record(
                    "admission", t0, trace.now(), priority_class=pclass,
                    queued=len(self._adm.queue),
                    brownout=self._adm.brownout.level,
                    breaker=self._adm.breaker.state)
                # every resolution path (finalize, shed, stop) returns the
                # class's concurrency-quota slot exactly once
                fut.add_done_callback(
                    lambda _f, t=ticket: self._adm.release(t))
            else:
                self._q.put(item)
        return fut.result()

    def stop(self) -> None:
        """Stop the dispatcher.  Requests still queued OR in flight are
        FAILED, not abandoned — a blocked RPC thread waiting on an
        unresolved future would pin process exit forever."""
        self._stop.set()
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            # dispatcher wedged (e.g. a device fence that never returns,
            # forced backend so no guard, or an H2D dispatch inside
            # scheduler.submit): fail everything still in flight so the RPC
            # threads unblock; the daemon dispatcher thread itself cannot
            # pin exit.  deque ops are thread-safe, and every entry the
            # wedged thread already popped is still in its _in_hand ledger
            # (coalescer-held requests included).
            for head, rest in self._inflight.pop_to(0):
                if head == "mega":
                    for (_kw, fut, _t, _w), _pending in rest:
                        _resolve(fut,
                                 exc=RuntimeError("solve pipeline stopped"))
                else:
                    _resolve(rest, exc=RuntimeError("solve pipeline stopped"))
            for fut in list(self._in_hand):
                _resolve(fut, exc=RuntimeError("solve pipeline stopped"))
        with self._submit_lock:
            while True:
                try:
                    _kwargs, fut, _t_enq, _t_wall = self._q.get_nowait()
                except queue.Empty:
                    break
                _resolve(fut, exc=RuntimeError("solve pipeline stopped"))
            if self._adm is not None:
                # tickets still queued in the admission queue: FAIL them
                # (same contract as the raw FIFO above — a blocked RPC
                # thread waiting on an unresolved future pins process exit)
                for ticket in self._adm.drain():
                    _kwargs, fut, _t_enq, _t_wall = ticket.item
                    _resolve(fut, exc=RuntimeError("solve pipeline stopped"))
        if self._delta_tab is not None:
            # graceful shutdown: spool the chains FIRST (KT_SESSION_DIR
            # set), so the replacement replica serves every surviving
            # session warm...
            if self._spool_dir:
                self.snapshot_sessions()
            # ...then the in-memory chains die with the pipeline; clients
            # whose sessions were not spooled re-establish against the
            # replacement (counted so a restart storm is visible as
            # eviction reason "stop", not mystery unknowns)
            self._delta_tab.clear("stop")
        if self._fwd_pool is not None:
            # queued forwards resolve their futures from pool threads;
            # wait=False — stop() must not block on a peer RPC, and
            # _resolve tolerates the stopped-pipeline double-fail
            self._fwd_pool.shutdown(wait=False)
        self._forwarder.close()

    def drain(self) -> None:
        """Enter graceful-drain mode (the fleet handshake, docs/
        RESILIENCE.md): from here on NEW session establishments are
        refused with a ``session_state="draining"`` hint, every served
        delta hands its chain off to the shared spool (record + released
        lease + dropped entry) on the same reply, and an immediate
        snapshot pass spools every quiescent chain so sessions that never
        send another delta before the pod dies are already adoptable.
        Serving continues — classic full solves and in-flight session
        chains are unaffected until their handoff."""
        self._draining = True
        if self._delta_tab is not None and self._spool_dir:
            self._delta_tab.snapshot(self._spool_dir)

    def draining(self) -> bool:
        return self._draining

    def snapshot_sessions(self) -> dict:
        """Spool every quiescent session chain (graceful-shutdown path:
        the serve SIGTERM handler and deploy preStop land here via
        ``stop()``; chaos/regression tests call it directly).  Safe
        against a dispatcher wedged MID-STEP — the wedged chain carries
        ``in_step``/moves its epoch and the table skips/discards it
        (epoch-atomicity over completeness: that one client
        re-establishes, nobody replays half a mutation).  Ordering vs an
        in-flight background periodic write is the table's ``_spool_lock``:
        this call captures AND renames after that writer finishes, so an
        older capture can never replace this newer spool."""
        if not self._spool_dir or self._delta_tab is None:
            return {}
        return self._delta_tab.snapshot(self._spool_dir)

    def _maybe_snapshot(self) -> None:
        """Periodic epoch-boundary spool write, handed to a background
        thread: pickling up to KT_DELTA_SESSIONS chains + fsync must
        never sit on a sub-ms serving path or hold the scheduler lock
        (the table's per-entry torn-entry guard makes the lock-free
        write safe).  Interval state is _sched_lock-serialized (every
        call site holds it); at most one write is in flight — a boundary
        arriving while one runs is skipped, the next one catches up."""
        if (not self._spool_dir or self._delta_tab is None
                or self._snap_interval <= 0 or self._stop.is_set()):
            # the _stop check matters: stop() writes the shutdown spool
            # then clears the table, and a straggling tick afterwards
            # would snapshot the now-EMPTY table — whose empty-write
            # path removes the spool the shutdown just wrote
            return
        # callers already hold the (re-entrant) ownership lock; taking it
        # here keeps the interval/worker state lexically guarded
        with self._sched_lock:
            now = self._clock.now()
            if now - self._last_snap < self._snap_interval:
                return
            if (self._snap_worker is not None
                    and self._snap_worker.is_alive()):
                return
            self._last_snap = now
            self._snap_worker = threading.Thread(
                target=self._delta_tab.snapshot, args=(self._spool_dir,),
                name="session-snapshot", daemon=True)
            self._snap_worker.start()

    def _finalize(self, pending, fut: Future) -> None:
        try:
            try:
                result = pending.result()
            # ktlint: allow[KT005] the dispatcher must survive any fence
            # outcome; the exception is handed to the blocked RPC thread via
            # its future and re-raised there
            except BaseException as err:  # noqa: BLE001 — fan to the RPC
                self._feed_breaker(fut, err)
                _resolve(fut, exc=err)
                return
            self._feed_breaker(fut, None)
            _resolve(fut, result=result)
        finally:
            # resolved either way: out of the dispatcher's hand
            try:
                self._in_hand.remove(fut)
            except ValueError:
                pass  # already failed by a concurrent stop()

    def _feed_breaker(self, fut: Future, err: Optional[BaseException]) -> None:
        """Per-request outcome -> circuit-breaker probe accounting.  Host-
        routed solves never touch the device, so their outcomes must not
        close (or trip) the device-path breaker."""
        if self._adm is None:
            return
        if fut in self._host_futs:
            self._host_futs.discard(fut)
            return
        if self._faults:
            effect = self._faults.fire("breaker")
            if effect is not None and effect.kind == "breaker_trip":
                # synthetic failure into the breaker's device-path feed:
                # composes breaker-open host routing with whatever else
                # the schedule is doing.  RETURN: the request whose
                # completion carried the injected trip must not also
                # record its organic outcome — record_success would
                # reset the closed-state failure count to 0 and N
                # consecutive injected trips could never reach the
                # open threshold
                self._adm.breaker.record_failure("injected")
                return
        if err is None:
            self._adm.breaker.record_success()
        elif isinstance(err, DeviceHang):
            self._adm.breaker.record_failure("device_hang")

    def _bucket_of(self, kwargs: dict):
        """Megabatch bucket probe — None routes the request down the classic
        single path (also when the scheduler has no bucketing: RemoteScheduler
        facades, test doubles)."""
        if self.max_slots <= 1:
            return None
        if self._mesh_unshardable:
            # construction-time verdict (scheduler.mega_unshardable): no
            # sharded megabatch program exists for this mesh, so the
            # per-request probe — and its tensorize — is skipped entirely;
            # _flush labels the resulting single-request flushes
            # mesh_serial
            return None
        bucket = getattr(self.scheduler, "bucket_key", None)
        if bucket is None:
            return None
        # the probe hardens, routes and TENSORIZES the batch (the real
        # solve's tensorize is then a cache hit): for a fresh 50k-pod batch
        # it is the host build itself.  Each of those stretches is a span
        # of bucket_key's own under this one (harden, carve, tensorize,
        # signature), which keeps next to nothing for itself
        trace = kwargs.get("trace") or NULL_TRACE
        # the probe itself never fails a request (bucket_key boxes its own
        # errors and returns None), but a facade without that contract must
        # not take the dispatcher down either
        try:
            with trace.span("bucket") as span:
                key = bucket(kwargs)
                span.annotate(bucketed=key is not None)
            return key
        # ktlint: allow[KT005] probe failure = unbatchable, logged at the
        # scheduler layer; the request solves on the single path
        except Exception:
            return None

    def _flush(self, batch, reason: str) -> None:
        """Dispatch one coalescer flush: a single request keeps the classic
        pipelined submit; 2+ requests ride one scheduler.submit_many
        megabatch dispatch.  NEITHER fences here — both park in the
        in-flight queue so the dispatcher coalesces/tensorizes the next
        batch while this one executes; megabatched responses get honest
        enqueue→respond solve_ms at finalization."""
        if not batch:
            return
        if reason == "bucket" and len(batch) == 1 and self._mesh_unshardable:
            # the coalescer resolved an unshardable-mesh rejection (None
            # bucket key) into this single-request serial flush — the
            # mesh is WHY it rides alone, so label it honestly
            reason = "mesh_serial"
        if len(batch) == 1:
            self.registry.counter(MEGABATCH_FLUSH).inc({"reason": reason})
            self._dispatch_single(*batch[0])
            return
        # a scheduler that can degrade a meshed flush to serial owns the
        # flush count (it incs mesh_serial OR our reason at dispatch, so
        # the labels partition flushes); facades/doubles without the
        # capability keep the upfront count here
        delegated = getattr(self.scheduler, "counts_flush_reason", False)
        if not delegated:
            self.registry.counter(MEGABATCH_FLUSH).inc({"reason": reason})
        try:
            pendings = self.scheduler.submit_many(
                [kw for kw, _f, _t, _w in batch],
                **({"flush_reason": reason} if delegated else {}))
        # ktlint: allow[KT005] submit failures fan to every waiting RPC
        # thread through their futures; the dispatcher itself must live on
        except BaseException as err:  # noqa: BLE001
            if delegated:
                # a registration-phase raise never reached the collector's
                # end-of-dispatch count — the flush still happened, and an
                # uncounted failing flush is the one an operator most
                # wants visible in the partition
                self.registry.counter(MEGABATCH_FLUSH).inc(
                    {"reason": reason})
            for _kw, fut, _t, _w in batch:
                _resolve(fut, exc=err)
                self._unhand(fut)
            return
        # one in-flight entry for the WHOLE megabatch (depth counts device
        # dispatches, and the megabatch is one); finalization order stays
        # FIFO because singles and megabatches share the one queue
        self._drain(self._inflight.push(("mega", list(zip(batch, pendings)))))
        if self._inbound_idle() and not len(self._coal):
            self._drain(self._inflight.pop_to(0))

    def _inbound_idle(self) -> bool:
        """No request waiting to be picked up (whichever front door is
        active: the admission queue or the raw FIFO)."""
        if self._adm is not None:
            return len(self._adm.queue) == 0
        return self._q.empty()

    def _host_scheduler(self) -> BatchScheduler:
        """Lazily-built oracle (host FFD) scheduler for breaker-open /
        brownout-routed solves.  Shares the pipeline's registry and the
        main scheduler's tracer so routed solves stay observable."""
        if self._host_sched is None:
            self._host_sched = BatchScheduler(
                backend="oracle", registry=self.registry,
                tracer=getattr(self.scheduler, "tracer", None),
            )
        return self._host_sched

    def _unhand(self, fut: Future) -> None:
        self._fwd_pclass.pop(fut, None)
        try:
            self._in_hand.remove(fut)
        except ValueError:
            pass  # already failed by a concurrent stop()

    def _drain(self, entries) -> None:
        for entry in entries:
            head, rest = entry
            if head == "mega":
                self._finalize_mega(rest)
            else:
                self._finalize(head, rest)

    def _finalize_mega(self, pairs) -> None:
        for (kwargs, fut, _t_enq, t_wall), pending in pairs:
            try:
                result = pending.result()
                # honest per-request latency: this RPC's enqueue → respond
                # wall time, not the megabatch-amortized device time
                result.solve_ms = (time.perf_counter() - t_wall) * 1000.0
            except SlotNotOwned as err:
                # the per-host fence demuxed this slot to another host
                # (multi-process mesh): route it through the forwarding
                # shim — NOT a device failure, so the breaker never sees
                # it, and the owner-host RPC runs off-thread so
                # batchmates' finalization is never stalled behind it
                self._forward_foreign(kwargs, fut, err, t_wall)
            # ktlint: allow[KT005] per-request failure fans to ITS RPC
            # thread only; batchmates still resolve
            except BaseException as err:  # noqa: BLE001
                self._feed_breaker(fut, err)
                _resolve(fut, exc=err)
            else:
                self._feed_breaker(fut, None)
                _resolve(fut, result=result)
            self._unhand(fut)

    def _forward_foreign(self, kwargs: dict, fut: Future,
                         err: SlotNotOwned, t_wall) -> None:
        """Resolve a foreign-slot future via the cross-host forwarding
        shim on its own thread (the RPC to the owning host must not stall
        the dispatcher); a disabled shim resolves the typed SlotNotOwned
        inline (counted 'unrouted')."""
        fwd = self._forwarder
        # read the admitted class NOW (dispatcher thread) — _unhand
        # clears the ledger entry right after this returns
        pclass = self._fwd_pclass.get(fut, "")
        if not fwd.enabled():
            try:
                fwd.forward(kwargs, err, priority=pclass)
            # ktlint: allow[KT005] the typed SlotNotOwned (or the shim's
            # wrapped transport error) fans to the waiting RPC thread
            except BaseException as exc:  # noqa: BLE001
                _resolve(fut, exc=exc)
            return
        kwargs = dict(kwargs)

        def run():
            try:
                result = fwd.forward(kwargs, err, priority=pclass)
                result.solve_ms = (time.perf_counter() - t_wall) * 1000.0
            # ktlint: allow[KT005] forwarding failure fans to ITS RPC
            # thread only, typed by the shim
            except BaseException as exc:  # noqa: BLE001
                _resolve(fut, exc=exc)
            else:
                _resolve(fut, result=result)

        if self._fwd_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._fwd_pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="slot-forward")
        self._fwd_pool.submit(run)

    def _dispatch_single(self, kwargs: dict, fut: Future, t_enq, t_wall,
                         scheduler: Optional[BatchScheduler] = None) -> None:
        try:
            pending = (scheduler or self.scheduler).submit(
                kwargs.pop("pods"), kwargs.pop("provisioners"),
                kwargs.pop("instance_types"), **kwargs,
            )
        # ktlint: allow[KT005] submit failures fan to the waiting RPC
        # thread through its future; the dispatcher itself must live on
        except BaseException as err:  # noqa: BLE001
            self._host_futs.discard(fut)
            _resolve(fut, exc=err)
            self._unhand(fut)
            return
        self._drain(self._inflight.push((pending, fut)))
        if self._inbound_idle() and not len(self._coal):
            # no overlap work available: drain so this caller's latency
            # is one dispatch + one fence, exactly the unpipelined path
            self._drain(self._inflight.pop_to(0))

    def delta_live(self) -> bool:
        """Whether session-routed requests have somewhere to land (KT_DELTA
        on).  Service-side routing probes this before tagging kwargs."""
        return self._delta_tab is not None

    def _inline_ok(self) -> bool:
        """Inline-shortcut eligibility: the pipeline is COMPLETELY idle —
        nothing queued, coalesced, in flight, or in the dispatcher's hand.
        Best-effort reads from the RPC thread (dispatcher-owned state);
        CORRECTNESS never rests on them — only _sched_lock serializes
        scheduler access — the check protects class ORDERING: an inline
        delta must not overtake work already queued ahead of it."""
        return (not self._stop.is_set()
                # live inline-routing knob: reads the last applied
                # IMMUTABLE snapshot (best-effort like the rest of this
                # probe; the registry knob lands via _apply_knobs)
                and bool(self._knob_snap.inline_delta)
                and not self._in_hand
                and not len(self._inflight)
                and not len(self._coal)
                and self._inbound_idle())

    def _solve_inline(self, kwargs: dict, pclass, deadline_s,
                      trace, t_enq, t_wall):
        """Serve one session-routed request on its own RPC thread (caller
        holds _sched_lock).  Admission posture applies in full via
        admit_inline — brownout-rung sheds, concurrency quota, rate limit
        all raise the same typed errors the queue path maps to the wire;
        only queue residency (depth quotas, preemption, deadline expiry
        while queued) is moot because dispatch is immediate."""
        ticket = None
        if self._adm is not None:
            pclass = parse_class(pclass or "")
            t0a = trace.now()
            ticket = self._adm.admit_inline(pclass, deadline_s=deadline_s)
            trace.record(
                "admission", t0a, trace.now(), priority_class=pclass,
                queued=0, inline=True,
                brownout=self._adm.brownout.level,
                breaker=self._adm.breaker.state)
        try:
            trace.record("window", t_enq, trace.now(), inflight=0,
                         coalesced=0, inline=True)
            info = kwargs.pop("_delta")
            kwargs.pop("_pclass", None)
            t0 = trace.now()
            wall0 = time.perf_counter()
            reply, outcome = self._serve_delta(kwargs, info, trace)
            self.registry.histogram(DELTA_RPC_DURATION).observe(
                time.perf_counter() - wall0)
            trace.record("delta", t0, trace.now(),
                         session=info["session_id"], outcome=outcome,
                         mode=reply.mode, epoch=reply.epoch, inline=True)
            # no observe_idle here: the dispatcher's own idle ticks (every
            # 100ms regardless of inline traffic) keep the brownout EWMA
            # decaying and the breaker feeds polled — paying a breaker
            # counter sweep per sub-ms RPC would tax exactly the path this
            # shortcut exists to strip
            reply.solve_ms = (time.perf_counter() - t_wall) * 1000.0
            self._maybe_snapshot()  # epoch boundary (caller holds the
            return reply            # scheduler-ownership lock)
        finally:
            if ticket is not None:
                self._adm.release(ticket)

    def _dispatch_delta(self, kwargs: dict, fut: Future, t_enq, t_wall) -> None:
        """Session-routed dispatch — the delta fast path.

        Bypasses the megabatch coalescer entirely: a sub-millisecond
        incremental step must not wait out ``KT_MAX_WAIT_MS`` in a slot
        queue, and it could never share a compiled bucket with full solves
        anyway.  Anything already held is flushed FIRST, so coalesced
        batchmates are never delayed behind session traffic.  Host routing
        (breaker open / brownout rung 3) is deliberately skipped: the
        incremental tiers never dispatch to the device, and the scan/full
        subsolves run through ``scheduler.solve``, which owns the device-
        health fallback ladder — guards err toward latency, never
        correctness (the PR-6 contract).  Admission is NOT skipped: the
        request was admitted as a normal ticket in its class before it
        got here (brownout L4 sheds best_effort deltas like any other)."""
        for reason, _key, batch in self._coal.flush("bucket"):
            self._flush(batch, reason)
        info = kwargs.pop("_delta")
        trace = kwargs.get("trace") or NULL_TRACE
        t0 = trace.now()
        wall0 = time.perf_counter()
        try:
            reply, outcome = self._serve_delta(kwargs, info, trace)
        # ktlint: allow[KT005] a failing step fans to its RPC thread via
        # the future; the dispatcher itself must live on
        except BaseException as err:  # noqa: BLE001
            _resolve(fut, exc=err)
            self._unhand(fut)
            return
        self.registry.histogram(DELTA_RPC_DURATION).observe(
            time.perf_counter() - wall0)
        trace.record("delta", t0, trace.now(),
                     session=info["session_id"], outcome=outcome,
                     mode=reply.mode, epoch=reply.epoch)
        # honest per-request latency: enqueue -> respond wall time
        reply.solve_ms = (time.perf_counter() - t_wall) * 1000.0
        _resolve(fut, result=reply)
        self._unhand(fut)
        # epoch boundary: the chain just committed, nothing is mid-step —
        # the natural moment for the periodic durability write
        self._maybe_snapshot()

    def _serve_delta(self, kwargs: dict, info: dict, trace):
        """One session-routed request -> (DeltaReply, outcome label).

        Runs on the dispatcher thread; the chain entry is dispatcher-owned
        end to end, so everything handed back for encoding is DETACHED
        (DeltaReply snapshots) — the next delta may mutate the chain while
        the RPC thread is still serializing this reply."""
        tab = self._delta_tab
        sid = info["session_id"]
        pods = kwargs.pop("pods")
        provisioners = kwargs.pop("provisioners")
        instance_types = kwargs.pop("instance_types")

        def _counted(reply: DeltaReply, outcome: str):
            # every outcome — incremental, fallback, establish, unknown —
            # is counted HERE, in the function that runs the solves:
            # ktlint KT015 pins that no delta-path full solve can ship
            # without its outcome landing in karpenter_solver_delta_rpc_total
            self.registry.counter(DELTA_RPC).inc({"outcome": outcome})
            return reply, outcome

        if not info["delta"]:
            if self._draining and tab is not None:
                # graceful drain: this replica admits NO new (or re-
                # establishing) sessions — the DRAINING hint sends the
                # client to a sibling, which establishes there instead of
                # binding a chain to a pod about to die
                if protocol._SINK is not None:
                    protocol.emit(sid, "drain_refused",
                                  replica=tab.replica)
                return _counted(DeltaReply(state="draining", full=False),
                                "drain_refused")
            # establish (or re-establish): ONE classic full solve, and the
            # result becomes the session's chain base
            result = self.scheduler.solve(
                pods, provisioners, instance_types,
                existing_nodes=kwargs.get("existing_nodes", ()),
                daemonsets=kwargs.get("daemonsets", ()),
                unavailable=kwargs.get("unavailable") or None,
                allow_new_nodes=kwargs.get("allow_new_nodes", True),
                max_new_nodes=kwargs.get("max_new_nodes"),
                trace=trace,
            )
            if tab is None:
                # delta serving off: answer like a plain solve ("" state
                # tells the client no session was retained)
                return _counted(_full_reply(result, 0, "", state=""), "establish")
            # establishment epochs come from the table's monotone floor,
            # NOT a constant 1: a re-established session must never be
            # able to advance back onto an epoch a stale incarnation
            # (spooled, or lost to an eviction race) already reached —
            # an exact-match epoch check against stale state is the one
            # silent-divergence path the protocol must close
            epoch0 = tab.next_epoch()
            # chain-identity nonce (model-checker divergence fix, ISSUE
            # 17): the epoch floor alone cannot protect against a spool
            # ROLLBACK restoring an old incarnation's record — its epoch
            # can collide with the new chain's acked epoch and the exact-
            # match check would silently apply a delta across lineages.
            # A per-establishment nonce makes chain identity explicit;
            # "" (old clients, legacy spool records) stays a wildcard.
            nonce0 = uuid.uuid4().hex[:16]
            tab.put(SessionEntry(
                session_id=sid, prev=result, epoch=epoch0,
                catalog_epoch=info["catalog_epoch"],
                provisioners=provisioners, instance_types=instance_types,
                daemonsets=kwargs.get("daemonsets") or (),
                unavailable=set(kwargs.get("unavailable") or ()),
                nonce=nonce0,
            ))
            if self._spool_dir:
                # take spool ownership NOW (force-claim): the client's
                # establishment supersedes any incarnation a sibling's
                # lease still guards — without this a session re-homed by
                # a routing flap livelocks between the stale lease holder
                # and the replica actually serving it.  Lifecycle span:
                # the session's lease CLAIM, the first event of its
                # journey timeline (docs/OBSERVABILITY.md span taxonomy).
                t0c = trace.now()
                tab.own(sid, self._spool_dir)
                trace.record("session_claim", t0c, trace.now(),
                             session_id=sid, replica_id=tab.replica,
                             epoch=epoch0)
            reply = _full_reply(result, epoch0, "establish")
            reply.nonce = nonce0
            return _counted(reply, "establish")
        # ---- incremental step -------------------------------------------
        entry = tab.get(sid) if tab is not None else None
        if entry is None and tab is not None and self._spool_dir:
            # fleet failover (docs/RESILIENCE.md): the chain may be
            # waiting in the shared spool — a dead or drained sibling
            # spooled it, the client re-homed here, and adoption (lease
            # claim + record consume) serves this very delta WARM.  Every
            # adoption outcome is counted; an unexpired sibling lease
            # refuses typed and the client pays the PR-10 exactly-one
            # re-establish instead.  Lifecycle span: "session_steal" when
            # the previous owner's lease had expired (the dead-replica
            # path), "session_adopt" otherwise — with the adopted-from
            # replica, so the journey timeline shows WHERE the chain came
            # from (docs/OBSERVABILITY.md span taxonomy).
            t0a = trace.now()
            entry = tab.adopt(self._spool_dir, sid)
            if entry is not None:
                trace.record(
                    "session_steal" if entry.adopt_how == "stolen"
                    else "session_adopt",
                    t0a, trace.now(), session_id=sid,
                    replica_id=tab.replica, epoch=entry.epoch,
                    adopted_from=entry.adopted_from)
        nonce_mismatch = (entry is not None and entry.nonce
                          and info.get("nonce")
                          and entry.nonce != info["nonce"])
        if entry is None or entry.epoch != info["base_epoch"] \
                or nonce_mismatch:
            # evicted / never established / epoch mismatch after a lost
            # response: the only safe answer is "re-establish" — applying
            # a delta onto the wrong base would silently diverge.  The
            # nonce arm closes the cross-lineage collision the model
            # checker found: a rolled-back old-incarnation record can
            # re-reach the very epoch this client acked, and the epoch
            # check alone would pass; matching chain IDENTITY (not just
            # position) makes the collision typed instead of silent.
            if protocol._SINK is not None:
                protocol.emit(sid, "serve_unknown", replica=tab.replica,
                              why=("nonce" if nonce_mismatch else
                                   "epoch" if entry is not None
                                   else "missing"))
            return _counted(DeltaReply(state="unknown", full=False),
                            "session_unknown")
        reseed = info["catalog_epoch"] != entry.catalog_epoch
        if reseed and not instance_types:
            # the catalog/price epoch moved and the new catalog is not
            # on the wire: every price the chain packed against is
            # stale, and there is nothing to re-pack with
            if protocol._SINK is not None:
                protocol.emit(sid, "serve_unknown", replica=tab.replica,
                              why="catalog")
            return _counted(DeltaReply(state="unknown", full=False),
                            "session_unknown")
        try:
            reply, outcome = self._apply_delta_step(
                entry, info, pods, provisioners, instance_types,
                kwargs, reseed, trace, _counted)
            # every incremental reply echoes the chain's identity nonce
            # so the client keeps sending the right one across reseeds
            # and guard-trip fallbacks (the chain object is the same)
            reply.nonce = entry.nonce
            if self._draining and reply.state == "ok":
                # drain handshake: the step was served (warm, committed),
                # its chain is handed off to the shared spool (record at
                # the acked epoch, lease RELEASED, entry dropped), and
                # the reply carries the DRAINING hint so the client
                # re-homes before this pod dies — the adopting sibling
                # serves the session's next delta warm.  Lifecycle span:
                # the handoff is the journey event that explains the
                # replica change the next hop's adopt span completes.
                t0h = trace.now()
                tab.handoff(sid, self._spool_dir)
                trace.record("session_drain_handoff", t0h, trace.now(),
                             session_id=sid, replica_id=tab.replica,
                             epoch=reply.epoch)
                reply.state = "draining"
            return reply, outcome
        # ktlint: allow[KT005] re-raised after eviction: the RPC thread
        # gets the real error, the poisoned chain never serves again
        except BaseException:
            # the step raised MID-APPLY: the chain may be half-mutated at
            # an unchanged epoch, and the client's cumulative retry would
            # pass the epoch check and re-apply onto a corrupted base —
            # evict, so the client re-establishes from scratch.  The
            # recovery outcome is counted whether the fault was injected
            # or organic (docs/RESILIENCE.md invariant: errors are typed,
            # recoveries are visible).
            tab.drop(sid, "error")
            faults_mod.count_recovery(self.registry, "delta_step",
                                      "evicted")
            raise

    def _apply_delta_step(self, entry: SessionEntry, info: dict, pods,
                          provisioners, instance_types, kwargs: dict,
                          reseed: bool, trace, _counted):
        """Apply one incremental step onto a live chain (dispatcher- or
        inline-thread, under _sched_lock either way).  Mutates the entry;
        the caller owns eviction if anything below raises."""
        # mid-mutation marker: from here until the epoch increments, this
        # chain must never be snapshotted (the spool writer skips it) —
        # set BEFORE the first mutation below, cleared after the commit
        entry.in_step = True
        if self._faults:
            effect = self._faults.fire("delta_step")
            if effect is not None and effect.kind == "slow_step":
                # injected latency while in_step is True: the adversary a
                # SIGTERM-mid-mutation snapshot must survive
                self._faults.sleep(effect)
        if reseed:
            entry.instance_types = instance_types
            if provisioners:
                entry.provisioners = provisioners
            entry.catalog_epoch = info["catalog_epoch"]
        prev = entry.prev
        # the step's watch set — every pod whose placement can change:
        # the adds, the removals, everything previously unplaced (removals
        # free capacity and re-offer them), and pods displaced off
        # reclaimed nodes.  The incremental tiers never move any other
        # pod (warmstart.py's by-construction contract), so the reply
        # only has to carry these.
        watch = {p.name for p in pods}
        watch.update(info["removed"])
        if gangmod.gang_enabled() and info["removed"]:
            # a member removal retracts the WHOLE gang (ISSUE 20): the
            # comembers' seats change too, so the delta reply must carry
            # them — the scheduler's own expansion decides their fate
            watch.update(gangmod.expand_gang_removals(
                prev, info["removed"])[0])
        watch.update(prev.infeasible)
        meta = getattr(prev, "_warmstart_meta", None)
        if meta is not None:
            watch.update(meta.unplaced)
        if info["reclaimed"]:
            by_name = {n.name: n
                       for n in list(prev.existing_nodes) + list(prev.nodes)}
            for rname in info["reclaimed"]:
                node = by_name.get(rname)
                if node is not None:
                    watch.update(p.name for p in node.pods)
        # ICE'd offerings accumulate on the ENTRY, not just the chain meta:
        # a guard-trip full fallback drops the meta, and the rebuild must
        # not forget offerings iced three steps ago
        entry.unavailable.update(tuple(u)
                                 for u in kwargs.get("unavailable") or ())
        outcome = self.scheduler.solve_delta(
            prev, added=pods, removed=info["removed"],
            iced=list(info["reclaimed"]),
            provisioners=entry.provisioners,
            instance_types=entry.instance_types,
            daemonsets=entry.daemonsets,
            unavailable=set(entry.unavailable) or None,
            force_full=reseed, trace=trace,
        )
        entry.prev = outcome.result
        if self._faults:
            # the half-mutated adversary: prev already replaced, epoch not
            # yet acked — a raise HERE must evict, never snapshot
            self._faults.fire("delta_commit")
        entry.epoch += 1
        entry.in_step = False
        if protocol._SINK is not None:
            # the COMMIT transition: the step is applied, the epoch is
            # acked — the event conformance checks against the model
            protocol.emit(entry.session_id, "commit",
                          replica=self._delta_tab.replica,
                          epoch=entry.epoch)
        if reseed:
            return _counted(
                _full_reply(outcome.result, entry.epoch, "reseed"), "reseed")
        if outcome.fell_back:
            # a warm-start guard tripped (KT_DELTA_MAX_FRAC, constraint
            # coupling, vocabulary miss): the step was served by the full
            # re-solve from the stripped base — correct, slower, and the
            # session survives; the reply is full-shaped
            return _counted(_full_reply(outcome.result, entry.epoch, "full"),
                            "fallback_full")
        res = outcome.result
        # node churn comes from the outcome's INCREMENTAL bookkeeping
        # (warmstart maintains created/pruned per step) — never a diff
        # over the fleet's node set, which would put an O(cluster) scan
        # on every sub-ms RPC
        meta2 = getattr(res, "_warmstart_meta", None)
        created = []
        if meta2 is not None:
            created = [meta2.nodes[meta2.node_idx[nm]].snapshot()
                       for nm in outcome.created_nodes
                       if nm in meta2.node_idx]
        reply = DeltaReply(
            state="ok", epoch=entry.epoch, mode=outcome.mode, full=False,
            assignments={n: res.assignments[n] for n in watch
                         if n in res.assignments},
            infeasible={n: res.infeasible[n] for n in watch
                        if n in res.infeasible},
            nodes=created,
            removed_nodes=list(outcome.pruned_nodes),
            solve_ms=outcome.solve_ms,
        )
        return _counted(reply, "delta")

    def _next_item(self, timeout: float):
        """Pop the next request from whichever front door is active.
        Admission path: priority-ordered pop + queue-delay accounting +
        the pre-dispatch deadline check — an expired ticket is rejected
        HERE, before any tensorize or device work happened for it."""
        if self._adm is None:
            return self._q.get(timeout=timeout)  # raises queue.Empty
        while True:
            ticket = self._adm.get(timeout=timeout)
            if ticket is None:
                raise queue.Empty
            self._adm.observe_dispatch(ticket)
            self._adm.breaker.poll()
            kwargs, fut, t_enq, t_wall = ticket.item
            if ticket.expired(self._adm.clock.now()):
                _resolve(fut, exc=self._adm.expire(ticket))
                timeout = 0.0  # deadline sheds must not reset the wait
                continue
            return kwargs, fut, t_enq, t_wall

    def _clamp_slots(self, n: int) -> int:
        """Bound a slot-cap ask against the global ladder and (meshed
        schedulers) floor/cap it at the mesh's device count / largest
        in-ladder rung — the ONE slot-clamp used at construction and at
        every live knob application, so a tuned cap can never flush
        half-empty shards or overflow the sharded program."""
        n = max(1, min(MEGA_MAX_SLOTS, int(n)))
        mesh = getattr(self.scheduler, "mesh", None)
        if mesh is not None and n > 1:
            n_dev = int(mesh.devices.size)
            if n_dev <= MEGA_MAX_SLOTS:
                n = min(max(n, n_dev), max_mega_slots(mesh))
        return n

    def _apply_knobs(self) -> None:
        """Dispatcher-owned knob application (caller holds _sched_lock):
        ONE atomic registry snapshot per iteration drives the coalescer's
        wait/slots, the brownout ladder's parameters, and the delta
        inline gate.  A knob the registry never overrode keeps its
        construction-time value byte-identically; a tuner update lands
        WHOLE at the next iteration, never mid-flush (ISSUE 19).  The
        brownout ladder's rungs then overlay the (possibly tuned) bases:
        rung 1+ zeroes the wait, rung 2+ caps the slots; back at level 0
        both revert."""
        snap = self.knobs.snapshot()
        self._knob_snap = snap
        base_wait = (max(0.0, snap.max_wait_ms) / 1000.0
                     if snap.is_overridden("max_wait_ms") else self.max_wait)
        base_slots = (self._clamp_slots(snap.max_slots)
                      if snap.is_overridden("max_slots") else self.max_slots)
        if self._adm is None:
            self._coal.max_wait = base_wait
            self._coal.max_slots = base_slots
            return
        if snap.is_overridden("brownout_ms"):
            self._adm.brownout.retune(
                step_s=max(0.0, snap.brownout_ms) / 1000.0)
        if snap.is_overridden("brownout_slot_cap"):
            self._adm.brownout.retune(slot_cap=int(snap.brownout_slot_cap))
        self._coal.max_wait = self._adm.brownout.max_wait(base_wait)
        self._coal.max_slots = self._adm.brownout.slot_cap(base_slots)

    def _effective_max_wait(self) -> float:
        snap = self._knob_snap
        base = (max(0.0, snap.max_wait_ms) / 1000.0
                if snap.is_overridden("max_wait_ms") else self.max_wait)
        if self._adm is None:
            return base
        return self._adm.brownout.max_wait(base)

    def _loop(self) -> None:
        while not self._stop.is_set():
            deadline = self._coal.deadline()
            if deadline is not None:
                timeout = min(0.1, max(0.0, deadline - self._clock.now()))
            else:
                timeout = 0.1
            try:
                kwargs, fut, t_enq, t_wall = self._next_item(timeout)
            except queue.Empty:
                if self._adm is not None:
                    # decay the brownout EWMA + poll the breaker feeds so
                    # recovery doesn't need traffic to make progress
                    self._adm.observe_idle()
                with self._sched_lock:
                    # tuned knobs (and brownout recovery) must land on
                    # idle ticks too — a quiet pipeline still converges
                    self._apply_knobs()
                    for reason, _key, batch in self._coal.poll():
                        self._flush(batch, reason)
                    if not len(self._coal):
                        self._drain(self._inflight.pop_to(0))
                    # idle tick: chains quiescent under _sched_lock — keep
                    # the spool fresh even when delta traffic rides the
                    # inline shortcut between dispatcher wakeups
                    self._maybe_snapshot()
                continue
            # in hand from pop to resolution (_flush/_finalize remove
            # it); coalescer-held requests stay in the ledger so a
            # stop() mid-hold fails them instead of stranding their
            # RPC threads.  A fut parked in _inflight is in the ledger
            # too — stop() may fail it twice (once per structure),
            # which _resolve absorbs.  Appended BEFORE acquiring the
            # ownership lock: the inline shortcut's _inline_ok reads
            # _in_hand, and appending later would open a window where a
            # just-popped request is invisible and an arriving delta
            # could overtake it.
            self._in_hand.append(fut)
            # every scheduler-touching section of an iteration holds the
            # ownership lock (the blocking queue wait above deliberately
            # does NOT): while the dispatcher works, the delta fast path's
            # inline shortcut cannot acquire and routes through the queue
            with self._sched_lock:
                self._apply_knobs()
                # close the queue-wait phase on the request's trace:
                # enqueue (RPC thread) -> pickup (this dispatcher)
                trace = kwargs.get("trace") or NULL_TRACE
                trace.record("window", t_enq, trace.now(),
                             inflight=len(self._inflight),
                             coalesced=len(self._coal))
                if "_delta" in kwargs:
                    # session-routed request: the delta fast path (bypasses
                    # the coalescer AND host routing — see _dispatch_delta;
                    # admission already ticketed it in its class)
                    kwargs.pop("_pclass", None)
                    self._dispatch_delta(kwargs, fut, t_enq, t_wall)
                    if self._inbound_idle() and not len(self._coal):
                        self._drain(self._inflight.pop_to(0))
                    continue
                if self._adm is not None:
                    pclass = kwargs.pop("_pclass", "") or ""
                    if pclass:
                        # remember the admitted class for the forwarding
                        # shim: a foreign-slot re-dispatch must carry it,
                        # or the owning host re-admits an already-admitted
                        # critical request as default-class and can shed
                        # it (cleared by _unhand on every resolution path)
                        self._fwd_pclass[fut] = pclass
                    host_reason = self._adm.route_host(pclass)
                    if host_reason is not None:
                        # breaker open / brownout rung 3+: this solve takes
                        # the host FFD tier — flush anything held first so
                        # response FIFO order survives, then dispatch on
                        # the single path
                        trace.annotate(host_routed=host_reason)
                        for reason, _key, batch in self._coal.flush("bucket"):
                            self._flush(batch, reason)
                        self._host_futs.add(fut)
                        self._dispatch_single(
                            kwargs, fut, t_enq, t_wall,
                            scheduler=self._host_scheduler())
                        continue
                key = self._bucket_of(kwargs)
                for reason, _key, batch in self._coal.add(
                        key, (kwargs, fut, t_enq, t_wall)):
                    self._flush(batch, reason)
                if len(self._coal) and self._inbound_idle() \
                        and self._effective_max_wait() <= 0.0:
                    # queue went idle with no wait configured: flush NOW so
                    # a lone request's latency matches the unbatched path;
                    # under real concurrency the queue is non-empty here
                    # and slots keep filling
                    for reason, _key, batch in self._coal.flush("deadline"):
                        self._flush(batch, reason)
        with self._sched_lock:
            for reason, _key, batch in self._coal.flush("deadline"):
                self._flush(batch, reason)
            self._drain(self._inflight.pop_to(0))


#: instance-type lists a sidecar keeps by its own digest: room for the
#: operator's catalog, a refresh of it and a second caller's, no more
CATALOGS_KEPT = 4


class CatalogUnknown(LookupError):
    """A Solve named a catalog this sidecar does not hold (direct callers;
    over gRPC it is FAILED_PRECONDITION with the same text)."""


class KeptCatalogs:
    """``digest -> the InstanceTypes decoded under it``: the
    ``CATALOGS_KEPT`` lists used last.  What it hands out are the SAME
    objects every time, which is the point: ``_instance_type_sig``'s
    identity memo hits on them (models/tensorize.py)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_digest: "OrderedDict[str, Tuple]" = OrderedDict()

    def get(self, digest: str) -> Optional[Tuple]:
        with self._lock:
            held = self._by_digest.get(digest)
            if held is not None:
                self._by_digest.move_to_end(digest)
            return held

    def keep(self, digest: str, instance_types: Sequence) -> None:
        """A digest already held keeps its first objects: requests that
        name it go on solving on the objects they solved on before."""
        with self._lock:
            self._by_digest.setdefault(digest, tuple(instance_types))
            self._by_digest.move_to_end(digest)
            while len(self._by_digest) > CATALOGS_KEPT:
                self._by_digest.popitem(last=False)

    def __len__(self) -> int:
        return len(self._by_digest)


class _Door:
    """How many requests the sidecar's handlers hold, and the stretches in
    which it holds none, each timed as the detached phase
    ``await_request``: from the moment the last ``response_serialize``
    closed with nothing in hand to the next ``request_parse``.  It is the
    sidecar's own account of the client's turn (its codec, both
    transports, whatever the caller does between two requests); with
    several clients it measures "no request in the sidecar", not a sum per
    client.  A request is counted where its handler enters and leaves, so
    one that gRPC parses and never hands to a handler (cancelled in
    between) ends the wait it arrived in and leaves no count behind: that
    one stretch goes unrecorded and the next answered request opens the
    wait again.  A disabled tracer hands out ``NULL_PHASE`` and nothing is
    recorded."""

    def __init__(self, tracer: Tracer) -> None:
        self._tracer = tracer
        self._lock = threading.Lock()
        self._held = 0      # guarded-by: _lock
        self._idle = None   # guarded-by: _lock — the open await_request

    def arrive(self) -> None:
        """Bytes of a request are here: the wait for them, if one is open,
        ends (on gRPC's parsing thread, not the one it began on)."""
        if not self._tracer.enabled:
            return
        with self._lock:
            idle, self._idle = self._idle, None
        if idle is not None:
            idle.__exit__(None, None, None)

    def enter(self) -> None:
        """A handler took a request."""
        with self._lock:
            self._held += 1

    def leave(self) -> None:
        """A request is out of the sidecar (answered or failed); with none
        left in hand the wait begins."""
        if not self._tracer.enabled:
            return
        with self._lock:
            self._held = max(0, self._held - 1)
            if self._held == 0 and self._idle is None:
                self._idle = self._tracer.phase(
                    "await_request", detached=True).__enter__()


class SolverService:
    def __init__(self, scheduler: Optional[BatchScheduler] = None,
                 registry: Optional[Registry] = None,
                 tracer: Optional[Tracer] = None,
                 max_slots: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 knobs: Optional[Knobs] = None) -> None:
        self.registry = registry or default_registry
        self.scheduler = scheduler or BatchScheduler(registry=self.registry)
        # serving knobs for every pipeline this service constructs (None:
        # KT_MAX_SLOTS / KT_MAX_WAIT_MS env, then the module defaults)
        self.max_slots = max_slots
        self.max_wait_ms = max_wait_ms
        # per-RPC traces; default to the scheduler's tracer so the sidecar's
        # /tracez sees exactly what its scheduler recorded
        self.tracer = tracer or getattr(
            self.scheduler, "tracer", None) or tracer_for(self.registry)
        #: request_parse timestamps on their way from gRPC's deserialiser
        #: to the handler, by id(request) (parse_request)
        self._parse_times: dict = {}
        self._door = _Door(self.tracer)
        for how in REQUEST_DECODE_HOW:
            self.registry.counter(REQUEST_DECODE_PODS).inc(
                {"how": how}, value=0)
        #: the catalogs of sessionless Solves, by this sidecar's digest
        self.catalogs = KeptCatalogs()
        for how in REQUEST_CATALOG_HOW:
            self.registry.counter(REQUEST_CATALOG).inc({"how": how}, value=0)
        self._schedulers = {"": self.scheduler}  # guarded-by: _direct_lock
        # KT_SOLVE_PIPELINE=0 falls back to direct, lock-serialized solves
        self._pipelined = os.environ.get("KT_SOLVE_PIPELINE", "1") != "0"
        if not self._pipelined and admission_enabled():
            # admission control rides the pipeline's queue; the direct
            # debug path has none — say so loudly instead of letting the
            # operator believe overload protection is active while inert
            logging.getLogger(__name__).warning(
                "KT_SOLVE_PIPELINE=0: direct solves bypass admission "
                "control entirely (no priority queue, no deadline "
                "shedding, no breaker/brownout — docs/ADMISSION.md)")
        self._pipelines: dict = {}               # guarded-by: _direct_lock
        self._closed = False                     # guarded-by: _direct_lock
        self._direct_lock = threading.Lock()
        # time-resolved telemetry (ISSUE 18): the background registry
        # sampler (NULL_SAMPLER when KT_TS_INTERVAL_S <= 0), the span-
        # stream occupancy accountant publishing its gauges on the
        # sampler's tick, and the per-class SLO burn-rate engine whose
        # windowed numbers come off the sampler's rings
        self.sampler = sampler_for(self.registry, clock=self.tracer.clock)
        self._occupancy = OccupancyAccountant(
            self.registry, clock=self.tracer.clock,
            sample_every=self.tracer.sample_every)
        self.tracer.add_sink(self._occupancy.on_trace)
        self.slo = SloEngine(self.registry, sampler=self.sampler,
                             clock=self.tracer.clock,
                             replica=self.tracer.replica)
        # self-tuning (ISSUE 19, docs/TUNING.md): the live knob registry
        # is always on (it changes nothing until a knob is set); the
        # feedback controller arms only with KT_TUNE=1 AND a live
        # sampler — it rides the sampler's tick like the occupancy
        # accountant, so FakeClock harnesses drive it deterministically.
        # An injected registry keeps a tuned bench/test service from
        # leaking overrides into the process-global singleton.
        self.knobs = knobs if knobs is not None else global_knobs()
        tuning_zero_init(self.registry)
        self.tuner: Optional[TuningController] = None
        if tune_enabled() and self.sampler:
            self.tuner = TuningController(
                self.knobs, self.registry, sampler=self.sampler,
                slo=self.slo, tracer=self.tracer)
            self.sampler.add_hook(self.tuner.on_tick)
        if self.sampler:
            self.sampler.add_hook(self._occupancy.tick)
            self.sampler.start()

    def _scheduler_for(self, backend: str) -> BatchScheduler:
        if backend and backend != self.scheduler.backend:
            # locked check-then-create: two concurrent first RPCs for the
            # same backend must share ONE scheduler (and therefore one
            # pipeline — _pipeline_for keys on the scheduler instance; a
            # lost race here would leak a live dispatcher thread forever)
            with self._direct_lock:
                if backend not in self._schedulers:
                    self._schedulers[backend] = BatchScheduler(
                        backend=backend, registry=self.registry
                    )
                return self._schedulers[backend]
        return self.scheduler

    def _pipeline_for(self, sched: BatchScheduler) -> SolvePipeline:
        with self._direct_lock:  # concurrent first RPCs must share one pipe
            if self._closed:
                # a Solve racing close() must not construct a fresh pipeline
                # AFTER close()'s snapshot — its dispatcher thread would
                # outlive the service with nothing left to stop it
                raise RuntimeError("solver service closed")
            pipe = self._pipelines.get(id(sched))
            if pipe is None:
                pipe = SolvePipeline(sched, registry=self.registry,
                                     max_slots=self.max_slots,
                                     max_wait_ms=self.max_wait_ms,
                                     knobs=self.knobs)
                self._pipelines[id(sched)] = pipe
            return pipe

    def drain(self) -> None:
        """Graceful-drain every pipeline (the serve SIGTERM handshake):
        new sessions are refused with the DRAINING hint, served deltas
        hand their chains to the shared spool, clients re-home to
        siblings — call :meth:`close` after the drain window to stop."""
        with self._direct_lock:
            pipes = list(self._pipelines.values())
        for pipe in pipes:
            pipe.drain()

    def statusz_extra(self) -> dict:
        """The serving layer's /statusz extension (ISSUE 15): this
        replica's identity plus the per-session block — chain epoch,
        last-delta age, lease owner, adopted-from — aggregated over every
        backend pipeline's session table.  Handed to
        :func:`obs.export.statusz` / ``serve(extra=...)`` so obs/ never
        imports service/."""
        out: dict = {"replica_id": self.tracer.replica,
                     "draining": False}
        sessions: dict = {}
        with self._direct_lock:
            pipes = list(self._pipelines.values())
        for pipe in pipes:
            out["draining"] = out["draining"] or pipe.draining()
            tab = pipe._delta_tab
            if tab is not None:
                sessions.update(tab.sessions_status())
        if sessions:
            out["sessions"] = sessions
        return out

    def sloz(self) -> dict:
        """The /sloz document provider (obs.export.serve(sloz=...)):
        the burn-rate evaluation plus the occupancy gauges and the
        sampler's coverage, so one page answers both 'are we meeting
        the objectives' and 'are we provisioned for them'."""
        doc = self.slo.evaluate()
        doc["occupancy"] = {
            "device_busy_share":
                self.registry.gauge(OCCUPANCY_DEVICE_BUSY).get(),
            "megabatch_slot_fill":
                self.registry.gauge(OCCUPANCY_SLOT_FILL).get(),
            "delta_inline_fraction":
                self.registry.gauge(OCCUPANCY_DELTA_INLINE).get(),
        }
        doc["sampler"] = {
            "enabled": bool(self.sampler),
            "interval_s": self.sampler.interval_s,
            "series": self.sampler.series_count(),
            "coverage_s": self.sampler.coverage(
                window_s=max(s for _, s in SLO_WINDOWS)),
        }
        return doc

    def tunez(self) -> dict:
        """The /tunez document provider (obs.export.serve(tunez=...)):
        the live knob table — value, default, lattice, freeze/override
        state — plus the controller's recent decision ring when the
        feedback loop is armed (KT_TUNE=1)."""
        if self.tuner is not None:
            return self.tuner.tunez()
        return {"enabled": False, "knobs": self.knobs.describe(),
                "decisions": []}

    def close(self) -> None:
        # latch closed + snapshot under the lock (a late first RPC racing
        # shutdown must neither resize the dict mid-iteration nor construct
        # a never-stopped pipeline after the snapshot), stop outside it —
        # stop() joins the dispatcher, and a join under _direct_lock would
        # deadlock against a dispatcher-path call that takes the lock
        with self._direct_lock:
            self._closed = True
            pipes = list(self._pipelines.values())
        for pipe in pipes:
            pipe.stop()
        self.sampler.stop()
        self.tracer.remove_sink(self._occupancy.on_trace)

    # ---- RPC methods -----------------------------------------------------
    @staticmethod
    def _deadline_of(request: pb.SolveRequest, context) -> Optional[float]:
        """The caller's remaining deadline budget, seconds: an explicit
        ``deadline_ms`` wins, else the propagated gRPC deadline
        (``context.time_remaining()``), else None — the admission policy's
        ``KT_DEFAULT_DEADLINE_MS`` applies.  ``getattr`` fallbacks keep an
        old-proto request (no new fields) decoding to 'no deadline'."""
        ms = float(getattr(request, "deadline_ms", 0.0) or 0.0)
        if ms > 0:
            return ms / 1000.0
        if context is not None:
            remaining = getattr(context, "time_remaining", None)
            if callable(remaining):
                rem = remaining()
                if rem is not None:
                    return max(0.0, float(rem))
        return None

    # ---- the door: gRPC's own (de)serialisation, timed --------------------
    def parse_request(self, data: bytes) -> pb.SolveRequest:
        """Solve's ``request_deserializer`` (``make_server``), as the
        ``request_parse`` phase.  gRPC runs it on the server's completion-
        queue thread, not on the pool thread that then runs the handler,
        so the timestamps cross to :meth:`Solve` by the request object's
        id (the object lives from here to the handler's return)."""
        self._door.arrive()
        with self.tracer.phase("request_parse") as ph:
            request = pb.SolveRequest.FromString(data)
        if ph is not NULL_PHASE:
            if len(self._parse_times) >= 1024:
                # only RPCs cancelled between parse and handler stay behind
                self._parse_times.clear()
            self._parse_times[id(request)] = (ph.t0, ph.t1)
        return request

    def serialize_response(self, resp: pb.SolveResponse) -> bytes:
        """Solve's ``response_serializer``, as the detached
        ``response_serialize`` phase: gRPC calls it on the handler's pool
        thread after the handler has returned and the trace has finished."""
        try:
            with self.tracer.phase("response_serialize",
                                   detached=True) as ph:
                data = resp.SerializeToString()
                ph.annotate(bytes=len(data))
        finally:
            self._door.leave()
        return data

    def Solve(self, request: pb.SolveRequest, context) -> pb.SolveResponse:
        # what gRPC's deserialiser timed for this request; None for a direct
        # caller's, which never came through the door
        parsed = self._parse_times.pop(id(request), None)
        if parsed is None:
            return self._solve(request, context, parsed)
        # through the door: in hand from here to response_serialize
        self._door.enter()
        try:
            return self._solve(request, context, parsed)
        except BaseException:
            # no response_serialize will end this request's stay
            self._door.leave()
            raise

    def _solve(self, request: pb.SolveRequest, context,
               parsed: Optional[tuple]) -> pb.SolveResponse:
        t_door = self.tracer.clock.now()
        # the door (docs/OBSERVABILITY.md): decoding the request runs
        # before the root span can open (the root adopts the wire trace
        # context, which is IN the request), so it is timed as a phase and
        # recorded into the tree once the root exists
        with self.tracer.phase("request_decode") as door:
            # the catalog by name, before anything of the request is
            # decoded: a sessionless request that left its instance types
            # out and names a list this sidecar kept is solved on that list
            sessionless = not getattr(request, "session_id", "")
            digest = getattr(request, "catalog_digest", "")
            catalogs = self.registry.counter(REQUEST_CATALOG)
            held = None
            if digest and sessionless and not request.instance_types:
                held = self.catalogs.get(digest)
                if held is None:
                    # a restart, an eviction, another replica: typed, and
                    # the client sends the list once more
                    catalogs.inc({"how": "unknown"})
                    msg = (f"CATALOG_UNKNOWN: no instance types held under "
                           f"catalog_digest {digest!r}; send them in full")
                    if context is None:
                        raise CatalogUnknown(msg)
                    context.abort(grpc.StatusCode.FAILED_PRECONDITION, msg)
            # one table of pod shapes per request, dropped with it
            shapes = codec.PodTemplates()
            kwargs = codec.decode_request(
                request, shapes, None if held is None else list(held))
            if held is None:
                # a list on the request wins over a digest beside it; this
                # sidecar names what it decoded and keeps it under the name
                digest = ""
                if sessionless and request.instance_types:
                    digest = codec.catalog_digest(request.instance_types)
                    self.catalogs.keep(digest, kwargs["instance_types"])
            catalog_how = "decoded" if held is None else "held"
            catalogs.inc({"how": catalog_how})
            decoded = self.registry.counter(REQUEST_DECODE_PODS)
            decoded.inc({"how": "templated"}, value=shapes.templated_pods)
            decoded.inc({"how": "plain"}, value=shapes.plain_pods)
            # gang audit at the door (ISSUE 20, docs/GANGS.md): a malformed
            # gang (members disagreeing on gang_size, oversubscribed
            # roster) refuses WHOLE with INVALID_ARGUMENT before admission
            # ever queues it — the gang is one ticket, so refusal is
            # all-or-nothing too.  A well-formed request stays one
            # admission unit either way: a shed sheds the whole request,
            # gangs included.
            try:
                gangmod.validate_batch(kwargs.get("pods", ()))
            except gangmod.GangValidationError as err:
                if context is None:
                    raise
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(err))
            sess = codec.decode_delta_fields(request)
            wire_trace, wire_parent = codec.decode_trace_fields(request)
        sched = self._scheduler_for(request.backend)
        pclass = parse_class(getattr(request, "priority_class", ""))
        deadline_s = self._deadline_of(request, context)
        # one trace per RPC, threaded through the pipeline's dispatch/
        # finalize boundary via the kwargs dict (the dispatcher records the
        # queue-wait "window" span on it; the scheduler opens tensorize/
        # dispatch/fence/reseat under it); "respond" covers the encode back
        # onto the wire.  A request carrying a wire trace context ADOPTS
        # the remote parent (start_remote): the hop keeps the ORIGIN's
        # trace id, so a request crossing replicas — establishment here,
        # deltas on a steal-adopting sibling, a forwarded foreign slot —
        # renders as ONE tree in /fleetz.
        # SLO accounting (obs/slo.py): every Solve lands in exactly one
        # outcome bucket for its class — 'ok' served, 'shed' a typed
        # admission/deadline refusal (the protection worked, the caller
        # still wasn't served), 'error' anything unexpected (including a
        # context.abort raised for non-SLO reasons) — recorded in the
        # finally so aborts (which raise) are counted too.
        slo_outcome = "error"
        slo_ms = None
        try:
            with self.tracer.start_remote(
                "solve", wire_trace, wire_parent,
                rpc="Solve", backend=sched.backend,
                n_pods=len(kwargs.get("pods", ())), priority_class=pclass,
                delta=bool(sess and sess["delta"]),
                **({"session_id": sess["session_id"]} if sess else {}),
                # gang-bearing batches record their admission-unit count
                # (each gang = ONE ticket): n_pods vs gang_units is the
                # trace-visible gang compression of the request
                **({"gang_units": gangmod.admission_units(
                        kwargs.get("pods", ()))}
                   if gangmod.gang_enabled()
                   and gangmod.has_gangs(kwargs.get("pods", ())) else {}),
            ) as trace:
                if parsed is not None:
                    trace.record("request_parse", *parsed)
                trace.record("request_decode", door.t0, door.t1,
                             n_pods=len(kwargs.get("pods", ())),
                             templates=shapes.templates,
                             templated_pods=shapes.templated_pods,
                             catalog=catalog_how)
                kwargs["trace"] = trace
                if self._pipelined:
                    pipe = self._pipeline_for(sched)
                    if sess is not None and pipe.delta_live():
                        # session-routed: the pipeline's delta fast path
                        # resolves with a DeltaReply (still one admission
                        # ticket in its class — sheds surface here exactly
                        # like classic solves)
                        kwargs["_delta"] = sess
                        result = pipe.solve(kwargs, pclass=pclass,
                                            deadline_s=deadline_s)
                    elif sess is not None and sess["delta"]:
                        # delta request against a delta-off server: there
                        # is no chain to apply it to — tell the client to
                        # fall back to full solves (KT_DELTA=0 contract:
                        # no session state, no behavior change otherwise)
                        result = DeltaReply(state="unknown", full=False)
                    else:
                        result = pipe.solve(kwargs, pclass=pclass,
                                            deadline_s=deadline_s)
                else:
                    if sess is not None and sess["delta"]:
                        # the direct debug path (KT_SOLVE_PIPELINE=0) has
                        # no dispatcher and therefore no session table
                        result = DeltaReply(state="unknown", full=False)
                    else:
                        with self._direct_lock:
                            result = sched.solve(
                                kwargs.pop("pods"),
                                kwargs.pop("provisioners"),
                                kwargs.pop("instance_types"), **kwargs,
                            )
                with trace.span("respond"):
                    if isinstance(result, DeltaReply):
                        resp = codec.encode_delta_reply(result)
                    else:
                        resp = codec.encode_response(result)
                        # this sidecar's name for the catalog it solved on:
                        # the client may send the name alone next time
                        resp.catalog_digest = digest
                    # which replica served: failover-aware clients stamp
                    # this on their "remote" span, and offline dump
                    # correlation keys on it
                    resp.replica_id = self.tracer.replica
            slo_outcome = "ok"
            # door to door on the tracer's clock: start of request_decode
            # to the close of the root.  (result.solve_ms is the host
            # clock around the device fence on the device tier — 15 ms of
            # a request the client waits 3.9 s for.)
            slo_ms = (self.tracer.clock.now() - t_door) * 1000.0
        except SolveDeadlineError as err:
            # shed BEFORE tensorize/dispatch: the wire contract is
            # DEADLINE_EXCEEDED for expired budgets, RESOURCE_EXHAUSTED for
            # everything else admission refused (client.py maps both back
            # to the typed errors — no silent retry into an overloaded
            # server).  Direct callers (context=None) get the typed raise.
            slo_outcome = "shed"
            if context is None:
                raise
            context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(err))
        except SolveShedError as err:
            slo_outcome = "shed"
            if context is None:
                raise
            context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(err))
        finally:
            self.slo.record(pclass, slo_outcome, solve_ms=slo_ms)
        return resp

    def Warm(self, request: pb.WarmRequest, context) -> pb.WarmResponse:
        """Forwarded warm_startup: the operator ships its live provisioners,
        catalog, and cluster snapshots; compiles run behind on the sidecar's
        chips (BatchScheduler.warm_startup semantics, including signature
        dedupe, so repeated Warm calls are cheap)."""
        kwargs = codec.decode_warm_request(request)
        sched = self._scheduler_for(request.backend)
        started = sched.warm_startup(
            kwargs.pop("provisioners"), kwargs.pop("instance_types"), **kwargs
        )
        return pb.WarmResponse(started=started)

    def Health(self, request: pb.HealthRequest, context) -> pb.HealthResponse:
        import jax

        return pb.HealthResponse(
            ok=True, backend=jax.default_backend(), devices=len(jax.devices())
        )


def make_server(
    service: Optional[SolverService] = None,
    port: int = 0,
    # enough RPC threads to fill a full megabatch: handlers just block on
    # the pipeline's futures (the dispatcher does the work), so idle-parked
    # threads are cheap — but 4 workers would cap the coalescer's reachable
    # occupancy at 4 no matter how many clients queue
    max_workers: int = MEGA_MAX_SLOTS + 4,
    host: str = "127.0.0.1",
) -> "tuple[grpc.Server, int]":
    """``host`` may also be a ``unix:`` address (``unix:/run/kt/solver.sock``)
    — the same-pod sidecar topology: a reconciler sharing the pod dials the
    socket instead of paying TCP loopback per RPC (the delta fast path's
    steady-state RPCs are sub-millisecond, so transport RTT is a visible
    fraction of them).  Unix binds return port 0; dial the address itself."""
    service = service or SolverService()
    handlers = {
        "Solve": grpc.unary_unary_rpc_method_handler(
            service.Solve,
            request_deserializer=service.parse_request,
            response_serializer=service.serialize_response,
        ),
        "Warm": grpc.unary_unary_rpc_method_handler(
            service.Warm,
            request_deserializer=pb.WarmRequest.FromString,
            response_serializer=pb.WarmResponse.SerializeToString,
        ),
        "Health": grpc.unary_unary_rpc_method_handler(
            service.Health,
            request_deserializer=pb.HealthRequest.FromString,
            response_serializer=pb.HealthResponse.SerializeToString,
        ),
    }
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[("grpc.max_receive_message_length", 256 * 1024 * 1024),
                 ("grpc.max_send_message_length", 256 * 1024 * 1024)],
    )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),)
    )
    if host.startswith("unix:"):
        server.add_insecure_port(host)
        bound = 0  # no TCP port; clients dial the unix address
    else:
        bound = server.add_insecure_port(f"{host}:{port}")
    server.start()
    return server, bound


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="karpenter-tpu-solver")
    parser.add_argument("--port", type=int, default=50151)
    # 0.0.0.0: the deployed topology dials this across pods
    # (deploy/operator.yaml -> Service karpenter-tpu-solver); loopback would
    # strand the operator on its local fallback forever
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--backend", default="auto", choices=["auto", "tpu", "oracle"])
    parser.add_argument("--obs-port", type=int, default=0,
                        help="observability HTTP port (/tracez, /statusz, "
                             "/metrics); 0 disables")
    parser.add_argument("--max-slots", type=int, default=None,
                        help="megabatch request slots per coalescer flush "
                             f"(default KT_MAX_SLOTS or {DEFAULT_MAX_SLOTS}; "
                             "1 disables cross-request batching)")
    parser.add_argument("--max-wait-ms", type=float, default=None,
                        help="max hold before a partial batch flushes "
                             f"(default KT_MAX_WAIT_MS or "
                             f"{DEFAULT_MAX_WAIT_MS:g}; 0 flushes the "
                             "moment the inbound queue idles)")
    parser.add_argument("--warmup", action="store_true",
                        help="block until the AOT bucket-grid precompile "
                             "lands (single-solve ladder + megabatch slot "
                             "rungs against the generated catalog) before "
                             "accepting traffic, and fail start-up if any "
                             "of those compiles failed; the persistent "
                             "compile cache (JAX_COMPILATION_CACHE_DIR) "
                             "skips even this across restarts")
    parser.add_argument("--small", action="store_true",
                        help="--warmup against the 20-type catalog")
    parser.add_argument("--admission", choices=["on", "off"], default=None,
                        help="admission control & overload protection "
                             "(docs/ADMISSION.md): bounded priority queue, "
                             "deadline shedding, circuit breaker, brownout "
                             "(default KT_ADMISSION, on)")
    parser.add_argument("--default-priority", default=None,
                        choices=["critical", "batch", "best_effort"],
                        help="priority class for requests that carry none "
                             "(KT_DEFAULT_PRIORITY_CLASS; default batch)")
    parser.add_argument("--default-deadline-ms", type=float, default=None,
                        help="enqueue deadline applied when the RPC "
                             "carries none (KT_DEFAULT_DEADLINE_MS; 0 = "
                             "no deadline)")
    parser.add_argument("--session-dir", default=None,
                        help="delta-session snapshot spool "
                             "(KT_SESSION_DIR): chains spool here on "
                             "graceful shutdown and every "
                             "KT_SESSION_SNAPSHOT_S seconds, and are "
                             "restored at startup so a restarted replica "
                             "serves surviving sessions warm "
                             "(docs/RESILIENCE.md); empty disables")
    args = parser.parse_args(argv)
    # admission knobs land in the env so every pipeline the service lazily
    # constructs (per backend) picks them up uniformly
    if args.admission is not None:
        os.environ["KT_ADMISSION"] = "1" if args.admission == "on" else "0"
    if args.default_priority is not None:
        os.environ["KT_DEFAULT_PRIORITY_CLASS"] = args.default_priority
    if args.default_deadline_ms is not None:
        os.environ["KT_DEFAULT_DEADLINE_MS"] = str(args.default_deadline_ms)
    if args.session_dir is not None:
        # env, not a ctor param: every pipeline the service lazily
        # constructs (per backend) picks the spool up uniformly
        os.environ["KT_SESSION_DIR"] = args.session_dir
    device = "device=unused"
    if args.backend in ("auto", "tpu"):
        # a device-backend sidecar serves from a TPU or not at all: with
        # `auto`, one that found no chip would answer from the host tiers
        # for ever without complaint (host-only runs: --backend oracle)
        import jax

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print(f"solver sidecar: --backend {args.backend} needs a TPU "
                  f"but jax found platform={dev.platform!r}; refusing to "
                  "serve", file=sys.stderr, flush=True)
            return 2
        device = (f"platform={dev.platform}, device_kind={dev.device_kind!r}, "
                  f"devices={len(jax.devices())}")
    # which host tier answers while a device program compiles behind: a
    # missing g++ or a failed build silently turns it into the Python
    # oracle (seconds at 50k pods) — say which one is live
    cold_tier = "native" if native_mod.available() else (
        f"oracle [native tier unavailable: {native_mod.load_error()}]")
    # said BEFORE the (minutes-long) warm-up, so whoever launched this can
    # tell at once what it is running on and where its compiles persist
    print(f"solver sidecar starting (pid={os.getpid()}, "
          f"backend={args.backend}, {device}, cold_tier={cold_tier}, "
          f"compile_cache={jit_cache_dir()} "
          f"entries={jit_cache_entries()})", flush=True)
    service = SolverService(BatchScheduler(backend=args.backend),
                            max_slots=args.max_slots,
                            max_wait_ms=args.max_wait_ms)
    if args.warmup:
        from ..models.catalog import generate_catalog
        from ..models.provisioner import Provisioner

        print("warmup: AOT bucket-grid precompile running "
              "(single ladder + megabatch rungs)...", flush=True)
        t_warm = time.perf_counter()
        # warm the slot cap this server will actually SERVE: a configured
        # --max-slots / KT_MAX_SLOTS above the default rung grid would
        # otherwise hit its first full flush cold and pay the megabatch
        # compile inline (KT014 pins this plumbing)
        cap = args.max_slots if args.max_slots is not None else int(
            os.environ.get("KT_MAX_SLOTS", str(DEFAULT_MAX_SLOTS)))
        cap = max(1, min(MEGA_MAX_SLOTS, cap))
        # the doubling ladder up to the cap, derived — not a literal that
        # rots the day MEGA_MAX_SLOTS moves (the KT014 drift class)
        grid, r = {cap}, 2
        while r < cap:
            grid.add(r)
            r *= 2
        try:
            n = service.scheduler.precompile_buckets(
                [Provisioner(name="default").with_defaults()],
                generate_catalog(full=not args.small),
                mega_slots=tuple(sorted(grid)),
                wait=True,
            )
        except WarmupFailed as err:
            # a shape whose compile failed would be served from the host
            # tiers indefinitely — that is not the sidecar --warmup promised
            print(f"warmup FAILED, not serving: {err}", file=sys.stderr,
                  flush=True)
            service.close()
            service.scheduler.stop_warms()
            return 1
        print(f"warmup: {n} bucket programs compiled in "
              f"{time.perf_counter() - t_warm:.1f}s; serving", flush=True)
    server, port = make_server(service, port=args.port, host=args.host)
    # admission rides the pipeline: with KT_SOLVE_PIPELINE=0 it is inert,
    # and the startup line must not claim otherwise
    admission_live = admission_enabled() and service._pipelined
    delta_live = delta_enabled() and service._pipelined
    print(f"solver sidecar listening on {args.host}:{port} "
          f"(backend={args.backend}, {device}, cold_tier={cold_tier}, "
          f"admission={'on' if admission_live else 'off'}, delta="
          f"{'on' if delta_live else 'off'})", flush=True)
    if args.obs_port:
        from ..obs import default_flight
        from ..obs.export import serve as obs_serve

        flight = service.tracer.flight or default_flight()
        # a unix: gRPC address is not a TCP hostname — the obs HTTP
        # server stays on loopback in the same-pod sidecar topology
        obs_host = ("127.0.0.1" if args.host.startswith("unix:")
                    else args.host)
        # the session block rides /statusz and KT_OBS_PEERS arms the
        # /fleetz fan-out (docs/OBSERVABILITY.md fleet tracing)
        _obs_server, obs_port = obs_serve(
            service.registry, flight, port=args.obs_port, host=obs_host,
            extra=service.statusz_extra, sloz=service.sloz,
            tunez=service.tunez)
        print(f"observability on http://{obs_host}:{obs_port}/tracez "
              f"(+/statusz /sloz /tunez /fleetz /metrics)")
    # graceful shutdown (ISSUE 12/13, docs/RESILIENCE.md): SIGTERM — the
    # kubelet's pod-termination signal, reinforced by deploy/solver.yaml's
    # preStop sleep — first enters the DRAIN handshake: new sessions are
    # refused with a session_state="draining" hint, every served delta
    # hands its chain to the KT_SESSION_DIR spool (lease released) on the
    # same reply, and clients proactively re-home to sibling replicas.
    # After KT_DRAIN_GRACE_S (or a second signal) the service stops, which
    # spools any remaining chains and releases their leases — whichever
    # replica each client lands on serves its next delta WARM.
    stop_ev = threading.Event()
    drain_ev = threading.Event()
    drain_grace = float(os.environ.get("KT_DRAIN_GRACE_S", "2"))

    def _graceful(signum, _frame):
        if not drain_ev.is_set():
            print(f"signal {signum}: draining — new sessions refused, "
                  f"chains handed to the session spool; exiting in "
                  f"{drain_grace:g}s (signal again to exit now)",
                  flush=True)
            drain_ev.set()
        else:
            stop_ev.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    try:
        while not drain_ev.wait(timeout=3600):
            pass
        service.drain()
        stop_ev.wait(timeout=drain_grace)
    except KeyboardInterrupt:
        pass
    print("drain window closed: snapshotting remaining delta sessions",
          flush=True)
    server.stop(grace=2.0)
    service.close()
    for sched in service._schedulers.values():
        sched.stop_warms()
    print("solver sidecar stopped", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
