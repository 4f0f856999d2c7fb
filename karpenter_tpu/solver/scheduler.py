"""Batch scheduler facade — routes pods to the TPU solver or the CPU oracle.

The provisioning and deprovisioning controllers call this, never the solvers
directly (the ``scheduling.Solve`` boundary, SURVEY.md §3.2 step 3).  Pods the
TPU path can't express (positive pod-affinity, v1 — see solver/tpu.py
docstring) are carved out and solved by the oracle against the TPU result's
node set, so one SolveResult comes back either way.
"""

from __future__ import annotations

import copy
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Set, Tuple

import logging

from ..metrics import (
    MEGABATCH_FLUSH,
    MEGABATCH_FLUSH_REASONS,
    MEGABATCH_SLOTS,
    MULTIHOST_FENCE_BYTES,
    MULTIHOST_FENCE_SCOPES,
    MULTIHOST_SLOT_OWNERSHIP,
    MULTIHOST_SLOTS,
    MULTIHOST_UNIFIED,
    PRECOMPILE_DURATION,
    SCHEDULING_DURATION,
    SOLVER_BACKEND_DURATION,
    SOLVER_COLD_FALLBACKS,
    SOLVER_COMPILE_DURATION,
    SOLVER_COMPILE_IN_PROGRESS,
    SOLVER_DEGRADED_SOLVES,
    SOLVER_DEVICE_HANGS,
    SOLVER_DEVICE_HEALTHY,
    INFLIGHT_DEPTH,
    TENSORIZE_CACHE_HITS,
    TENSORIZE_CACHE_MISSES,
    TENSORIZE_DURATION,
    Registry,
    registry as default_registry,
)
from ..models import labels as L
from ..models.instancetype import InstanceType
from ..models.pod import LabelSelector, PodSpec
from ..models.provisioner import Provisioner
from ..models.tensorize import (
    TensorizeCache,
    batch_needs_oracle,
    device_inexpressible,
    tensorize,
)
from ..obs import tracer_for
from ..obs.trace import NULL_TRACE, Tracer
from .guard import DeviceGuard, DeviceHang
from .reference import solve as oracle_solve
from .tpu import (
    MEGA_MAX_SLOTS,
    SlotsExhausted,
    TpuSolver,
    _mesh_size,
    mega_key_at_slots,
    mega_key_dims,
    mesh_shardable,
    unify_mega_keys,
)
from .types import SimNode, SolveResult

logger = logging.getLogger(__name__)


#: "auto" routes batches below this pod count (with no topology constraints)
#: to the native C++ tier; larger or constrained batches go to the device.
NATIVE_BATCH_LIMIT = 256
#: relaxation-ladder depth cap: at most this many retry waves per solve; a
#: pod with more preferences has its top rungs collapsed (several dropped at
#: once) instead of funding one solve per preference
MAX_RELAXATION_WAVES = 8
#: residue-convergence depth: still-infeasible pods re-solve against the
#: accumulated placed state until nothing more places (or this many waves).
#: This is the batched solver's equivalent of the sequential oracle's
#: invalidate-and-retry: the oracle co-packs multi-group residuals onto tail
#: nodes and cascades through limit-capped provisioners one placement at a
#: time; each wave here gives the device solve the same second look at open
#: rows and remaining limit headroom (karpenter.sh_provisioners.yaml:160-173
#: limits + :305-314 weights).
MAX_RESIDUE_WAVES = 6


def _delta_local_enabled() -> bool:
    """Meshed delta steps route through the host-local single-shard
    program by default (ISSUE 14: the sub-ms displaced-subproblem solves
    must not pay sharded dispatch + mesh fence); ``KT_DELTA_LOCAL=0``
    keeps them on the scheduler's mesh."""
    return os.environ.get("KT_DELTA_LOCAL", "1") != "0"


def _soft_spreads(pod: PodSpec):
    return [t for t in pod.topology_spread if not t.hard]


def _n_preferences(pod: PodSpec) -> int:
    """Relaxable preferences: preferred node-affinity terms + ScheduleAnyway
    topology spreads (both sit on the same relaxation ladder, like core's
    Preferences — scheduling.md:205-233 + :303-346 ScheduleAnyway)."""
    return len(pod.preferred_affinity_terms) + len(_soft_spreads(pod))


def _harden_preferences(pod: PodSpec, keep: Optional[int] = None) -> PodSpec:
    """Fold the first ``keep`` preferences (all when None) into the hard
    constraint set: preferred affinity terms join the required set,
    ScheduleAnyway spreads become DoNotSchedule.  The ladder drops soft
    spreads first (they sort after affinity terms), then affinity terms
    last-first.  Returns the pod unchanged when it has no preferences."""
    if not pod.preferred_affinity_terms and (
        not pod.topology_spread or all(t.hard for t in pod.topology_spread)
    ):
        return pod  # no preferences (the hot path at scale)

    from ..models.pod import TopologySpreadConstraint

    prefs_aff = pod.preferred_affinity_terms
    soft = _soft_spreads(pod)
    total = len(prefs_aff) + len(soft)
    k = total if keep is None else max(0, keep)
    kept_aff = prefs_aff[: min(k, len(prefs_aff))]
    kept_soft = soft[: max(0, k - len(prefs_aff))]

    out = copy.copy(pod)
    if kept_aff:
        out.required_affinity_terms = [
            list(term) + [r for pt in kept_aff for r in pt]
            for term in (pod.required_affinity_terms or [[]])
        ]
    out.preferred_affinity_terms = []
    out.topology_spread = [t for t in pod.topology_spread if t.hard] + [
        TopologySpreadConstraint(t.max_skew, t.topology_key, "DoNotSchedule",
                                 t.label_selector)
        for t in kept_soft
    ]
    out.__dict__.pop("_group_key", None)  # hardened copy needs its own key
    return out


def _adopt_placed(prev_existing: List[SimNode], sub: SolveResult):
    """Split a wave's placed snapshots back into (existing, prior+new nodes).

    ``sub`` solved against ``prev_existing + <prior new nodes>`` in that
    order and returned its placed copies in ``sub.existing_nodes``; the
    copies replace the prior references so the next wave sees every
    placement so far — capacity bookkeeping chains across waves without
    mutating the caller's node objects.  The ONLY place this split-index
    logic lives; both _merge and _solve_tpu's staging use it."""
    ne = len(prev_existing)
    placed = list(sub.existing_nodes)
    return placed[:ne], placed[ne:] + list(sub.nodes)


def _merge(result: SolveResult, sub: SolveResult) -> None:
    """Fold a retry wave's outcome into ``result`` (shared by the preference
    ladder and the OR-term ladder so their merge semantics cannot diverge)."""
    for name in list(result.infeasible):
        if name in sub.assignments:
            del result.infeasible[name]
    result.infeasible.update(sub.infeasible)
    result.assignments.update(sub.assignments)
    result.existing_nodes, result.nodes = _adopt_placed(result.existing_nodes, sub)
    result.solve_ms += sub.solve_ms
    result.tensorize_ms += sub.tensorize_ms
    result.served_cold = result.served_cold or sub.served_cold


class _PendingWave:
    """A dispatched-but-unfenced first solver wave; ``finish()`` fences the
    device, handles the fallback ladders, and returns the wave's
    SolveResult.  Internal to the scheduler's submit/solve split."""

    __slots__ = ("finish",)

    def __init__(self, finish) -> None:
        self.finish = finish


class PendingScheduleResult:
    """Handle returned by :meth:`BatchScheduler.submit`; ``result()`` blocks
    on the device fence (one RTT) plus any retry epilogues and is
    idempotent."""

    __slots__ = ("_finish", "_result")

    def __init__(self, finish) -> None:
        self._finish = finish
        self._result: Optional[SolveResult] = None

    def result(self) -> SolveResult:
        if self._result is None:
            self._result = self._finish()
        return self._result


def _budget_left(result: SolveResult, max_new_nodes: Optional[int]) -> Optional[int]:
    return (None if max_new_nodes is None
            else max(0, max_new_nodes - len(result.nodes)))


class _MegaSlot:
    """One request's slot in a pending megabatch dispatch: ``result()`` is
    valid after the owning collector's ``dispatch()`` ran; it fences lazily
    (first resolver fences the whole group — the overlap window between
    megabatch N's dispatch and its fence belongs to the pipeline) and
    re-raises the slot's own exception (SlotsExhausted / DeviceHang) so the
    per-request fallback ladder in ``_solve_tpu`` stays identical to the
    single path."""

    __slots__ = ("_collector", "_idx")

    def __init__(self, collector: "_MegaCollector", idx: int) -> None:
        self._collector = collector
        self._idx = idx

    def result(self):
        return self._collector.resolve(self._idx)


class _MegaCollector:
    """Deferred cross-request device dispatch (``BatchScheduler.submit_many``).

    During the registration phase ``_solve_tpu`` routes each request's first
    device wave here instead of dispatching it; ``dispatch()`` then enqueues
    ONE vmapped device program per shape bucket (``solve_many_async``) —
    or, while a slot-rung program is still compiling behind, per-request
    async dispatches on the already-compiled single program (warming the
    rung).  Nothing fences at dispatch: the first ``resolve()`` of a group
    pays its single batch-wide fence, so the pipeline coalesces and
    tensorizes megabatch N+1 while N executes on the device.
    Single-threaded: registration, dispatch, and resolution all happen on
    the pipeline's dispatcher thread (the submit_many contract)."""

    def __init__(self, solver: TpuSolver, guard=None, registry=None,
                 warm=None, mesh=None, on_mesh_serial=None,
                 flush_reason: Optional[str] = None) -> None:
        self.solver = solver
        self.guard = guard
        self.registry = registry
        self.warm = warm
        #: the owning scheduler's device mesh: flushes dispatch the SHARDED
        #: megabatch program (slot axis over the flattened mesh) and the
        #: serial fallback dispatches the sharded single-solve program
        self.mesh = mesh
        #: scheduler hook counting/logging a meshed flush that degraded to
        #: serial dispatches (MEGABATCH_FLUSH{reason="mesh_serial"})
        self.on_mesh_serial = on_mesh_serial
        #: the pipeline's coalescer reason for this flush, or None for
        #: direct submit_many callers.  When set, the collector owns the
        #: flush count and incs exactly ONE reason at dispatch —
        #: "mesh_serial" if the meshed flush degraded to serial, else this
        #: reason — so the counter's labels stay a partition of flushes
        #: (counting upfront at the pipeline AND again on degradation
        #: would double-count every degraded meshed flush)
        self.flush_reason = flush_reason
        self._degraded = False
        self.entries: List[dict] = []
        #: per-slot resolver state after dispatch():
        #: ("mega", PendingMegaSolve, pos) | ("single", PendingTpuSolve)
        #: | ("err", Exception)
        self._slots: List[tuple] = []

    def add(self, **entry) -> _MegaSlot:
        self.entries.append(entry)
        return _MegaSlot(self, len(self.entries) - 1)

    def _observe_slots(self, occupied: int) -> None:
        if self.registry is not None:
            self.registry.histogram(MEGABATCH_SLOTS).observe(occupied)

    def _guarded(self, fn):
        return self.guard.run(fn) if self.guard else fn()

    def _mesh_serial(self, detail: str) -> None:
        if self.mesh is None:
            return
        first_degrade = not self._degraded
        self._degraded = True
        if self.on_mesh_serial is not None:
            # the counter is in FLUSH units: pipeline-owned flushes count
            # at end of dispatch() instead, and a direct caller's flush
            # counts on its FIRST degraded group only (a flush spanning
            # two cold buckets is still one degraded flush)
            self.on_mesh_serial(
                detail,
                count=self.flush_reason is None and first_degrade)

    def dispatch(self) -> None:
        self._slots = [None] * len(self.entries)
        sigs: List[tuple] = []
        groups: Dict[tuple, List[int]] = {}
        for i, e in enumerate(self.entries):
            key = self.solver.mega_signature(
                e["st"], existing_nodes=e["existing_nodes"],
                max_nodes=e["max_nodes"], slots=1, mesh=self.mesh,
            )
            sigs.append(key)
            groups.setdefault(key, []).append(i)
        # host-aware mixed-bucket unification (ISSUE 14): merge shape
        # buckets whose dims UNIFY (one dominates component-wise —
        # solver/tpu.unify_mega_keys) so the whole flush shares ONE mesh
        # dispatch at the dominant bucket's program instead of serial
        # per-bucket dispatches; dominated requests pad up via
        # target_dims, byte-identical to their own-bucket solves
        merged: List[list] = []  # [unified_key, idxs, n_source_buckets]
        for key, idxs in groups.items():
            for m in merged:
                u = unify_mega_keys(m[0], key)
                if u is not None:
                    m[0] = u
                    m[1].extend(idxs)
                    m[2] += 1
                    break
            else:
                merged.append([key, list(idxs), 1])
        for ukey, idxs, n_src in merged:
            idxs.sort()  # slot order == arrival order, like the old path
            unified = n_src > 1
            use_mega = len(idxs) > 1 and mesh_shardable(self.mesh)
            if len(idxs) > 1 and not mesh_shardable(self.mesh):
                # device count past the slot-rung ladder: this mesh cannot
                # pad a batch to one-slot-per-chip (bucket_key already
                # rejects these; direct submit_many callers land here)
                self._mesh_serial(
                    f"{_mesh_size(self.mesh)}-device mesh exceeds the "
                    f"{MEGA_MAX_SLOTS}-slot rung ladder")
            if use_mega:
                mega_sig = mega_key_at_slots(ukey, len(idxs), self.mesh)
                if not self.solver.ready(mega_sig):
                    # callers must never eat a cold compile (the compile-
                    # behind contract): serve this flush from the compiled
                    # single program, compile the slot-rung program behind.
                    # Warm from an entry OF the dominant bucket, so the
                    # compiled program is the one a unified flush runs.
                    if self.warm is not None:
                        warm_i = next(
                            (i for i in idxs if sigs[i] == ukey), idxs[0])
                        self.warm(self.entries[warm_i], len(idxs))
                    use_mega = False
                    self._mesh_serial("sharded slot-rung program still "
                                      "compiling behind")
            if use_mega:
                if unified and self.registry is not None:
                    self.registry.counter(MULTIHOST_UNIFIED).inc()
                reqs = [
                    dict(
                        st=self.entries[i]["st"],
                        existing_nodes=self.entries[i]["existing_nodes"],
                        max_nodes=self.entries[i]["max_nodes"],
                        raise_on_exhaust=self.entries[i]["raise_on_exhaust"],
                        trace=self.entries[i]["trace"],
                    )
                    for i in idxs
                ]
                target = mega_key_dims(ukey) if unified else None
                try:
                    handle = self._guarded(
                        lambda reqs=reqs, target=target:
                        self.solver.solve_many_async(
                            reqs, mesh=self.mesh, target_dims=target,
                            registry=self.registry))
                except DeviceHang as err:
                    # hang at H2D dispatch: fan to every slot — each
                    # request's _finish_mega degrades to the warm tier
                    for i in idxs:
                        self._slots[i] = ("err", err)
                    continue
                # ktlint: allow[KT005] megabatch CONSTRUCTION failures
                # (bucket mismatch after a raced warm-state flip, stacking
                # errors) degrade the flush to the proven serial path —
                # clients must never fail on an optimization-layer error
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "megabatch dispatch failed; serving the flush "
                        "serially", exc_info=True)
                    self._mesh_serial("megabatch construction failed; "
                                      "flush degraded")
                    self._dispatch_serial(idxs)
                    continue
                self._observe_slots(len(idxs))
                for pos, i in enumerate(idxs):
                    self._slots[i] = ("mega", handle, pos)
            else:
                self._dispatch_serial(idxs)
        if self.flush_reason is not None and self.registry is not None:
            # pipeline-owned flush count: exactly one reason per flush
            reason = "mesh_serial" if self._degraded else self.flush_reason
            self.registry.counter(MEGABATCH_FLUSH).inc({"reason": reason})

    def _dispatch_serial(self, idxs: List[int]) -> None:
        """Per-request async dispatches on the single-solve program (the
        SHARDED single program for a meshed collector): still one enqueue
        per request before any fence (the cold-rung and degraded-flush
        path)."""
        for i in idxs:
            e = self.entries[i]
            self._observe_slots(1)
            try:
                pending = self._guarded(
                    lambda e=e: self.solver.solve_async(
                        e["st"], existing_nodes=e["existing_nodes"],
                        max_nodes=e["max_nodes"], mesh=self.mesh,
                        raise_on_exhaust=e["raise_on_exhaust"],
                        trace=e["trace"],
                    ))
            # ktlint: allow[KT005] boxed per-slot outcome, re-raised
            # by the request's own _MegaSlot.result()
            except BaseException as err:  # noqa: BLE001
                self._slots[i] = ("err", err)
                continue
            self._slots[i] = ("single", pending)

    def resolve(self, idx: int):
        """Fence-and-extract slot ``idx`` (first resolver of a mega group
        fences the whole group; later ones hit the cached outputs)."""
        state = self._slots[idx]
        assert state is not None, "megabatch slot read before dispatch()"
        if state[0] == "err":
            raise state[1]
        if state[0] == "single":
            return self._guarded(state[1].result)
        _kind, handle, pos = state
        outs = self._guarded(handle.results)
        out = outs[pos]
        if isinstance(out, BaseException):
            raise out
        return out


class WarmupFailed(RuntimeError):
    """A blocking precompile (``precompile_buckets(wait=True)``) ended with
    a compile that raised, or one still running past the wait budget.  The
    shapes it names would be served from the host tiers — the opposite of
    what the caller blocked for — so the caller decides (``serve --warmup``
    refuses to start)."""


class BatchScheduler:
    def __init__(
        self,
        backend: str = "auto",  # "auto" | "tpu" | "native" | "oracle"
        registry: Optional[Registry] = None,
        mesh=None,
        native_batch_limit: int = NATIVE_BATCH_LIMIT,
        compile_behind: bool = True,
        tracer: Optional[Tracer] = None,
    ) -> None:
        assert backend in ("auto", "tpu", "native", "oracle")
        self.backend = backend
        self.registry = registry or default_registry
        # per-solve span tracing + anomaly dumps (obs/): callers pass a
        # Trace per solve via the `trace` kwarg; the tracer itself is held
        # for its flight recorder (hang/degraded anomaly hooks)
        self.tracer = tracer if tracer is not None else tracer_for(self.registry)
        self.mesh = mesh
        self.native_batch_limit = native_batch_limit
        self.compile_behind = compile_behind
        self._tpu = TpuSolver(registry=self.registry)
        # change-gated stall logging; _start_warm runs at fence time, and
        # WHICH thread fences depends on the caller (pipeline dispatcher vs
        # direct RPC threads under KT_SOLVE_PIPELINE=0) — a cheap lock makes
        # the invariant local instead of inherited from caller threading
        self._cold_lock = threading.Lock()
        self._cold_logged: Set[tuple] = set()  # guarded-by: _cold_lock
        # every background compile that raised, oldest first — what a
        # blocking precompile reports instead of "N accepted"
        self._warm_errors: List[str] = []      # guarded-by: _cold_lock
        # incremental host tensorize: group-level tensors built once per
        # batch shape, reused across solves (models/tensorize.TensorizeCache)
        self._tensorize_cache = TensorizeCache()
        # hang protection for the auto policy's device dispatches (a PJRT
        # call that never returns must degrade the reconcile loop to the
        # warm host tiers, not freeze it — see solver/guard.py); forced
        # backends keep direct calls so tests and inline-compile flows are
        # untouched
        self._guard = DeviceGuard(on_health_change=self._device_health_changed)
        self.registry.gauge(SOLVER_DEVICE_HEALTHY).set(1)
        # zero-init so every label series exists from the first scrape (a
        # counter first appearing at its first increment loses that
        # increment to Prometheus rate()/increase()); inc(0) creates the
        # sample, merely constructing the Counter does not.  Both fallback
        # counters carry a backend label with BOTH reachable values —
        # _cold_solve returns "native" or "oracle" depending on tier
        # availability and batch topology.
        self.registry.counter(SOLVER_DEVICE_HANGS).inc(value=0.0)
        for fallback_backend in ("native", "oracle"):
            self.registry.counter(SOLVER_DEGRADED_SOLVES).inc(
                {"backend": fallback_backend}, value=0.0
            )
            self.registry.counter(SOLVER_COLD_FALLBACKS).inc(
                {"backend": fallback_backend}, value=0.0
            )
        for tier in ("identity", "shape"):
            self.registry.counter(TENSORIZE_CACHE_HITS).inc(
                {"tier": tier}, value=0.0
            )
        self.registry.counter(TENSORIZE_CACHE_MISSES).inc(value=0.0)
        # 0 in flight until a SolvePipeline drives submit(); the series must
        # exist from process start like every other solver series — but only
        # when absent: re-constructing a scheduler (per-backend lazily, or
        # in tests) must not clobber a live pipeline's depth
        inflight = self.registry.gauge(INFLIGHT_DEPTH)
        if not inflight.has({"backend": self.backend}):
            inflight.set(0, {"backend": self.backend})
        # megabatch collector: non-None only INSIDE submit_many's
        # registration phase, on the pipeline dispatcher thread — _solve_tpu
        # routes first device waves through it instead of dispatching
        self._mega_collect: Optional[_MegaCollector] = None
        # register the megabatch/precompile families so the documented
        # metrics are visible before the first megabatch lands; every flush
        # reason exists at 0 from construction (KT003 — the pipeline
        # re-zero-inits too, for facade schedulers without this init)
        self.registry.histogram(MEGABATCH_SLOTS)
        self.registry.histogram(PRECOMPILE_DURATION)
        for reason in MEGABATCH_FLUSH_REASONS:
            self.registry.counter(MEGABATCH_FLUSH).inc(
                {"reason": reason}, value=0.0)
        # multi-host serving families (ISSUE 14): per-host fence byte
        # accounting, slot-ownership demux counts, unified-flush counts —
        # all exist at 0 from construction (KT003)
        fence_c = self.registry.counter(MULTIHOST_FENCE_BYTES)
        for scope in MULTIHOST_FENCE_SCOPES:
            fence_c.inc({"scope": scope}, value=0.0)
        slots_c = self.registry.counter(MULTIHOST_SLOTS)
        for ownership in MULTIHOST_SLOT_OWNERSHIP:
            slots_c.inc({"ownership": ownership}, value=0.0)
        self.registry.counter(MULTIHOST_UNIFIED).inc(value=0.0)
        # a meshed scheduler degrading a would-be sharded megabatch to
        # serial dispatches logs once per process (the metric carries the
        # ongoing count; the log explains the first occurrence)
        self._mesh_serial_logged = False  # guarded-by: _cold_lock
        #: the unshardable-mesh verdict, hoisted to construction (ISSUE 14
        #: satellite): a mesh whose device count exceeds the slot-rung
        #: ladder can never serve a sharded megabatch, so per-request
        #: probes (bucket_key) return None immediately instead of walking
        #: the log-once path per queued request — the verdict is logged
        #: ONCE, here, where it is decided
        self.mega_unshardable = (
            mesh is not None and not mesh_shardable(mesh))
        if self.mega_unshardable and backend in ("auto", "tpu"):
            logger.info(
                "mesh of %d devices exceeds the %d-slot rung ladder: "
                "megabatching is off for this scheduler; flushes serve "
                "serially and count under karpenter_solver_megabatch_"
                "flush_total{reason=\"mesh_serial\"}",
                _mesh_size(mesh), MEGA_MAX_SLOTS)
        # warm-start delta series exist before the first solve_delta call
        from .warmstart import zero_init_metrics as _ws_zero_init

        _ws_zero_init(self.registry)
        # relax-rung series exist before the first device solve (KT003)
        from .relax import zero_init_metrics as _rx_zero_init

        _rx_zero_init(self.registry)
        # hierarchical-routing series exist before the first 100k+ batch
        from .hierarchy import zero_init_hier_metrics as _hier_zero_init

        _hier_zero_init(self.registry)
        # gang outcome series exist before the first ganged batch (KT003)
        from ..gang import zero_init_gang_metrics as _gang_zero_init

        _gang_zero_init(self.registry)
        # hierarchical re-entrancy depth: repair solves issued from inside
        # solve_hierarchical must never route hierarchically themselves
        self._hier_depth = 0

    def _device_health_changed(self, healthy: bool) -> None:
        self.registry.gauge(SOLVER_DEVICE_HEALTHY).set(1 if healthy else 0)
        if not healthy:
            self.registry.counter(SOLVER_DEVICE_HANGS).inc()

    def solve(
        self,
        pods: Sequence[PodSpec],
        provisioners: Sequence[Provisioner],
        instance_types: Sequence[InstanceType],
        *,
        existing_nodes: Sequence[SimNode] = (),
        daemonsets: Sequence[PodSpec] = (),
        unavailable: Optional[Set[tuple]] = None,
        allow_new_nodes: bool = True,
        max_new_nodes: Optional[int] = None,
        trace=None,
        relax: Optional[bool] = None,
    ) -> SolveResult:
        """Solve with preference relaxation: pods carrying preferences
        (preferred affinity terms, ScheduleAnyway topology spreads) are first
        solved with all preferences hardened; any that come back infeasible
        retry dropping one preference at a time, last first (the reference's
        scheduler relaxes preferences one failure at a time —
        scheduling.md:205-233).  Pods with OR'd required-affinity terms
        that stay infeasible under term[0] retry under each alternate term —
        with the full preference ladder re-applied per term, so a pod landing
        on term[1] still honors its satisfiable preferences."""
        return self._submit(
            pods, provisioners, instance_types,
            existing_nodes=existing_nodes, daemonsets=daemonsets,
            unavailable=unavailable, allow_new_nodes=allow_new_nodes,
            max_new_nodes=max_new_nodes, trace=trace, relax=relax,
            # a synchronous caller fences immediately — async dispatch buys
            # no overlap and would just split the device call across two
            # code paths; keep solve() on the classic sync path
            dispatch=False,
        ).result()

    def submit(
        self,
        pods: Sequence[PodSpec],
        provisioners: Sequence[Provisioner],
        instance_types: Sequence[InstanceType],
        *,
        existing_nodes: Sequence[SimNode] = (),
        daemonsets: Sequence[PodSpec] = (),
        unavailable: Optional[Set[tuple]] = None,
        allow_new_nodes: bool = True,
        max_new_nodes: Optional[int] = None,
        trace=None,
        relax: Optional[bool] = None,
    ) -> "PendingScheduleResult":
        """Async entry point for pipelined callers (service/server.py
        SolvePipeline): tensorizes and DISPATCHES the first solver wave to
        the device, then returns a handle whose ``result()`` fences and runs
        the (usually zero-iteration) relaxation/residue epilogues.  Between
        ``submit`` and ``result`` the host is free — the pipeline tensorizes
        batch N+1 there while batch N executes on the device.  Same
        result semantics as :meth:`solve`.
        Not re-entrant: submits and results must come from one thread, and
        results must be taken in submit order (FIFO) — the solver waves
        chain per-call state only, so interleaved independent batches are
        safe, concurrent ones are not."""
        return self._submit(
            pods, provisioners, instance_types,
            existing_nodes=existing_nodes, daemonsets=daemonsets,
            unavailable=unavailable, allow_new_nodes=allow_new_nodes,
            max_new_nodes=max_new_nodes, trace=trace, relax=relax,
            dispatch=True,
        )

    def solve_delta(
        self,
        prev: SolveResult,
        added: Sequence[PodSpec] = (),
        removed: Sequence[str] = (),
        iced: Sequence[object] = (),
        *,
        provisioners: Sequence[Provisioner],
        instance_types: Sequence[InstanceType],
        daemonsets: Sequence[PodSpec] = (),
        unavailable: Optional[Set[tuple]] = None,
        max_delta_frac: Optional[float] = None,
        force_full: bool = False,
        trace=None,
    ):
        """Warm-start delta solve through the full scheduler ladder (see
        solver/warmstart.py): removals and unconstrained adds are host
        bookkeeping; displaced pods that need a real solve go through
        :meth:`solve` seeded with the surviving placements — preference
        relaxation, oracle carve-outs, residue waves and the auto-policy
        routing all apply to the subproblem exactly as they would to a
        fresh batch.  Falls back to a full :meth:`solve` of the whole pod
        set when the perturbation exceeds ``KT_DELTA_MAX_FRAC`` or a
        coupling guard trips.  Consumes ``prev``; returns a
        ``DeltaOutcome``."""
        from . import warmstart
        from .relax import relax_delta_enabled

        # the relax rung is a $-for-latency trade the sub-ms delta path
        # must not pay: displaced-subproblem scans always skip it, and the
        # FULL-solve boundaries (threshold/guard fallbacks — already
        # paying a whole re-solve) run it only when KT_RELAX_DELTA=1.
        # A MESHED scheduler's displaced subproblems route through the
        # host-local single-shard program (ISSUE 14, KT_DELTA_LOCAL):
        # these are sub-ms steps that fit one chip — the sharded program
        # would pay cross-host dispatch and a mesh-wide fence per step,
        # which is exactly the transfer tax the delta path exists to
        # avoid.  The FULL-solve fallbacks keep the mesh: a whole-cluster
        # re-solve is the workload the sharded program is built for.
        use_local = self.mesh is not None and _delta_local_enabled()

        def _solve(pods, existing, unavail, relax=False):
            if use_local:
                with self._host_local():
                    return self.solve(
                        pods, provisioners, instance_types,
                        existing_nodes=existing, daemonsets=daemonsets,
                        unavailable=unavail or None, trace=trace,
                        relax=relax,
                    )
            return self.solve(
                pods, provisioners, instance_types,
                existing_nodes=existing, daemonsets=daemonsets,
                unavailable=unavail or None, trace=trace, relax=relax,
            )

        def _solve_full(pods, existing, unavail):
            return self.solve(
                pods, provisioners, instance_types,
                existing_nodes=existing, daemonsets=daemonsets,
                unavailable=unavail or None, trace=trace,
                relax=None if relax_delta_enabled() else False,
            )

        # gang composition (ISSUE 20, docs/GANGS.md): a member removal
        # retracts the WHOLE gang — seated comembers join the removal set
        # and surface as unplaced with the typed GangUnplaced reason
        from .. import gang as gangmod

        gang_retracted: Dict[str, str] = {}
        if gangmod.gang_enabled() and removed:
            removed, gang_retracted = gangmod.expand_gang_removals(
                prev, removed)

        out = warmstart.delta_solve(
            prev, added, removed, iced,
            solve_displaced=_solve, solve_full=_solve_full,
            max_delta_frac=max_delta_frac, registry=self.registry,
            unavailable=unavailable, force_full=force_full,
        )
        # a gang add places atomically or falls back to the FULL solve:
        # when the incremental tier left an added gang (wholly, post-
        # epilogue) unplaced, re-solve everything from the stripped base —
        # one more chance before the typed verdict stands.  The warm-start
        # retention dict re-offers the failed members to the full solve.
        if (gangmod.gang_enabled() and out.mode != "full" and added
                and gangmod.delta_needs_full(out.result, added)):
            out = warmstart.delta_solve(
                out.result, (), (), (),
                solve_displaced=_solve, solve_full=_solve_full,
                max_delta_frac=max_delta_frac, registry=self.registry,
                unavailable=unavailable, force_full=True,
            )
        for name, reason in gang_retracted.items():
            out.result.infeasible.setdefault(name, reason)
        return out

    #: capability probe for SolvePipeline._flush: this scheduler's
    #: submit_many accepts flush_reason= and owns the MEGABATCH_FLUSH
    #: count for the flush (facades/test doubles without it keep the
    #: pipeline-side upfront count)
    counts_flush_reason = True

    def submit_many(
        self, requests: Sequence[dict],
        flush_reason: Optional[str] = None,
    ) -> List["PendingScheduleResult"]:
        """Cross-request megabatch entry (service/server.py SolvePipeline's
        coalescer flushes here): each request is a kwargs dict (``pods``,
        ``provisioners``, ``instance_types`` plus the :meth:`solve`
        keywords).  The registration phase runs each request's tensorize +
        routing exactly like :meth:`submit`, but first device waves land in
        a :class:`_MegaCollector` instead of dispatching; one vmapped device
        call per shape bucket then solves every slot in a single round trip.
        Returns per-request handles IN ORDER — ``result()`` runs that
        request's own epilogues (relaxation ladder, residue waves, reseat)
        against its own result only; requests share nothing but the device
        dispatch.  Same single-thread contract as :meth:`submit`.
        ``flush_reason`` (the pipeline's coalescer reason) transfers the
        MEGABATCH_FLUSH count here: the flush incs exactly one reason —
        "mesh_serial" when a meshed flush degraded to serial, else
        ``flush_reason`` — keeping the labels a partition of flushes."""
        guarded = self.backend == "auto" and self._guard.enabled
        collector = _MegaCollector(
            self._tpu, guard=self._guard if guarded else None,
            registry=self.registry, warm=self._warm_mega, mesh=self.mesh,
            on_mesh_serial=self._note_mesh_serial,
            flush_reason=flush_reason,
        )
        self._mega_collect = collector
        try:
            pendings = [
                self._submit(
                    req["pods"], req["provisioners"], req["instance_types"],
                    **{k: v for k, v in req.items()
                       if k not in ("pods", "provisioners", "instance_types",
                                    "relax")},
                    # megabatch slots skip the relax rung: the coalesced
                    # flush is the latency path, and a per-slot host
                    # rounding pass on the dispatcher thread would stall
                    # every batchmate behind it (KT_RELAX's routing note)
                    relax=bool(req.get("relax", False)),
                    dispatch=True,
                )
                for req in requests
            ]
        finally:
            self._mega_collect = None
        collector.dispatch()
        return pendings

    def _note_mesh_serial(self, detail: str, count: bool = True) -> None:
        """A mesh-configured scheduler served (or will serve) a would-be
        sharded megabatch serially: count it so meshed-serving degradation
        is visible (the acceptance dashboards watch this stay near zero),
        log the first occurrence with the why.  ``count=False`` logs only —
        used when the count is owned elsewhere: a pipeline-owned
        submit_many flush (flush_reason=) counts once at collector
        dispatch — counting here too would double-count and mix units
        with the per-flush full/deadline/bucket reasons.  (The old
        per-request caller — bucket_key probing an unshardable mesh — is
        gone: that verdict is hoisted onto ``mega_unshardable`` at
        construction, so this now only runs at flush dispatch.)"""
        if count:
            self.registry.counter(MEGABATCH_FLUSH).inc(
                {"reason": "mesh_serial"})
        with self._cold_lock:
            first = not self._mesh_serial_logged
            self._mesh_serial_logged = True
        if first:
            logger.info(
                "meshed scheduler served a megabatch flush serially (%s); "
                "counted under karpenter_solver_megabatch_flush_total"
                "{reason=\"mesh_serial\"}", detail)

    def unify_buckets(self, held_key: tuple,
                      new_key: tuple) -> Optional[tuple]:
        """Mixed-bucket unification hook for the pipeline's SlotCoalescer
        (ISSUE 14): the DOMINANT of two megabatch bucket keys when one
        subsumes the other (solver/tpu.unify_mega_keys), else None.  A
        held flush can then admit a dominated request and the whole batch
        shares one mesh dispatch at the dominant bucket's program —
        dominated requests pad up at dispatch (target_dims), results
        byte-identical to their own-bucket solves."""
        return unify_mega_keys(held_key, new_key)

    @contextmanager
    def _host_local(self):
        """Scoped mesh override: the enclosed solve waves run the
        HOST-LOCAL single-shard programs (mesh=None) instead of the
        scheduler's mesh — the delta fast path's route for sub-ms
        displaced-subproblem steps on a meshed scheduler.  Safe under the
        scheduler's documented single-dispatcher contract (the thread
        that runs submit/solve/solve_delta owns every solve section —
        concurrent solves were never allowed); readiness probes and
        compile-behind warms inside the scope target the host-local
        programs, so the first local step rides the warm host tier while
        its single-shard program compiles behind, like any cold shape."""
        prev, self.mesh = self.mesh, None
        try:
            yield
        finally:
            self.mesh = prev

    def bucket_key(self, kwargs: dict) -> Optional[tuple]:
        """Megabatch shape bucket of one queued solve request, or None when
        it cannot ride a megabatch (non-device backend, oracle routing,
        device carve-outs, cold shape, unhealthy device, cache disabled,
        or a mesh whose device count exceeds the slot-rung ladder).
        Meshed schedulers bucket like single-device ones since the sharded
        megabatch round — the key carries the mesh signature, so requests
        against different meshes can never coalesce.
        Pipeline-dispatcher-only, like submit: the tensorize it performs
        lands in the cache, so the real solve's tensorize is a hit.
        With the request's ``trace`` in ``kwargs`` each stretch of the
        probe is a span under the caller's ``bucket``, ``where="probe"`` on
        each: ``harden`` and ``carve`` (the passes over the batch that
        ``_submit`` and ``_solve_tpu`` make again), ``tensorize`` (the
        fresh batch's build: the ``miss`` of the request) and
        ``signature``."""
        if self.backend not in ("auto", "tpu"):
            return None
        if self.mega_unshardable:
            # the slot axis cannot pad to one-slot-per-chip on this mesh —
            # a verdict hoisted to (and logged at) construction, so the
            # per-request probe is one attribute read; the pipeline counts
            # the resulting single-request flushes under mesh_serial
            return None
        pods = list(kwargs.get("pods") or ())
        if not pods or not kwargs.get("allow_new_nodes", True):
            return None
        if self._route_small(len(pods)):
            return None
        trace = kwargs.get("trace") or NULL_TRACE
        try:
            with trace.span("harden", where="probe"):
                hardened = [_harden_preferences(p) for p in pods]
            with trace.span("carve", where="probe"):
                serial = (batch_needs_oracle(hardened)
                          # oracle carve-outs couple waves; keep serial
                          or any(device_inexpressible(p) for p in hardened))
            if serial:
                return None
            if (self.backend == "auto" and self._guard.enabled
                    and not self._guard.healthy):
                return None
            tpu_pods = hardened
            st, _dt = self._tensorize(
                tpu_pods, kwargs["provisioners"], kwargs["instance_types"],
                kwargs.get("daemonsets") or (), kwargs.get("unavailable"),
                trace=trace, where="probe",
            )
            existing = list(kwargs.get("existing_nodes") or ())
            max_new = kwargs.get("max_new_nodes")
            new_budget = len(tpu_pods) if max_new is None else max_new
            max_slots = len(existing) + new_budget
            with trace.span("signature", where="probe"):
                if not self._device_ready(st, existing, max_slots):
                    return None  # cold shapes keep the compile-behind path
                return self._tpu.mega_signature(
                    st, existing_nodes=existing, max_nodes=max_slots,
                    slots=1, mesh=self.mesh,
                )
        # ktlint: allow[KT005] the bucket probe must never fail a request —
        # an unbucketable request just solves on the classic single path,
        # where a real error surfaces with full context
        except Exception:
            logger.debug("bucket_key probe failed; request rides the single "
                         "path", exc_info=True)
            return None

    def _warm_mega(self, entry: dict, slots: int) -> None:
        """Background-compile the megabatch program for a bucket whose flush
        just fell back to serial dispatches (cold slot rung) — the SHARDED
        rung program for a meshed scheduler."""
        if not self.compile_behind or not self._guard.healthy:
            return
        started = self._tpu.warm_async(
            entry["st"],
            existing_nodes=[n.snapshot() for n in entry["existing_nodes"]],
            max_nodes=entry["max_nodes"], slots=max(2, slots),
            mesh=self.mesh, on_done=self._warm_done,
        )
        if started:
            self.registry.gauge(SOLVER_COMPILE_IN_PROGRESS).set(
                self._tpu.compiles_in_flight()
            )

    def _submit(
        self,
        pods: Sequence[PodSpec],
        provisioners: Sequence[Provisioner],
        instance_types: Sequence[InstanceType],
        *,
        existing_nodes: Sequence[SimNode] = (),
        daemonsets: Sequence[PodSpec] = (),
        unavailable: Optional[Set[tuple]] = None,
        allow_new_nodes: bool = True,
        max_new_nodes: Optional[int] = None,
        trace=None,
        relax: Optional[bool] = None,
        dispatch: bool = False,
    ) -> "PendingScheduleResult":
        t0 = time.perf_counter()
        trace = trace or NULL_TRACE
        trace.annotate(backend=self.backend, n_pods=len(pods))
        with trace.span("harden"):
            hardened = [_harden_preferences(p) for p in pods]
        try:
            # the dispatch span covers tensorize + H2D + device enqueue on
            # the async path; on the sync/oracle path it covers the whole
            # first wave (there is no separate fence to split out)
            with trace.span("dispatch", async_dispatch=dispatch):
                first = self._solve_once(
                    hardened, provisioners,
                    instance_types, list(existing_nodes), daemonsets,
                    unavailable, allow_new_nodes, max_new_nodes,
                    dispatch=dispatch, trace=trace,
                )
        except BaseException:
            # the old solve() observed in a finally around the WHOLE solve;
            # a synchronous failure before the finish closure exists must
            # still land in the histogram
            self.registry.histogram(SCHEDULING_DURATION).observe(
                time.perf_counter() - t0)
            raise

        def _finish() -> SolveResult:
            try:
                if isinstance(first, _PendingWave):
                    # the overlap window closes here: one RTT to the device
                    # fence (plus any slot-exhaustion retry) — the span that
                    # explains a solve stuck behind a hung device call
                    with trace.span("fence"):
                        res0 = first.finish()
                else:
                    res0 = first
                result = self._solve_wave(
                    pods, provisioners, instance_types, list(existing_nodes),
                    daemonsets, unavailable, allow_new_nodes, max_new_nodes,
                    first=res0, trace=trace,
                )

                # the post-fence repair epilogues (OR-term ladder, residue
                # convergence, capped-node reseat) share one "reseat" span —
                # zero-iteration in steady state, the whole story when a
                # solve is slow because its batch needed repair waves
                with trace.span("reseat") as reseat_span:
                    waves = 0
                    # OR'd required-affinity terms beyond the first: the
                    # solvers pack under term[0] only (tensorize.group_pods),
                    # so still-infeasible pods retry under each alternate
                    # term in order — the term list is a disjunction
                    # (scheduling.md nodeSelectorTerms semantics).
                    max_terms = max(
                        (len(p.required_affinity_terms) for p in pods), default=0)
                    for k in range(1, max_terms):
                        alts = []
                        for p in pods:
                            if p.name in result.infeasible and len(p.required_affinity_terms) > k:
                                q = copy.copy(p)
                                q.required_affinity_terms = [p.required_affinity_terms[k]]
                                q.__dict__.pop("_group_key", None)
                                alts.append(q)
                        if not alts:
                            break
                        waves += 1
                        _merge(result, self._solve_wave(
                            alts, provisioners, instance_types,
                            list(result.existing_nodes) + result.nodes, daemonsets,
                            unavailable, allow_new_nodes,
                            _budget_left(result, max_new_nodes), trace=trace,
                        ))

                    # residue convergence (see MAX_RESIDUE_WAVES): re-offer
                    # the still-infeasible pods the state every prior wave
                    # produced — open rows on placed nodes and the limit
                    # headroom left after funded creations — until a wave
                    # places nothing new.
                    for _ in range(MAX_RESIDUE_WAVES):
                        retry = [p for p in pods if p.name in result.infeasible]
                        if not retry:
                            break
                        sub = self._solve_wave(
                            retry, provisioners, instance_types,
                            list(result.existing_nodes) + result.nodes, daemonsets,
                            unavailable, allow_new_nodes,
                            _budget_left(result, max_new_nodes), trace=trace,
                        )
                        if not sub.assignments:
                            break  # no progress: the residue is genuinely infeasible
                        waves += 1
                        _merge(result, sub)
                    # ct-spread batches are already fully oracle-interleaved
                    # (batch_needs_oracle routing); the reseat epilogue buys
                    # nothing there and its incremental _ct_allowed re-fill has
                    # the same mid-band-hole weakness the zone check guards
                    # (ADVICE r5 medium) — skip it wholesale.  Judged on the
                    # HARDENED pods: routing hardens first, so a ScheduleAnyway
                    # ct spread becomes DoNotSchedule and oracle-routes exactly
                    # like a hard one — the skip must see the same batch
                    if not batch_needs_oracle(hardened):
                        self._reseat_capped(
                            result, provisioners, instance_types, daemonsets,
                            unavailable, n_pods=len(pods),
                            max_new_nodes=max_new_nodes,
                        )
                    reseat_span.annotate(repair_waves=waves)

                # convex-relaxation refinement rung (solver/relax.py):
                # re-pack the large unconstrained groups globally and ship
                # min(scan, relax+round) — never worse by construction
                result = self._maybe_relax(
                    result, hardened, provisioners, instance_types,
                    daemonsets, unavailable, allow_new_nodes,
                    max_new_nodes, relax, trace,
                )

                # gang all-or-nothing + co-location epilogue (ISSUE 20,
                # karpenter_tpu/gang/): after the relax rung — gang groups
                # are relax-INELIGIBLE (relax.eligible_partition), so their
                # scan seats are fixed boundary conditions by the time the
                # epilogue audits, retracts, and packs them
                from .. import gang as gangmod

                if gangmod.gang_enabled() and gangmod.has_gangs(pods):
                    with trace.span("gang") as gang_span:
                        result = gangmod.run_epilogue(
                            result, pods,
                            registry=self.registry,
                            # a retraction that would disturb watched spread/
                            # affinity accounting re-solves the keep-set from
                            # the pristine pre-solve existing nodes
                            resolve=lambda keep: self._solve_wave(
                                keep, provisioners, instance_types,
                                list(existing_nodes), daemonsets, unavailable,
                                allow_new_nodes, max_new_nodes, trace=trace),
                            provisioners=provisioners,
                            instance_types=instance_types,
                            daemonsets=daemonsets,
                            unavailable=unavailable,
                            allow_new_nodes=allow_new_nodes,
                            max_new_nodes=max_new_nodes,
                            in_band=self._reseat_in_band,
                            trace=gang_span,
                        )

                trace.annotate(
                    served_cold=result.served_cold,
                    n_nodes=len(result.nodes),
                    n_infeasible=len(result.infeasible),
                    cost=round(result.new_node_cost, 4),
                    solve_ms=round(result.solve_ms, 3),
                )
                return result
            finally:
                self.registry.histogram(SCHEDULING_DURATION).observe(
                    time.perf_counter() - t0)

        return PendingScheduleResult(_finish)

    def _reseat_capped(
        self, result: SolveResult, provisioners, instance_types, daemonsets,
        unavailable, *, n_pods: int, max_new_nodes: Optional[int] = None,
    ) -> None:
        """Cost-decreasing epilogue for nearly-empty residue nodes: the scan
        solver places group-at-a-time, so a group tail (or a per-node-capped
        group — hostname anti-affinity, spread caps) can buy dedicated
        near-empty nodes where the oracle's pod-interleaved first-fit seats
        the same pods on other groups' open capacity, or serves them from a
        cheaper right-sized node (fuzz seed 5: 7 single-pod m5.large at
        +3.3%; kubelet seed 20: a zone-spread band-top orphan riding a
        2xlarge it shares with one hostname-spread pod, where re-solving
        seats the orphan on another zone's slack and downsizes the node).
        Take the new nodes holding at most two pods, re-solve exactly those
        pods with the oracle against everything else placed, and adopt the
        answer only when every pod still places AND it is strictly cheaper —
        quality can only improve by construction.  Device backends only —
        the oracle backend (and auto's oracle-served small batches) already
        interleave."""
        if (self.backend == "oracle" or self._route_small(n_pods)
                or not result.nodes or result.served_cold):
            return

        def _capped(p: PodSpec) -> bool:
            # per-node CAPS: hostname anti-affinity and hard hostname spread
            # — the shapes whose reseat wins are structural (they build
            # single-pod fleets with backfillable slack)
            return any(
                t.anti and t.topology_key == L.HOSTNAME
                for t in p.affinity_terms
            ) or any(
                t.hard and t.topology_key == L.HOSTNAME
                for t in p.topology_spread
            )

        waste = [n for n in result.nodes if n.pods and len(n.pods) <= 2]
        # bounded epilogue: a batch whose pods are node-sized (1-2 per node
        # by design) would otherwise re-solve nearly everything through the
        # sequential oracle and erase the device speedup.  Trim to a 64-pod
        # re-solve budget, keeping capped fleets first (the structural wins)
        # then the most expensive residue — never skip wholesale
        if sum(len(n.pods) for n in waste) > 64:
            waste.sort(key=lambda n: (
                0 if all(_capped(p) for p in n.pods) else 1, -n.price, n.name))
            trimmed, tot = [], 0
            for n in waste:
                if tot + len(n.pods) > 64:
                    continue  # overfull node; later smaller ones may still fit
                trimmed.append(n)
                tot += len(n.pods)
            waste = trimmed
        if not waste:
            return
        waste_ids = {id(n) for n in waste}
        waste_pods = [p for n in waste for p in n.pods]
        keep = [n for n in result.nodes if id(n) not in waste_ids]
        others = list(result.existing_nodes) + keep
        # fast screen before paying a sequential oracle solve on EVERY batch
        # whose pod count isn't a multiple of node capacity (almost all):
        # a win requires either free room for a waste pod somewhere else
        # (resource-only — caps/zones may still block, the oracle decides)
        # or a waste node that isn't the cheapest catalog way to host its
        # own pods.  A routine right-sized tail node fails both and skips.
        if not self._reseat_plausible(waste, others, instance_types):
            return
        # honor the caller's new-node budget: the epilogue may only spend
        # what the waste nodes gave back (max_new_nodes=1 what-ifs must not
        # come back with 2 replacements)
        budget = (None if max_new_nodes is None
                  else max(0, max_new_nodes - len(keep)))
        re = oracle_solve(
            waste_pods, provisioners, instance_types,
            existing_nodes=others, daemonsets=daemonsets,
            unavailable=unavailable, allow_new_nodes=True,
            max_new_nodes=budget,
        )
        old_cost = sum(n.price for n in waste)
        if re.infeasible or re.new_node_cost >= old_cost - 1e-9:
            return
        if not self._reseat_in_band(waste_pods, re, instance_types):
            return
        placed = list(re.existing_nodes)  # snapshots of others, pods seated
        ne = len(result.existing_nodes)
        result.existing_nodes = placed[:ne]
        result.nodes = placed[ne:] + list(re.nodes)
        result.assignments.update(re.assignments)

    @staticmethod
    def _reseat_plausible(waste, others, instance_types) -> bool:
        """Cheap necessary condition for a reseat win: some waste pod has
        resource-level room on another placed node (absorption might be
        possible), or some waste node is priced above the cheapest catalog
        type that fits its pods (downsizing might be possible)."""
        for n in waste:
            for p in n.pods:
                req = dict(p.requests)
                req.setdefault(L.RESOURCE_PODS, 1.0)
                for o in others:
                    rem = o.remaining()
                    if all(rem.get(k, 0.0) >= v - 1e-9 for k, v in req.items()):
                        return True
        for n in waste:
            total: Dict[str, float] = {}
            for p in n.pods:
                for k, v in p.requests.items():
                    total[k] = total.get(k, 0.0) + v
            total[L.RESOURCE_PODS] = float(len(n.pods))
            for it in instance_types:
                if not all(it.allocatable.get(k, 0.0) >= v - 1e-9
                           for k, v in total.items()):
                    continue
                cheapest = min(
                    (o.price for o in it.offerings if o.available),
                    default=None,
                )
                if cheapest is not None and cheapest < n.price - 1e-9:
                    return True
        return False

    @staticmethod
    def _reseat_in_band(moved, re, instance_types) -> bool:
        """Global zone-spread check on a reseat adoption candidate.

        The oracle's incremental band check (`counts[z]+1-min <= skew`)
        assumes an IN-BAND starting state; removing the waste nodes can hand
        it a mid-band hole it then legally over-fills from (fuzz seed 17:
        removing four 2-pod zone-1b nodes left {11,1,8}; per-placement-legal
        refilling ended {11,7,10} — skew 4 over a 3 band).  Re-check every
        moved pod's hard zone spread GLOBALLY over its eligible zones and
        reject the adoption on any violation — the pre-reseat result was
        valid, so rejecting preserves validity."""
        # spec key mirrors the ground-truth validator: same selector + skew
        # but different node pins are DIFFERENT spread groups with different
        # eligible-zone sets — deduping on (selector, skew) alone would let
        # a zone-pinned pod (trivially in band over its one zone) mask an
        # unpinned group's violation.  Specs come from EVERY pod in the
        # adoption candidate whose selector matches a moved pod, not just
        # the moved pods' own constraints — a kept group's spread counts
        # the moved pod too (the oracle's observe() matches by selector,
        # regardless of which pod carries the constraint)
        nodes = list(re.existing_nodes) + list(re.nodes)
        moved_labels = [p.labels for p in moved]
        specs = {}
        for n in nodes:
            for q in n.pods:
                for tsc in q.topology_spread:
                    if not (tsc.hard and tsc.topology_key == L.ZONE):
                        continue
                    if not any(tsc.label_selector.matches(lb)
                               for lb in moved_labels):
                        continue
                    key = (tsc.label_selector, tsc.max_skew,
                           tuple(sorted(q.node_selector.items())),
                           tuple(q.volume_zone_requirements))
                    specs.setdefault(key, (tsc, q))
        if specs:
            all_zones: List[str] = []
            for it in instance_types:
                for o in it.offerings:
                    if o.zone not in all_zones:
                        all_zones.append(o.zone)
            for tsc, rep in specs.values():
                eligible = [
                    z for z in all_zones
                    if rep.node_selector.get(L.ZONE, z) == z
                    and all(r.value_set().contains(z)
                            for r in rep.volume_zone_requirements)
                ]
                if not eligible:
                    continue
                counts = {z: 0 for z in eligible}
                for n in nodes:
                    if n.zone in counts:
                        counts[n.zone] += sum(
                            1 for q in n.pods
                            if tsc.label_selector.matches(q.labels)
                        )
                if max(counts.values()) - min(counts.values()) > tsc.max_skew:
                    return False
        # hostname anti-affinity is enforced by the oracle only for the
        # INCOMING pod's own terms; a moved pod with no terms could land
        # beside a kept pod whose anti selector matches it.  Re-check every
        # node that received a moved pod bidirectionally (the validator's
        # rule: a pod's hostname-anti term may match at most one co-located
        # pod — itself)
        moved_names = {p.name for p in moved}
        for n in nodes:
            if not any(q.name in moved_names for q in n.pods):
                continue
            for q in n.pods:
                for term in q.affinity_terms:
                    if term.anti and term.topology_key == L.HOSTNAME:
                        matches = sum(
                            1 for r in n.pods
                            if term.label_selector.matches(r.labels)
                        )
                        if matches > 1:
                            return False
        # same bidirectional rule at zone scope: any pod in a zone that
        # received a moved pod may carry a zone anti-affinity term the
        # moved pod violates (at most one matching pod — itself — in the
        # zone)
        moved_zones = {n.zone for n in nodes
                       if any(q.name in moved_names for q in n.pods)}
        for z in moved_zones:
            zone_pods = [q for n in nodes if n.zone == z for q in n.pods]
            for q in zone_pods:
                for term in q.affinity_terms:
                    if term.anti and term.topology_key == L.ZONE:
                        matches = sum(
                            1 for r in zone_pods
                            if term.label_selector.matches(r.labels)
                        )
                        allowed = 1 if term.label_selector.matches(q.labels) else 0
                        if matches > allowed:
                            return False
        # kept pods' POSITIVE zone-affinity toward moved pods: a kept pod
        # whose only selector-matching zone-mate was a moved pod is orphaned
        # when the reseat moves that pod to another zone.  Conservative
        # global re-check (rejecting keeps the valid pre-reseat result):
        # every pod carrying a positive zone term whose selector matches any
        # moved pod must still have a matching pod in its own zone — itself
        # only when no matcher exists anywhere else (the mode-B seed shape).
        for n in nodes:
            for q in n.pods:
                for term in q.affinity_terms:
                    if term.anti or term.topology_key != L.ZONE:
                        continue
                    if not any(term.label_selector.matches(lb)
                               for lb in moved_labels):
                        continue  # the reseat moved nothing this term matches
                    if any(term.label_selector.matches(r.labels)
                           for nn in nodes if nn.zone == n.zone
                           for r in nn.pods if r.name != q.name):
                        continue
                    if term.label_selector.matches(q.labels) and not any(
                        term.label_selector.matches(r.labels)
                        for nn in nodes if nn.zone != n.zone
                        for r in nn.pods
                    ):
                        continue  # sole matcher anywhere: valid self-seed
                    return False
        # hard hostname spread on nodes that RECEIVED a moved pod: the
        # oracle enforces the incoming pod's own constraints only, so a
        # moved pod landing beside a kept spread-bearing pod can push that
        # node's matching count past the band (per-node cap is maxSkew —
        # an empty node keeps the global hostname minimum at 0)
        for n in nodes:
            if not any(q.name in moved_names for q in n.pods):
                continue
            for q in n.pods:
                for tsc in q.topology_spread:
                    if not (tsc.hard and tsc.topology_key == L.HOSTNAME):
                        continue
                    matches = sum(1 for r in n.pods
                                  if tsc.label_selector.matches(r.labels))
                    if matches > tsc.max_skew:
                        return False
        return True

    def _maybe_relax(
        self, result: SolveResult, hardened, provisioners, instance_types,
        daemonsets, unavailable, allow_new_nodes,
        max_new_nodes: Optional[int], relax: Optional[bool], trace,
    ) -> SolveResult:
        """Route a finished device-tier solve through the convex-relaxation
        refinement rung (solver/relax.py) and ship min(scan, relax+round).

        ``relax`` is the caller's policy: False skips unconditionally (the
        delta fast path, megabatch slots), None defers to ``KT_RELAX``
        (default on).  The rung only applies to device-scan results — the
        oracle-routed small/ct-spread batches and forced non-device
        backends return untouched and uncounted (the rung's outcome
        counter partitions rung EVALUATIONS, not all solves) — and only to
        unbudgeted provisioning solves: consolidation what-ifs
        (max_new_nodes / allow_new_nodes) are judged on feasibility at a
        fixed budget, not on node cost.  A still-compiling relax program
        counts 'skipped' and warms behind — the serving path never eats
        the XLA stall (the compile-behind contract, KT014-audited)."""
        from . import relax as relax_mod

        if relax is False or not relax_mod.relax_enabled():
            return result
        if self.backend not in ("auto", "tpu"):
            return result  # the rung refines the device scan only
        if not allow_new_nodes or max_new_nodes is not None:
            return result
        with trace.span("carve"):
            tpu_pods = [p for p in hardened if not device_inexpressible(p)]
            scan_refinable = (len(tpu_pods) > self.native_batch_limit
                              and not batch_needs_oracle(hardened))
        if not scan_refinable:
            # small batches are oracle-grade already (and under auto the
            # oracle served them — no scan to refine); the rung targets
            # LARGE unconstrained groups on every backend, so forced-tpu
            # small-batch tests/fuzz keep byte-stable scan results
            return result
        guarded = self.backend == "auto" and self._guard.enabled
        if result.served_cold or (guarded and not self._guard.healthy):
            relax_mod.record_outcome(self.registry, "skipped")
            return result
        try:
            # identity-tier hit: these are the same pod objects the solve
            # wave tensorized moments ago
            st, _tsec = self._tensorize(
                tpu_pods, provisioners, instance_types, daemonsets,
                unavailable, trace=trace)
            sig = relax_mod.relax_signature(st)
            if not self._tpu.ready(sig):
                if self.compile_behind and self._guard.healthy:
                    relax_mod.warm_relax(self._tpu, st,
                                         on_done=self._warm_done)
                relax_mod.record_outcome(self.registry, "skipped")
                return result

            def _repair(stranded, seeds):
                # integrality repair: the existing scan, seeded from the
                # rounded fleet as existing-node state (PR-6 shape); the
                # repair solve must never re-enter the rung
                return self._submit(
                    stranded, provisioners, instance_types,
                    existing_nodes=seeds, daemonsets=daemonsets,
                    unavailable=unavailable, allow_new_nodes=True,
                    relax=False, trace=trace,
                ).result()

            result, _outcome = relax_mod.refine(
                result, st, registry=self.registry,
                guard=self._guard if guarded else None, trace=trace,
                repair_solve=_repair,
            )
            return result
        # ktlint: allow[KT005] the rung is an optimization layer — any
        # routing failure ships the proven scan solution as a fallback
        except Exception:
            logger.warning("relax rung routing failed; scan solution ships",
                           exc_info=True)
            relax_mod.record_outcome(self.registry, "fallback")
            return result

    def _solve_wave(
        self, pods, provisioners, instance_types, existing_nodes, daemonsets,
        unavailable, allow_new_nodes, max_new_nodes, first=None,
        trace=None,
    ) -> SolveResult:
        """One pod wave with the preference-relaxation ladder applied.
        ``first`` short-circuits the all-preferences-hardened opening solve
        when the caller already dispatched it (submit's async first wave)."""
        result = first if first is not None else self._solve_once(
            [_harden_preferences(p) for p in pods], provisioners,
            instance_types, existing_nodes, daemonsets, unavailable,
            allow_new_nodes, max_new_nodes, trace=trace,
        )
        # cap the ladder depth like the reference caps its long axes
        # (SURVEY §5 long-context analog: 60-type truncation, batching):
        # a pod with absurdly many preferences drops straight to its last
        # MAX_RELAXATION_WAVES instead of funding one solve per preference.
        # Finding the depth is a pass over the batch even when no pod has a
        # preference: the `ladder` span holds it and the rungs it funds
        with (trace or NULL_TRACE).span("ladder") as span:
            max_pref = min(
                max((_n_preferences(p) for p in pods), default=0),
                MAX_RELAXATION_WAVES,
            )
            span.annotate(depth=max_pref)
            for keep in range(max_pref - 1, -1, -1):
                retry = [p for p in pods if p.name in result.infeasible
                         and _n_preferences(p) > keep]
                if not retry:
                    continue
                _merge(result, self._solve_once(
                    [_harden_preferences(p, keep) for p in retry],
                    provisioners, instance_types,
                    list(result.existing_nodes) + result.nodes, daemonsets,
                    unavailable, allow_new_nodes,
                    _budget_left(result, max_new_nodes), trace=trace,
                ))
        return result

    def _solve_once(
        self, pods, provisioners, instance_types, existing_nodes, daemonsets,
        unavailable, allow_new_nodes, max_new_nodes, dispatch=False,
        trace=None,
    ):
        # a hard capacity-type spread couples the whole batch to the
        # sequential engine (batch_needs_oracle) — exact interleaved
        # semantics, every backend.  Asking is a pass over the batch, the
        # same routing question `_solve_tpu` goes on with: one name
        host = self.backend == "oracle" or self._route_small(len(pods))
        if not host:
            with (trace or NULL_TRACE).span("carve"):
                host = batch_needs_oracle(pods)
        if host:
            t0 = time.perf_counter()
            try:
                return oracle_solve(
                    pods, provisioners, instance_types,
                    existing_nodes=existing_nodes, daemonsets=daemonsets,
                    unavailable=unavailable, allow_new_nodes=allow_new_nodes,
                    max_new_nodes=max_new_nodes,
                )
            finally:
                self.registry.histogram(SOLVER_BACKEND_DURATION).observe(
                    time.perf_counter() - t0, {"backend": "oracle"}
                )
        if self._route_hier(pods, existing_nodes, allow_new_nodes,
                            max_new_nodes):
            from .hierarchy import solve_hierarchical

            result = solve_hierarchical(
                self, pods, provisioners, instance_types,
                daemonsets=daemonsets, unavailable=unavailable, trace=trace,
            )
            if result is not None:
                return result
            # None = flat is the right (or only warm) program for this
            # batch — the hier metrics label recorded why; fall through
        return self._solve_tpu(
            pods, provisioners, instance_types, existing_nodes, daemonsets,
            unavailable, allow_new_nodes, max_new_nodes, dispatch=dispatch,
            trace=trace,
        )

    #: startup-warmup shape profiles: (groups, total_pods, with_zone_spread).
    #: These mirror the steady-state controller batches — a provisioning wave
    #: of mixed pods, with and without topology spread (the selector-axis S
    #: rung differs between the two, so they are distinct compile
    #: signatures) — so the first real batches hit a compiled program; shapes
    #: outside the warmed ladder are covered by compile-behind
    #: (_device_ready), never by a caller stall.
    WARM_PROFILES = ((16, 400, False), (16, 400, True))

    #: megabatch slot rungs the startup precompile covers by default: the
    #: coalescer pads flushes to power-of-two rungs (tpu._mega_rung), so
    #: warming these serves every occupancy up to the default --max-slots
    WARM_MEGA_SLOTS = (2, 4, 8)

    def _profile_tensors(self, provisioners, instance_types, daemonsets,
                         profiles=None):
        """Tensorized startup-warmup batches, one per shape profile — the
        single source :meth:`warm_startup` (single-solve ladder) and
        :meth:`precompile_buckets` (megabatch rungs) both warm from."""
        from ..models.pod import TopologySpreadConstraint

        out = []
        for groups, total, spread in (profiles or self.WARM_PROFILES):
            pods = []
            per = max(1, total // groups)
            for gi in range(groups):
                sel = LabelSelector.of({"warmup-group": f"g{gi}"})
                constraints = (
                    [TopologySpreadConstraint(1, L.ZONE, "DoNotSchedule", sel)]
                    if spread else []
                )
                for i in range(per):
                    pods.append(PodSpec(
                        name=f"warmup-g{gi}-{i}",
                        labels={"warmup-group": f"g{gi}"},
                        requests={"cpu": 0.25 * (1 + gi % 8),
                                  "memory": float(2 ** (30 + gi % 3))},
                        topology_spread=list(constraints),
                        owner_key=f"warmup-g{gi}",
                    ))
            out.append(tensorize(pods, provisioners, instance_types,
                                 daemonsets=daemonsets))
        return out

    def warm_startup(
        self,
        provisioners,
        instance_types,
        daemonsets: Sequence[PodSpec] = (),
        existing_nodes: Sequence[SimNode] = (),
        profiles=None,
    ) -> int:
        """Kick off background compiles for the startup shape ladder against
        the live catalog/provisioners — and, crucially, against the live
        CLUSTER SIZE: ``existing_nodes`` (snapshots) set the NE/NR rungs, so
        an operator restarting over a 500-node cluster warms the shapes its
        provisioning and consolidation solves will actually hit, not the
        empty-cluster ones.  Returns the number of compiles accepted.  Cheap
        to call repeatedly (signatures dedupe), so the operator re-invokes
        it on settings changes that reshape the catalog."""
        from . import relax as relax_mod

        if (self.backend not in ("auto", "tpu") or not self.compile_behind
                or not self._guard.healthy):
            return 0
        started = 0
        for st in self._profile_tensors(provisioners, instance_types,
                                        daemonsets, profiles):
            # provisioning shape: batch solved against the current cluster
            if self._tpu.warm_async(st, existing_nodes=existing_nodes,
                                    mesh=self.mesh, on_done=self._warm_done):
                started += 1
            # the relax rung's program for the same shape (KT_RELAX): the
            # first refinable solve then runs the rung instead of
            # skip-and-warm-behind (KT014 audits this grid's coverage)
            if relax_mod.relax_enabled() and relax_mod.warm_relax(
                    self._tpu, st, on_done=self._warm_done):
                started += 1
            if existing_nodes:
                # consolidation what-if shape: a small repack against the
                # cluster with at most one new node (deprovisioning.py
                # _solve_what_if passes max_new_nodes=1)
                if self._tpu.warm_async(
                    st, existing_nodes=existing_nodes,
                    max_nodes=len(existing_nodes) + 1,
                    mesh=self.mesh, on_done=self._warm_done,
                ):
                    started += 1
        if started:
            self.registry.gauge(SOLVER_COMPILE_IN_PROGRESS).set(
                self._tpu.compiles_in_flight()
            )
            logger.info("startup warmup: %d solver shape compiles accepted "
                        "in the background", started)
        return started

    def precompile_buckets(
        self,
        provisioners,
        instance_types,
        daemonsets: Sequence[PodSpec] = (),
        existing_nodes: Sequence[SimNode] = (),
        profiles=None,
        mega_slots: Optional[Sequence[int]] = None,
        wait: bool = False,
        timeout: float = 1800.0,
    ) -> int:
        """Ahead-of-time bucket-grid precompile: the startup single-solve
        ladder (:meth:`warm_startup`) PLUS the megabatch programs at the
        given request-slot rungs, so both the serial and the coalesced
        serving paths are warm before the first RPC.  ``wait=True`` blocks
        until every accepted compile lands (the ``serve --warmup`` path),
        observes the total in ``karpenter_solver_precompile_duration_seconds``
        and raises :class:`WarmupFailed` if any of them failed or outlived
        ``timeout`` — so under ``wait`` the return value counts programs
        that COMPILED.  The persistent compile cache (solver/tpu.py
        ``jit_cache_dir``) lets restarts skip even this.  Without ``wait``
        returns the number of compiles accepted."""
        t0 = time.perf_counter()
        with self._cold_lock:
            errors_before = len(self._warm_errors)
        started = self.warm_startup(
            provisioners, instance_types, daemonsets=daemonsets,
            existing_nodes=existing_nodes, profiles=profiles,
        )
        if (self.backend in ("auto", "tpu") and self.compile_behind
                and self._guard.healthy and mesh_shardable(self.mesh)):
            # meshed schedulers warm the SHARDED rung ladder: warm_async
            # resolves each requested slot count to its sharded rung (floor
            # = device count), and signature dedupe collapses requests that
            # land on the same rung — the default (2, 4, 8) grid on an
            # 8-device mesh warms exactly the 8-slot sharded program
            rungs = sorted({
                s for s in (mega_slots or self.WARM_MEGA_SLOTS)
                if 2 <= s <= MEGA_MAX_SLOTS
            })
            for st in self._profile_tensors(provisioners, instance_types,
                                            daemonsets, profiles):
                for s in rungs:
                    if self._tpu.warm_async(
                        st, existing_nodes=existing_nodes, slots=s,
                        mesh=self.mesh, on_done=self._warm_done,
                    ):
                        started += 1
        if wait and started:
            deadline = time.perf_counter() + timeout
            while (not self._tpu.warm_idle()
                   and time.perf_counter() < deadline):
                time.sleep(0.25)
            self.registry.histogram(PRECOMPILE_DURATION).observe(
                time.perf_counter() - t0)
            with self._cold_lock:
                failed = self._warm_errors[errors_before:]
            if not self._tpu.warm_idle():
                raise WarmupFailed(
                    f"bucket precompile still running after the {timeout:.0f}s "
                    "wait budget")
            if failed:
                raise WarmupFailed(
                    f"{len(failed)} of {started} bucket compiles failed: "
                    + "; ".join(failed))
            logger.info("bucket precompile complete: %d programs in %.1fs",
                        started, time.perf_counter() - t0)
        return started

    # ---- compile-behind (cold-start) ----------------------------------
    def stop_warms(self) -> None:
        """Stop background compiles (operator shutdown): queued warms are
        dropped; exit waits only for compiles already in flight.  Also stops
        the device-guard recovery probe."""
        self._tpu.stop_warms()
        self._guard.stop()

    def _warm_done(self, sig, seconds: float, err) -> None:
        # this callback runs BEFORE the warm thread clears its own in-flight
        # entry (TpuSolver keeps it until after on_done so watchers that
        # poll compiles_in_flight() down to 0 never miss these metrics);
        # exclude the completing compile from the gauge
        self.registry.gauge(SOLVER_COMPILE_IN_PROGRESS).set(
            max(0, self._tpu.compiles_in_flight() - 1)
        )
        if err is not None:
            # failed compiles stay out of the duration histogram — it
            # documents actual compile cost; TpuSolver arms a per-shape
            # retry backoff so this shape isn't hot-recompiled
            logger.warning("background solver compile failed after %.1fs: %r",
                           seconds, err)
            with self._cold_lock:
                self._warm_errors.append(f"{err!r}"[:500])
        else:
            self.registry.histogram(SOLVER_COMPILE_DURATION).observe(seconds)
            logger.info("solver shape compiled in background (%.1fs); "
                        "subsequent solves of this shape run on-device", seconds)

    def _device_ready(self, st, existing_nodes, max_slots) -> bool:
        """True when the device program for this solve's shape is already
        compiled.  (The background compile for a cold shape is kicked off by
        _start_warm AFTER the fallback solve returns, so the compile thread
        never contends with the caller's own solve.)"""
        sig = self._tpu.signature(
            st, existing_nodes=existing_nodes, max_nodes=max_slots,
            mesh=self.mesh,
        )
        return self._tpu.ready(sig)

    def _start_warm(self, st, existing_nodes, max_slots) -> None:
        """Kick the background compile for a shape that just went cold,
        with snapshot inputs so the live node objects aren't shared with
        the worker thread.  Logged once per shape."""
        if not self.compile_behind or not self._guard.healthy:
            return  # a compile against a wedged device would hang its thread
        started = self._tpu.warm_async(
            st, existing_nodes=[n.snapshot() for n in existing_nodes],
            max_nodes=max_slots, mesh=self.mesh, on_done=self._warm_done,
        )
        if started:
            self.registry.gauge(SOLVER_COMPILE_IN_PROGRESS).set(
                self._tpu.compiles_in_flight()
            )
        sig = self._tpu.signature(
            st, existing_nodes=existing_nodes, max_nodes=max_slots,
            mesh=self.mesh,
        )
        with self._cold_lock:
            first_time = sig not in self._cold_logged
            self._cold_logged.add(sig)
        if first_time:
            logger.info(
                "device program for this solve shape was not compiled yet; "
                "served from the warm tier (compile running in background: "
                "%s)", started or self._tpu.compiling(sig),
            )

    def _cold_solve(
        self, st, tpu_pods, provisioners, instance_types, all_existing,
        daemonsets, unavailable, allow_new_nodes, max_slots, max_new_nodes,
    ):
        """Serve a solve whose device program is still compiling: the native
        C++ tier when it can express the batch (ms-scale, zero warmup — the
        Go-FFD-like cold-start answer), else the CPU oracle."""
        from . import native as native_mod

        if native_mod.available() and not native_mod.has_topology(st):
            res = native_mod.solve_tensors_native(
                st, existing_nodes=all_existing, max_nodes=max_slots,
            )
            return res, "native"
        res = oracle_solve(
            tpu_pods, provisioners, instance_types,
            existing_nodes=all_existing, daemonsets=daemonsets,
            unavailable=unavailable, allow_new_nodes=allow_new_nodes,
            max_new_nodes=max_new_nodes,
        )
        return res, "oracle"

    def _route_small(self, n_pods: int) -> bool:
        """auto-policy: STEADY-STATE batches below the device-dispatch
        crossover are served by the sequential CPU oracle — exact-parity FFD
        at ~ms latency for any constraint shape (r4 weak #3: the native
        tier's small-shape answer was 19-20 nodes where oracle/device pack
        16, and it was serving those batches permanently).  The native tier
        still serves COLD shapes of any size while the device program
        compiles behind (_cold_solve) — that is where its 50k-in-224ms
        speed, not its packing polish, is the right trade."""
        return self.backend == "auto" and n_pods <= self.native_batch_limit

    def _route_hier(self, pods, existing_nodes, allow_new_nodes,
                    max_new_nodes) -> bool:
        """Hierarchical routing gate: flat below ``KT_HIER_THRESHOLD`` pods
        (default 100k), block decomposition at/above it — greenfield
        batches only (no existing nodes, unbounded budget: the delta chain
        and retry waves keep flat's exact placed-snapshot semantics), on a
        healthy device tier, with no device-inexpressible pods (the flat
        path owns that oracle carve-out)."""
        from .hierarchy import hier_threshold

        thr = hier_threshold()
        return (
            thr > 0
            and not getattr(self, "_hier_depth", 0)
            and self.backend in ("auto", "tpu")
            and len(pods) >= thr
            and not existing_nodes
            and allow_new_nodes
            and max_new_nodes is None
            and self._guard.healthy
            and not any(device_inexpressible(p) for p in pods)
        )

    def _route_native(self, st, n_pods: int) -> bool:
        """Forced native backend only.  The auto policy no longer serves
        steady-state batches from the native tier: small batches go to the
        oracle (_route_small, exact parity), large ones to the device; the
        native tier serves cold shapes via _cold_solve."""
        return self.backend == "native"

    def _tensorize(self, pods, provisioners, instance_types, daemonsets,
                   unavailable, trace=NULL_TRACE,
                   **attrs) -> Tuple["object", float]:
        """Host tensorize through the incremental cache (steady-state: a
        lookup plus a counts vector — models/tensorize.TensorizeCache).
        The bucket probe's build goes through here like the solve's hit,
        so the span, the histogram and the hit / miss counters see a
        request's tensors where they are built.  ``attrs`` go on the span.
        Returns (tensors, seconds spent)."""
        t0 = time.perf_counter()
        with trace.span("tensorize", **attrs) as span:
            st, tier = self._tensorize_cache.tensorize(
                pods, provisioners, instance_types,
                daemonsets=daemonsets, unavailable=unavailable,
            )
            span.annotate(tier=tier)
        dt = time.perf_counter() - t0
        self.registry.histogram(TENSORIZE_DURATION).observe(dt)
        if tier in ("identity", "shape"):
            self.registry.counter(TENSORIZE_CACHE_HITS).inc({"tier": tier})
        elif tier == "miss":
            self.registry.counter(TENSORIZE_CACHE_MISSES).inc()
        return st, dt

    def _flight_anomaly(self, reason: str, detail: str, trace) -> None:
        """Hand an anomaly (hang-guard trip, degraded solve) to the flight
        recorder with the in-flight trace, so the dump explains THIS solve,
        not just the ring before it.  Best-effort by contract: this sits on
        the degraded/hang FALLBACK paths, where a failure to record must
        never fail the solve the warm tier is about to serve."""
        try:
            flight = getattr(self.tracer, "flight", None)
            if flight is not None:
                flight.anomaly(reason, detail=detail,
                               trace=trace if trace else None)
        except Exception:  # noqa: BLE001 — observability must not fail solves
            logger.warning("flight-recorder anomaly dump failed (%s)",
                           reason, exc_info=True)

    def _solve_tpu(
        self, pods, provisioners, instance_types, existing_nodes, daemonsets,
        unavailable, allow_new_nodes, max_new_nodes, dispatch=False,
        trace=None,
    ):
        """Device-tier wave.  Returns a SolveResult — or, when ``dispatch``
        is set and the batch takes the plain already-compiled device path
        with no oracle carve-outs, a :class:`_PendingWave` whose ``finish``
        fences the async dispatch (the pipelined-overlap window lives
        between the two)."""
        trace = trace or NULL_TRACE
        # carve out pods the device solver can't express (rare shapes
        # only — but finding that out is two passes over the batch)
        with trace.span("carve"):
            tpu_pods = [p for p in pods if not device_inexpressible(p)]
            cpu_pods = [p for p in pods if device_inexpressible(p)]

        # positive affinity couples the two batches: whichever side's
        # affinity selectors match the other side's pods must solve SECOND,
        # so the counts it co-locates against already exist.  Default (and
        # tie-break) is device-first, oracle against its result.
        def _refers(src, dst):
            sels = [t.label_selector for p in src for t in p.affinity_terms
                    if not t.anti]
            return any(s.matches(q.labels) for s in sels for q in dst)

        cpu_first = bool(cpu_pods and tpu_pods
                         and _refers(tpu_pods, cpu_pods)
                         and not _refers(cpu_pods, tpu_pods))

        # placed-snapshot chaining: each stage solves against the previous
        # stage's PLACED existing snapshots (+ placed prior new nodes), and
        # the placed copies replace the prior references afterwards — see
        # _merge for the cross-wave bookkeeping rationale
        cur_existing: List[SimNode] = list(existing_nodes)
        nodes: List[SimNode] = []
        assignments: Dict[str, str] = {}
        infeasible: Dict[str, str] = {}
        solve_ms = 0.0
        tensorize_ms = 0.0
        served_cold = False

        def chain(res: SolveResult) -> None:
            """Adopt a stage's placed snapshots of (cur_existing + nodes)."""
            nonlocal cur_existing, nodes
            cur_existing, nodes = _adopt_placed(cur_existing, res)

        if cpu_first:
            res0 = oracle_solve(
                cpu_pods, provisioners, instance_types,
                existing_nodes=cur_existing, daemonsets=daemonsets,
                unavailable=unavailable, allow_new_nodes=allow_new_nodes,
                max_new_nodes=max_new_nodes,
            )
            chain(res0)
            assignments.update(res0.assignments)
            infeasible.update(res0.infeasible)
            solve_ms += res0.solve_ms
            cpu_pods = []
            if max_new_nodes is not None:
                max_new_nodes = max(0, max_new_nodes - len(res0.nodes))

        def _tail() -> SolveResult:
            """cpu-carve-out epilogue + result assembly — shared verbatim by
            the synchronous return and the async wave's finish."""
            nonlocal cur_existing, nodes, solve_ms
            if cpu_pods:
                t0c = time.perf_counter()
                res2 = oracle_solve(
                    cpu_pods, provisioners, instance_types,
                    existing_nodes=list(cur_existing) + nodes,
                    daemonsets=daemonsets, unavailable=unavailable,
                    allow_new_nodes=allow_new_nodes,
                    max_new_nodes=None if max_new_nodes is None else max(0, max_new_nodes - len(nodes)),
                )
                self.registry.histogram(SOLVER_BACKEND_DURATION).observe(
                    time.perf_counter() - t0c, {"backend": "oracle"}
                )
                chain(res2)
                assignments.update(res2.assignments)
                infeasible.update(res2.infeasible)
                solve_ms += res2.solve_ms
            return SolveResult(
                nodes=nodes,
                assignments=assignments,
                infeasible=infeasible,
                existing_nodes=cur_existing,
                solve_ms=solve_ms,
                tensorize_ms=tensorize_ms,
                served_cold=served_cold,
            )

        if not tpu_pods:
            return _tail()

        st, tsec = self._tensorize(
            tpu_pods, provisioners, instance_types, daemonsets, unavailable,
            trace=trace)
        tensorize_ms += tsec * 1000.0
        t0 = time.perf_counter()
        new_budget = len(tpu_pods) if max_new_nodes is None else max_new_nodes
        all_existing = list(cur_existing) + nodes
        max_slots = len(all_existing) + new_budget

        def _adopt_device(res: SolveResult, backend_used: str) -> SolveResult:
            """Post-device bookkeeping (metrics, what-if filtering, chain) —
            identical for the sync and async returns."""
            nonlocal solve_ms
            trace.annotate(backend_used=backend_used)
            self.registry.histogram(SOLVER_BACKEND_DURATION).observe(
                time.perf_counter() - t0, {"backend": backend_used}
            )
            if not allow_new_nodes and res.nodes:
                # consolidation what-if with no new nodes allowed: pods that
                # needed new nodes are infeasible
                for n in res.nodes:
                    for p in n.pods:
                        infeasible[p.name] = "needs a new node (disallowed)"
                res.nodes = []
                for p in list(res.assignments):
                    if p in infeasible:
                        del res.assignments[p]
            chain(res)
            assignments.update(res.assignments)
            infeasible.update(res.infeasible)
            solve_ms += res.solve_ms
            return _tail()

        def _cold_fallback() -> Tuple[SolveResult, str]:
            """Warm-tier serve for a still-compiling shape (transient: the
            reseat epilogue skips it so the cold path keeps its latency
            contract; the device program takes over once compiled)."""
            nonlocal served_cold
            res, backend_used = self._cold_solve(
                st, tpu_pods, provisioners, instance_types, all_existing,
                daemonsets, unavailable, allow_new_nodes, max_slots,
                max_new_nodes,
            )
            served_cold = True
            trace.annotate(served_cold=True)
            self.registry.counter(SOLVER_COLD_FALLBACKS).inc(
                {"backend": backend_used}
            )
            self._start_warm(st, all_existing, max_slots)
            return res, backend_used

        def _degraded_fallback() -> Tuple[SolveResult, str]:
            """Warm-tier serve while the device tier is latched unhealthy.
            NOT a cold-start fallback (the program was compiled, the device
            was not answering — distinct counter so outage traffic can't
            pollute cold-start SLOs) and NOT flagged served_cold: degraded
            answers provision real long-lived nodes (nothing supersedes
            them when a compile lands), so they keep the reseat polish.
            No _start_warm either: a background compile against a wedged
            device would hang its warm thread too."""
            res, backend_used = self._cold_solve(
                st, tpu_pods, provisioners, instance_types, all_existing,
                daemonsets, unavailable, allow_new_nodes, max_slots,
                max_new_nodes,
            )
            self.registry.counter(SOLVER_DEGRADED_SOLVES).inc(
                {"backend": backend_used}
            )
            trace.annotate(degraded=True)
            self._flight_anomaly(
                "degraded_solve",
                f"device tier latched unhealthy; {len(tpu_pods)}-pod batch "
                f"served by the warm {backend_used} tier", trace)
            return res, backend_used

        if self._route_native(st, len(tpu_pods)):
            from . import native as native_mod

            res = native_mod.solve_tensors_native(
                st, existing_nodes=all_existing, max_nodes=max_slots,
            )
            return _adopt_device(res, "native")
        if self.backend == "auto" and not self._device_ready(
            st, all_existing, max_slots
        ):
            # compile-behind: the device program for this shape is not
            # compiled yet; serve this solve from the warm tier so the
            # caller never eats the XLA stall, then _start_warm (inside
            # _cold_fallback, after the fallback returns) kicks the
            # background compile
            res, backend_used = _cold_fallback()
            return _adopt_device(res, backend_used)

        guarded = self.backend == "auto" and self._guard.enabled
        degraded = guarded and not self._guard.healthy
        raise_on_exhaust = self.backend == "auto" and self.compile_behind

        collector = self._mega_collect
        if dispatch and not degraded and collector is not None:
            # megabatch registration (submit_many): the first device wave
            # joins the collector's pending batch instead of dispatching;
            # ONE vmapped device call later serves every slot (SHARDED over
            # the mesh's chips for a meshed scheduler).  The fallback
            # ladder at fence time is identical to the single async path —
            # per REQUEST, so one exhausted/hung slot degrades itself only.
            slot = collector.add(
                st=st, existing_nodes=all_existing, max_nodes=max_slots,
                raise_on_exhaust=raise_on_exhaust, trace=trace,
            )

            def _finish_mega() -> SolveResult:
                try:
                    out = slot.result()
                    return _adopt_device(out.result, "tpu")
                except SlotsExhausted:
                    res, backend_used = _cold_fallback()
                    return _adopt_device(res, backend_used)
                except DeviceHang:
                    self._flight_anomaly(
                        "device_hang", "megabatch device dispatch hung past "
                        "the guard deadline", trace)
                    res, backend_used = _degraded_fallback()
                    return _adopt_device(res, backend_used)

            return _PendingWave(_finish_mega)

        if dispatch and not degraded:
            # async dispatch: enqueue the device program WITHOUT fencing and
            # hand the fence back as a _PendingWave — the caller (submit /
            # SolvePipeline) tensorizes batch N+1 in the window between
            # dispatch and finish while this batch executes on the device.
            # The fallback ladder (slots-exhausted → warm tier, hang →
            # degraded tier) runs at fence time, identical to the sync path;
            # the dispatch itself is guarded too (an H2D transfer can hang
            # inside the runtime exactly like the fence).
            def _dispatch_call():
                return self._tpu.solve_async(
                    st, existing_nodes=all_existing, max_nodes=max_slots,
                    mesh=self.mesh, raise_on_exhaust=raise_on_exhaust,
                    trace=trace,
                )

            try:
                pending = (self._guard.run(_dispatch_call) if guarded
                           else _dispatch_call())
            except DeviceHang:
                self._flight_anomaly(
                    "device_hang", "H2D dispatch hung past the guard "
                    "deadline", trace)
                res, backend_used = _degraded_fallback()
                return _adopt_device(res, backend_used)

            def _finish_wave() -> SolveResult:
                try:
                    out = (self._guard.run(pending.result) if guarded
                           else pending.result())
                    return _adopt_device(out.result, "tpu")
                except SlotsExhausted:
                    res, backend_used = _cold_fallback()
                    return _adopt_device(res, backend_used)
                except DeviceHang:
                    self._flight_anomaly(
                        "device_hang", "device fence hung past the guard "
                        "deadline", trace)
                    res, backend_used = _degraded_fallback()
                    return _adopt_device(res, backend_used)

            return _PendingWave(_finish_wave)

        def _device_call():
            return self._tpu.solve(
                st, existing_nodes=all_existing, max_nodes=max_slots,
                mesh=self.mesh, raise_on_exhaust=raise_on_exhaust,
                trace=trace,
            )

        if not degraded:
            try:
                out = (self._guard.run(_device_call) if guarded
                       else _device_call())
                return _adopt_device(out.result, "tpu")
            except SlotsExhausted:
                # the optimistic node-slot axis ran out and the full-budget
                # program is cold: serve from the warm tier now, compile the
                # full program behind (the solver remembered the exhaustion,
                # so _start_warm targets it)
                res, backend_used = _cold_fallback()
                return _adopt_device(res, backend_used)
            except DeviceHang:
                # the guard latched the device tier unhealthy; serve THIS
                # batch from the warm tier like every batch until the
                # recovery probe succeeds
                self._flight_anomaly(
                    "device_hang", "device solve hung past the guard "
                    "deadline", trace)
        res, backend_used = _degraded_fallback()
        return _adopt_device(res, backend_used)
