"""Per-solve span tracing.

The pipelined solver made a solve's latency a composite — batcher window,
tensorize-cache tier, H2D dispatch, device fence, reseat/repair — but the
aggregate histograms cannot explain a SINGLE slow or degraded solve after
the fact.  A :class:`Tracer` produces one :class:`Trace` per solve: a tree
of named :class:`Span`\\ s (``window`` → ``tensorize`` → ``dispatch`` →
``fence`` → ``reseat`` → ``respond``) carrying attributes (backend, cache
tier, ``served_cold``, batch size, cost), timestamped through the injectable
:class:`~karpenter_tpu.utils.clock.Clock` so FakeClock tests are
deterministic (and KT002 stays clean).

Design constraints, in order:

- **Near-zero cost when sampling is off.**  ``Tracer.start`` returns the
  :data:`NULL_TRACE` singleton when disabled/unsampled; every span call on
  it is a constant no-op, so the hot path pays one attribute check.
- **Thread-crossing solves.**  A pipelined solve opens its root on the RPC
  thread, its dispatch/fence spans on the dispatcher thread, and may fence
  on the hang guard's expendable thread.  Nesting is tracked with one
  per-thread stack of open spans and phases, every trace's: a span's parent
  is the innermost open span of ITS trace on the thread, the root when
  there is none.  Already-elapsed cross-thread phases (the pipeline
  queue wait) are attached with :meth:`Trace.record`, which never leaves a
  span open.
- **Lock discipline.**  The span tree is mutated from multiple threads and
  read mid-solve by the flight recorder's anomaly dumps; all tree state is
  ``# guarded-by:`` the trace lock (KT004) and ``to_dict`` snapshots under
  it.
- **Self time.**  Spans nest, so durations overlap; when a trace finishes
  every closed span's duration minus the part of its own interval its
  children cover (their union, clipped: children run on other threads and
  recorded children may start before the root) is counted into
  ``karpenter_trace_span_self_seconds_total{span}``.  Inside one root the
  self times sum to the root's duration (siblings that run side by side on
  two threads each keep the shared time, so they add it once more).
- **One clock with the profiler.**  A span opened with :meth:`Trace.span`
  and every :meth:`Tracer.phase` also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so a profiler session shows
  the program's spans on the host plane beside the device's operations.
  This module never imports jax: the annotation class is taken from
  ``sys.modules`` and only exists where jax is already loaded.  The root is
  not mirrored (it would cover every device gap and name them all
  ``solve``).
- **Collector pauses.**  The first enabled tracer of a process registers
  one ``gc.callbacks`` entry; pauses are counted into
  ``karpenter_process_gc_pause_seconds_total{generation}`` of every
  registry that has an enabled tracer, and generation-2 pauses are mirrored
  as ``gc_gen2``.  No span per collection.  Each pause is also put down to
  the innermost span or phase open on the thread it ran on
  (``karpenter_trace_span_gc_pause_seconds_total{span}``, ``none`` outside
  any): the callback reads this thread's stack of open spans and adds to a
  plain dict, which reaches the registries when a trace finishes.  An
  enabled tracer also registers ``karpenter_process_allocated_blocks``
  (what the heap holds), read when the registry is scraped.
- **Context-manager lifecycle (KT007).**  ``with tracer.start(...) as
  trace:`` / ``with trace.span(...):`` are the only blessed forms — a bare
  ``Tracer.start()`` leaks an open trace on any exception path, and ktlint
  rule KT007 flags it.
"""

from __future__ import annotations

import gc
import itertools
import logging
import os
import sys
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from ..metrics import (
    ALLOCATED_BLOCKS,
    GC_GENERATIONS,
    GC_PAUSE_SECONDS,
    GC_SPAN_PAUSE_SECONDS,
    GC_SPANS_ZEROED,
    TRACE_REMOTE_OUTCOMES,
    TRACE_REMOTE_SPANS,
    TRACE_SPAN_DURATION,
    TRACE_SPAN_SELF,
    TRACE_TRACES,
    Registry,
    registry as default_registry,
)
from ..utils.clock import Clock

#: hard per-trace span cap: a runaway retry ladder must not grow one trace
#: without bound (spans past the cap are dropped and counted on the root)
MAX_SPANS_PER_TRACE = 512

_TRACE_IDS = itertools.count(1)


def _annotate(name: str):
    """An entered ``jax.profiler.TraceAnnotation(name)`` where jax is
    already loaded in this process, else None.  With no profiler session
    the annotation is a check of one flag; a process that never imports
    jax (the operator, a benchmark client) pays one dict lookup."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)
    if cls is None:
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def _end(ann) -> None:
    """Close what :func:`_annotate` gave (None where there was no jax)."""
    if ann is not None:
        ann.__exit__(None, None, None)


#: the spans and phases open on this thread, innermost last, whatever trace
#: each belongs to: a span's parent is found here (Trace.span) and the
#: collector's callback reads the innermost one.  An entry that ended on
#: another thread than it began on (the door's ``await_request``) stays
#: until the next push prunes it; readers skip what has ended (``t1`` set)
_HERE = threading.local()


def _open_here() -> list:
    st = getattr(_HERE, "open", None)
    if st is None:
        st = _HERE.open = []
    return st


def _entered(what) -> None:
    """``what`` (a span or a phase) is open on this thread from now on."""
    st = _open_here()
    while st and st[-1].t1 is not None:
        st.pop()
    st.append(what)


def _left(what) -> None:
    """``what`` is no longer open on this thread.  Two traces' spans may
    interleave on one thread (the dispatcher's), so only ``what`` goes."""
    st = _open_here()
    if st and st[-1] is what:
        st.pop()
    elif what in st:
        st.remove(what)


class _GcWatch:
    """The process's one ``gc.callbacks`` entry.  Pause seconds go to every
    registry added (weakly held: a test's private registry dies with it):
    by generation at once, by the span they stopped at the next
    :meth:`flush` (a trace's finish)."""

    def __init__(self) -> None:
        self._registries: "weakref.WeakSet[Registry]" = weakref.WeakSet()
        self._lock = threading.Lock()
        self._t0 = 0.0
        self._ann = None
        self._labels = [{"generation": g} for g in GC_GENERATIONS]
        #: span -> pause seconds since the process began; the callback is
        #: its only writer, under no lock (collections do not nest)
        self._by_span: Dict[str, float] = {}
        self._flushed: Dict[str, float] = {}  # guarded-by: _lock

    def add(self, registry: Registry) -> None:
        counter = registry.counter(GC_PAUSE_SECONDS)
        for labels in self._labels:
            counter.inc(labels, value=0.0)
        by_span = registry.counter(GC_SPAN_PAUSE_SECONDS)
        for span in GC_SPANS_ZEROED:
            by_span.inc({"span": span}, value=0.0)
        # what was paused before belongs to the registries of before
        self.flush()
        with self._lock:
            self._registries.add(registry)
            if self._callback not in gc.callbacks:
                gc.callbacks.append(self._callback)

    def flush(self) -> None:
        """Hand the registries what the callback has put down to each span
        since the last flush.  Totals are kept and their rise handed on, so
        a pause that lands while this runs is in the next one."""
        with self._lock:
            # copy(): one allocation before the copying starts, so a
            # collection (and with it the callback) cannot land in the
            # middle of the walk
            for span, total in self._by_span.copy().items():
                moved = total - self._flushed.get(span, 0.0)
                if moved <= 0.0:
                    continue
                self._flushed[span] = total
                labels = {"span": span}
                for registry in list(self._registries):
                    # span names are runtime data: add() zero-initialises
                    # the ones a metric selects by name
                    registry.counter(GC_SPAN_PAUSE_SECONDS).inc(
                        labels, value=moved)

    def _callback(self, phase: str, info: dict) -> None:
        # collections do not nest and a start/stop pair runs on one thread
        gen = info.get("generation", 0)
        if phase == "start":
            if gen == 2:
                self._ann = _annotate("gc_gen2")
            self._t0 = time.perf_counter()
            return
        pause = time.perf_counter() - self._t0
        _end(self._ann)
        self._ann = None
        labels = self._labels[min(gen, len(self._labels) - 1)]
        for registry in list(self._registries):
            registry.counter(GC_PAUSE_SECONDS).inc(labels, value=pause)
        span = "none"
        for entry in reversed(getattr(_HERE, "open", ())):
            if entry.t1 is None:
                span = entry.name
                break
        self._by_span[span] = self._by_span.get(span, 0.0) + pause


_GC_WATCH = _GcWatch()


def replica_id() -> str:
    """This process's stable trace-origin identity: ``KT_REPLICA_ID`` (the
    deploy sets the pod name — the same identity the session-lease
    protocol uses) or a host-pid fallback.  Trace ids are PREFIXED with it
    (``replica-0-t000042``) so two replicas' locally-minted ids can never
    collide and a forwarded / failed-over hop joins exactly its parent's
    tree in the /fleetz merge.  Read per call, not at import: in-process
    fleet harnesses construct replicas under different env."""
    env = os.environ.get("KT_REPLICA_ID", "")
    if env:
        return env
    import socket

    return f"{socket.gethostname()}-{os.getpid()}"


class Span:
    """One timed, attributed phase of a trace.  Obtained from
    :meth:`Trace.span` (context manager) or :meth:`Trace.record`
    (pre-closed); never constructed directly by instrumentation."""

    __slots__ = ("name", "span_id", "t0", "t1", "attrs", "children",
                 "_trace", "_ann")

    def __init__(self, trace: "Trace", name: str, t0: float,
                 attrs: Optional[dict] = None, span_id: str = "") -> None:
        self.name = name
        #: trace-local id (``s1`` = root, ``s2``...), carried on the wire
        #: as ``parent_span`` so a remote child hop can attach under THIS
        #: span in the /fleetz cross-replica tree
        self.span_id = span_id
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs: Dict[str, object] = dict(attrs or ())
        self.children: List["Span"] = []  # guarded-by the owning trace lock
        self._trace = trace
        #: the profiler annotation mirroring a live span (Trace.span)
        self._ann = None

    @property
    def done(self) -> bool:
        return self.t1 is not None

    @property
    def duration_s(self) -> float:
        return 0.0 if self.t1 is None else max(0.0, self.t1 - self.t0)

    def annotate(self, **attrs) -> "Span":
        self._trace._annotate_span(self, attrs)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self._trace._annotate_span(self, {"error": repr(exc)})
        self._trace._close_span(self)
        return False  # never swallow

    def _to_dict_locked(self) -> dict:
        """Serialize (caller holds the trace lock; see Trace.to_dict)."""
        out: dict = {
            "name": self.name,
            "span_id": self.span_id,
            "start": self.t0,
            "end": self.t1,
            "duration_ms": (None if self.t1 is None
                            else round(self.duration_s * 1000.0, 3)),
        }
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["spans"] = [c._to_dict_locked() for c in self.children]
        return out


class _NullSpan:
    """Do-nothing span: the entire cost of tracing while sampling is off."""

    __slots__ = ()

    name = ""
    span_id = ""
    attrs: dict = {}
    children: list = []
    done = True
    duration_s = 0.0

    def annotate(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _NullTrace:
    """Do-nothing trace returned by a disabled/unsampled ``Tracer.start``.
    Falsy, so instrumentation can write ``trace = trace or NULL_TRACE`` and
    branch on ``if trace:`` where it matters."""

    __slots__ = ()

    trace_id = ""
    name = ""
    duration_s = 0.0

    def __bool__(self) -> bool:
        return False

    def now(self) -> float:
        return 0.0

    def span(self, name: str, **attrs) -> _NullSpan:
        return NULL_SPAN

    def record(self, name: str, t0: float, t1: float, **attrs) -> _NullSpan:
        return NULL_SPAN

    def annotate(self, **attrs) -> None:
        return None

    def wire_context(self) -> "tuple[str, str]":
        """No context crosses the wire for an unsampled/disabled trace —
        the remote side roots locally (counted ``local``)."""
        return ("", "")

    def spans(self) -> list:
        return []

    def span_names(self) -> list:
        return []

    def to_dict(self) -> dict:
        return {}

    def __enter__(self) -> "_NullTrace":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NULL_TRACE = _NullTrace()


class _Phase:
    """A phase outside any open span (:meth:`Tracer.phase`): timed on the
    tracer's clock and mirrored on the profiler's host plane while it
    runs.  ``t0``/``t1`` are for :meth:`Trace.record` once the trace it
    belongs to is open; a ``detached`` phase belongs to none and is
    observed into the span families when it exits."""

    __slots__ = ("name", "t0", "t1", "_tracer", "_detached", "_ann")

    def __init__(self, tracer: "Tracer", name: str, detached: bool) -> None:
        self.name = name
        self.t0 = 0.0
        self.t1: Optional[float] = None  # while it is open, like a span's
        self._tracer = tracer
        self._detached = detached
        self._ann = None

    def annotate(self, **attrs) -> None:
        """Attributes of the phase, kept where the phase is kept: on the
        profiler's event."""
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "_Phase":
        self._ann = _annotate(self.name)
        _entered(self)
        self.t0 = self._tracer.clock.now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = self._tracer.clock.now()
        _left(self)
        _end(self._ann)
        self._ann = None
        if self._detached:
            self._tracer._observe(self.name, self.t1 - self.t0,
                                  self.t1 - self.t0)
        return False


class _NullPhase:
    """Do-nothing phase of a disabled tracer."""

    __slots__ = ()

    name = ""
    t0 = t1 = 0.0

    def annotate(self, **attrs) -> None:
        return None

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NULL_PHASE = _NullPhase()


class Trace:
    """One solve's span tree.  Context manager: exiting closes the root and
    hands the finished trace to the tracer (metrics + flight recorder)."""

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[dict] = None,
                 trace_id: Optional[str] = None) -> None:
        self._tracer = tracer
        self._clock = tracer.clock
        # replica-prefixed so two replicas' locally-minted ids can never
        # collide in a fleet merge; a remote-parented trace ADOPTS the
        # origin's id instead (Tracer.start_remote) — one request, one id
        self.trace_id = (trace_id
                         or f"{tracer.replica}-t{next(_TRACE_IDS):06d}")
        self.name = name
        self._lock = threading.Lock()
        self._n_spans = 1           # guarded-by: _lock
        self._n_dropped = 0         # guarded-by: _lock
        self.root = Span(self, name, self._clock.now(), attrs, span_id="s1")

    # ---- time -----------------------------------------------------------
    def now(self) -> float:
        """The trace's clock (so callers on other threads timestamp
        cross-thread phases consistently with the span tree)."""
        return self._clock.now()

    @property
    def duration_s(self) -> float:
        return self.root.duration_s

    # ---- span lifecycle -------------------------------------------------
    def _innermost(self) -> Span:
        """This thread's innermost open span of this trace (the root when
        it has none)."""
        for entry in reversed(_open_here()):
            if getattr(entry, "_trace", None) is self and entry.t1 is None:
                return entry
        return self.root

    def span(self, name: str, **attrs):
        """Open a child span under this thread's innermost open span (the
        root when none).  Use as ``with trace.span("tensorize") as sp:``."""
        parent = self._innermost()
        with self._lock:
            if self._n_spans >= MAX_SPANS_PER_TRACE:
                self._n_dropped += 1
                self.root.attrs["spans_dropped"] = self._n_dropped
                return NULL_SPAN
            self._n_spans += 1
            sp = Span(self, name, self._clock.now(), attrs,
                      span_id=f"s{self._n_spans}")
            parent.children.append(sp)
        _entered(sp)
        sp._ann = _annotate(name)
        return sp

    def record(self, name: str, t0: float, t1: float, **attrs):
        """Attach an already-elapsed span (cross-thread phases — e.g. the
        pipeline queue wait, timestamped on the RPC thread and recorded by
        the dispatcher).  The span is born closed, so no context manager is
        needed and nothing can leak."""
        with self._lock:
            if self._n_spans >= MAX_SPANS_PER_TRACE:
                self._n_dropped += 1
                self.root.attrs["spans_dropped"] = self._n_dropped
                return NULL_SPAN
            self._n_spans += 1
            sp = Span(self, name, t0, attrs, span_id=f"s{self._n_spans}")
            sp.t1 = t1
            self.root.children.append(sp)
        return sp

    def _close_span(self, span: Span) -> None:
        _end(span._ann)
        span._ann = None
        with self._lock:
            if span.t1 is None:
                span.t1 = self._clock.now()
        _left(span)

    def _annotate_span(self, span: Span, attrs: dict) -> None:
        with self._lock:
            span.attrs.update(attrs)

    def annotate(self, **attrs) -> None:
        """Attach attributes to the root span (backend, batch size, cost,
        served_cold, ...)."""
        self._annotate_span(self.root, attrs)

    def wire_context(self) -> "tuple[str, str]":
        """The ``(trace_id, parent_span)`` pair a wire-crossing send site
        attaches to its request (ktlint KT019 pins the discipline): the
        remote side opens its child trace under this thread's innermost
        OPEN span (the root when none), so the hop lands exactly where
        the RPC happened in the tree."""
        return (self.trace_id, self._innermost().span_id)

    # ---- completion / introspection -------------------------------------
    def finish(self) -> "Trace":
        with self._lock:
            if self.root.t1 is None:
                self.root.t1 = self._clock.now()
        return self

    def spans(self) -> List[Span]:
        """Flat snapshot of every span (root first, depth-first)."""
        with self._lock:
            out: List[Span] = []
            stack = [self.root]
            while stack:
                sp = stack.pop()
                out.append(sp)
                stack.extend(reversed(sp.children))
            return out

    def span_names(self) -> List[str]:
        return [sp.name for sp in self.spans()]

    def closed_spans(self) -> List[Tuple[str, float, float]]:
        """``(name, duration_s, self_s)`` of every closed span.  Self time
        is the duration minus the union of the closed children's intervals
        clipped to the span's own: children on other threads overlap, and a
        recorded child may lie before its parent (it then covers nothing of
        it and keeps its whole duration as its own)."""
        out: List[Tuple[str, float, float]] = []
        with self._lock:
            stack = [self.root]
            while stack:
                sp = stack.pop()
                stack.extend(sp.children)
                if sp.t1 is None:
                    continue
                covered, edge = 0.0, sp.t0
                for c0, c1 in sorted((c.t0, min(c.t1, sp.t1))
                                     for c in sp.children
                                     if c.t1 is not None):
                    if c1 > edge:
                        covered += c1 - max(c0, edge)
                        edge = c1
                dur = sp.duration_s
                out.append((sp.name, dur, max(0.0, dur - covered)))
        return out

    def to_dict(self) -> dict:
        """JSON-ready snapshot; safe to call mid-solve (anomaly dumps
        serialize in-flight traces — open spans carry ``end: null``)."""
        with self._lock:
            return {"trace_id": self.trace_id, **self.root._to_dict_locked()}

    def __enter__(self) -> "Trace":
        # the root is open on the thread that runs the ``with``
        _entered(self.root)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.annotate(error=repr(exc))
        # the root goes, and with it whatever of this trace was opened on
        # this thread and never closed
        here = _open_here()
        here[:] = [e for e in here if getattr(e, "_trace", None) is not self]
        self._tracer._finish(self)
        return False


class Tracer:
    """Trace factory + completion sink.

    ``enabled`` defaults from ``KT_TRACE`` (``0`` disables — the hot path
    then costs one attribute check per solve); ``sample_every`` (from
    ``KT_TRACE_SAMPLE_EVERY``) keeps one trace in every N starts, for
    high-rate deployments where even ring churn matters.  Finished traces
    are counted (``karpenter_trace_traces_total``), their spans observed
    into ``karpenter_trace_span_duration_seconds{span=...}`` and
    ``karpenter_trace_span_self_seconds_total{span=...}``, and handed to
    the attached :class:`~karpenter_tpu.obs.recorder.FlightRecorder`.
    """

    def __init__(
        self,
        clock: Optional[Clock] = None,
        registry: Optional[Registry] = None,
        flight=None,
        enabled: Optional[bool] = None,
        sample_every: Optional[int] = None,
    ) -> None:
        self.clock = clock or Clock()
        self.registry = registry or default_registry
        self.flight = flight
        if enabled is None:
            enabled = os.environ.get("KT_TRACE", "1") != "0"
        self.enabled = enabled
        if sample_every is None:
            sample_every = int(os.environ.get("KT_TRACE_SAMPLE_EVERY", "1"))
        self.sample_every = max(1, sample_every)
        #: this tracer's trace-id prefix + the replica_id attr every
        #: adopted hop carries (captured at construction: in-process fleet
        #: harnesses build replicas under different KT_REPLICA_ID env)
        self.replica = replica_id()
        self._lock = threading.Lock()
        self._n_started = 0  # guarded-by: _lock
        #: finished-trace sinks beyond the flight recorder (the occupancy
        #: accountant subscribes here); each called with the closed trace
        self._sinks: List = []
        # zero-init so the series exists from the first scrape (KT003), and
        # register the span-duration family so the documented metric is
        # visible before the first trace completes
        self.registry.counter(TRACE_TRACES).inc(value=0.0)
        remote = self.registry.counter(TRACE_REMOTE_SPANS)
        for outcome in TRACE_REMOTE_OUTCOMES:
            remote.inc({"outcome": outcome}, value=0.0)
        self.registry.histogram(TRACE_SPAN_DURATION)
        self.registry.counter(TRACE_SPAN_SELF).inc(value=0.0)
        if self.enabled:
            _GC_WATCH.add(self.registry)
            # a walk over the heap's pools (4 ms at 24M blocks): read when
            # a scraper asks, never on a request's path
            self.registry.at_scrape(ALLOCATED_BLOCKS, sys.getallocatedblocks)

    def phase(self, name: str, detached: bool = False):
        """Time a phase that no open span can hold — the RPC's door, before
        the root opens and after it has closed — as ``with
        tracer.phase("request_decode") as ph:``.  The caller attaches it to
        the request's tree with ``trace.record(ph.name, ph.t0, ph.t1)``;
        ``detached=True`` is for a phase whose trace has already finished
        (gRPC serialises the reply after the handler returns): it lands in
        the span families alone.  :data:`NULL_PHASE` when disabled."""
        if not self.enabled:
            return NULL_PHASE
        return _Phase(self, name, detached)

    def _observe(self, name: str, duration_s: float, self_s: float) -> None:
        labels = {"span": name}
        self.registry.histogram(TRACE_SPAN_DURATION).observe(
            max(0.0, duration_s), labels)
        # span names are runtime data: the zero-init is the unlabeled sample
        self.registry.counter(TRACE_SPAN_SELF).inc(
            labels, value=max(0.0, self_s))

    def start(self, name: str, **attrs):
        """Begin a trace — ALWAYS as ``with tracer.start(...) as trace:``
        (ktlint KT007 flags bare starts).  Returns :data:`NULL_TRACE` when
        disabled or unsampled."""
        if not self.enabled:
            return NULL_TRACE
        with self._lock:
            self._n_started += 1
            sampled = self._n_started % self.sample_every == 0
        if not sampled:
            return NULL_TRACE
        return Trace(self, name, attrs)

    def start_remote(self, name: str, trace_id: str, parent_span: str,
                     **attrs):
        """Begin a trace that may ADOPT a remote parent — the server-entry
        facade (ktlint KT019: every entry that decodes a wire trace
        context must open its trace through here; KT007 covers the
        context-manager form).  With a non-empty ``trace_id`` the trace
        joins the remote tree: it reuses the ORIGIN's trace id (so the
        /fleetz merge groups the hops into one tree), records the parent
        span id + this replica's identity on its root, and BYPASSES
        sampling — the origin already made the sampling decision, and a
        half-sampled tree is worse than none.  With an empty ``trace_id``
        (old client, direct call, unsampled origin) this is exactly
        :meth:`start`.  Counted into
        ``karpenter_trace_remote_spans_total{outcome}`` per trace actually
        opened."""
        if not self.enabled:
            return NULL_TRACE
        if not trace_id:
            trace = self.start(name, **attrs)
            if trace:
                self.registry.counter(TRACE_REMOTE_SPANS).inc(
                    {"outcome": "local"})
            return trace
        attrs = dict(attrs)
        attrs["replica_id"] = self.replica
        if parent_span:
            attrs["remote_parent"] = parent_span
        self.registry.counter(TRACE_REMOTE_SPANS).inc(
            {"outcome": "adopted"})
        return Trace(self, name, attrs, trace_id=trace_id)

    def add_sink(self, sink) -> None:
        """Subscribe ``sink(trace)`` to every finished trace (append-only
        list read without the lock — sinks are wired at service
        construction, before traffic)."""
        self._sinks.append(sink)

    def remove_sink(self, sink) -> None:
        try:
            self._sinks.remove(sink)
        except ValueError:
            pass

    def _finish(self, trace: Trace) -> None:
        trace.finish()
        self.registry.counter(TRACE_TRACES).inc()
        for name, duration_s, self_s in trace.closed_spans():
            self._observe(name, duration_s, self_s)
        _GC_WATCH.flush()
        for sink in self._sinks:
            try:
                sink(trace)
            except Exception:  # noqa: BLE001 — same contract as the flight
                # recorder below: observers never fail the solve path
                logging.getLogger(__name__).warning(
                    "trace sink failed for %s", trace.trace_id,
                    exc_info=True)
        if self.flight is not None:
            try:
                self.flight.add(trace)
            except Exception:  # noqa: BLE001 — runs in Trace.__exit__ on the
                # solve path; a recorder failure must not fail the solve
                logging.getLogger(__name__).warning(
                    "flight recorder rejected trace %s", trace.trace_id,
                    exc_info=True)
