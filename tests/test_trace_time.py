"""Naming the time (ISSUE 25): self time for every span, the spans at the
sidecar's door and on the client, the tracer's spans on the profiler's
clock, collector pauses — and all of it off under ``KT_TRACE=0``.  ISSUE 37:
the leaves beneath ``bucket`` and ``extract``, the tensorize miss counted
where it is built, the collector's pauses by the span they stop, what the
heap holds, and the door's ``await_request``."""

import gc
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from karpenter_tpu.metrics import (
    ALLOCATED_BLOCKS,
    GC_PAUSE_SECONDS,
    GC_SPAN_PAUSE_SECONDS,
    GC_SPANS_ZEROED,
    REQUEST_CATALOG,
    SLO_LATENCY,
    TENSORIZE_CACHE_HITS,
    TENSORIZE_CACHE_MISSES,
    TRACE_SPAN_DURATION,
    TRACE_SPAN_SELF,
    Registry,
)
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.pod import PodSpec
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.obs import FlightRecorder, Tracer
from karpenter_tpu.obs import trace as trace_mod
from karpenter_tpu.obs.trace import NULL_PHASE
from karpenter_tpu.service import codec
from karpenter_tpu.service.client import DeltaSession, RemoteScheduler
from karpenter_tpu.service.server import (
    KeptCatalogs,
    SolverService,
    _Door,
    make_server,
)
from karpenter_tpu.solver.scheduler import BatchScheduler
from karpenter_tpu.utils.clock import FakeClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOOR = ("request_parse", "request_decode", "response_serialize")


def batch(n=5, app="a"):
    return [PodSpec(name=f"{app}-{i}", labels={"app": app},
                    requests={"cpu": 0.5, "memory": GIB}, owner_key=app)
            for i in range(n)]


def fake_tracer():
    clock, reg = FakeClock(100.0), Registry()
    return clock, reg, Tracer(clock=clock, registry=reg,
                              flight=FlightRecorder(clock=clock,
                                                    registry=reg))


def self_s(reg, span):
    return reg.counter(TRACE_SPAN_SELF).get({"span": span})


# ---- self time -----------------------------------------------------------


def _nested(clock, trace):
    clock.advance(1)
    with trace.span("dispatch"):
        clock.advance(1)
        with trace.span("tensorize"):
            clock.advance(3)
        clock.advance(1)
    clock.advance(2)
    return {"solve": 3.0, "dispatch": 2.0, "tensorize": 3.0}


def _overlapping_on_other_threads(clock, trace):
    # two children of the root, [1, 4] and [3, 6] of its [0, 8]: their
    # union covers 5 s, not 6
    trace.record("a", 101.0, 104.0)
    trace.record("b", 103.0, 106.0)
    clock.advance(8)
    return {"solve": 3.0, "a": 3.0, "b": 3.0}


def _recorded_before_the_root(clock, trace):
    # the door: decoded before the root could open; it covers nothing of
    # the root and keeps its own
    trace.record("request_decode", 98.0, 100.0)
    clock.advance(4)
    return {"solve": 4.0, "request_decode": 2.0}


def _child_overhangs_its_parent(clock, trace):
    # a child that ends after the root (a straggler on another thread)
    # covers the root only as far as the root goes
    trace.record("late", 102.0, 105.0)
    clock.advance(3)
    return {"solve": 2.0, "late": 3.0}


def _child_still_open_at_the_finish(clock, trace):
    clock.advance(1)
    trace.span("straggler")  # ktlint-free: a test of the unclosed case
    clock.advance(1)
    return {"solve": 2.0, "straggler": 0.0}


@pytest.mark.parametrize("shape", [
    _nested, _overlapping_on_other_threads, _recorded_before_the_root,
    _child_overhangs_its_parent, _child_still_open_at_the_finish],
    ids=lambda f: f.__name__.strip("_"))
def test_self_time_is_duration_minus_what_the_children_cover(shape):
    clock, reg, tracer = fake_tracer()
    with tracer.start("solve") as trace:
        want = shape(clock, trace)
    for span, seconds in want.items():
        assert self_s(reg, span) == pytest.approx(seconds), span
    # the zero-init sample stays 0 and the family is on /metrics
    assert reg.counter(TRACE_SPAN_SELF).has() and \
        reg.counter(TRACE_SPAN_SELF).get() == 0.0
    assert f'{TRACE_SPAN_SELF}{{span="solve"}}' in reg.expose()


def test_inside_a_root_the_self_times_sum_to_the_root():
    clock, reg, tracer = fake_tracer()
    with tracer.start("solve") as trace:
        _nested(clock, trace)
    spans = trace.closed_spans()
    assert sum(s for _n, _d, s in spans) == pytest.approx(trace.duration_s)


def test_a_detached_phase_lands_in_both_families_and_in_no_trace():
    clock, reg, tracer = fake_tracer()
    with tracer.phase("response_serialize", detached=True) as ph:
        clock.advance(0.5)
        ph.annotate(bytes=3)  # the profiler's event keeps it; no-op here
    assert (ph.t0, ph.t1) == (100.0, 100.5)
    assert self_s(reg, "response_serialize") == pytest.approx(0.5)
    hist = reg.histogram(TRACE_SPAN_DURATION)
    assert hist.count({"span": "response_serialize"}) == 1
    assert tracer.flight.traces() == []
    # an attached phase is only timed: the caller records it on its trace
    with tracer.phase("request_decode") as ph:
        clock.advance(1)
    assert hist.count({"span": "request_decode"}) == 0


# ---- a real served solve ------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """One sidecar on the real wall clock behind a real gRPC server, and a
    client-side tracer of its own (the operator's)."""
    reg = Registry()
    flight = FlightRecorder(registry=reg)
    tracer = Tracer(registry=reg, flight=flight)
    svc = SolverService(BatchScheduler(backend="tpu", registry=reg,
                                       tracer=tracer), registry=reg)
    threads = {}
    for name in ("parse_request", "Solve", "serialize_response"):
        def spy(*a, _inner=getattr(svc, name), _name=name, **kw):
            threads[_name] = threading.get_ident()
            return _inner(*a, **kw)
        setattr(svc, name, spy)
    srv, port = make_server(svc, port=0)
    creg = Registry()
    ctracer = Tracer(registry=creg, flight=FlightRecorder(registry=creg))
    yield {"svc": svc, "reg": reg, "flight": flight, "port": port,
           "creg": creg, "ctracer": ctracer, "threads": threads}
    srv.stop(grace=None)
    svc.close()


def _solve(served, small_catalog, n=300, app="w"):
    remote = RemoteScheduler(f"127.0.0.1:{served['port']}", backend="tpu",
                             registry=served["creg"])
    prov = Provisioner(name="default").with_defaults()
    try:
        with served["ctracer"].start("provision") as trace:
            res = remote.solve(batch(n, app), [prov], small_catalog,
                               trace=trace)
    finally:
        remote.close()
    assert not res.infeasible
    return trace


def _by_name(trace):
    return {name: (dur, own) for name, dur, own in trace.closed_spans()}


def test_the_door_spans_appear_once_per_solve(served, small_catalog):
    hist = served["reg"].histogram(TRACE_SPAN_DURATION)
    before = {s: hist.count({"span": s}) for s in DOOR + ("solve",)}
    for k in range(2):
        _solve(served, small_catalog, app=f"once{k}")
    for span, n in before.items():
        assert hist.count({"span": span}) == n + 2, span
    # the two that precede the root are in the request's own tree
    tree = served["flight"].traces()[-1].to_dict()
    kids = {c["name"]: c for c in tree["spans"]}
    assert kids["request_decode"]["attrs"]["n_pods"] == 300
    assert kids["request_parse"]["end"] <= kids["request_decode"]["start"]
    assert kids["request_decode"]["end"] <= tree["start"]
    assert "response_serialize" not in kids
    # nothing waits in the hand-over from gRPC's deserialiser
    assert served["svc"]._parse_times == {}


def test_grpc_parses_on_its_own_thread_and_serialises_on_the_handlers(
        served, small_catalog):
    """What docs/OBSERVABILITY.md says of the door's threads."""
    _solve(served, small_catalog, n=20, app="thr")
    t = served["threads"]
    assert t["parse_request"] != t["Solve"]
    assert t["serialize_response"] == t["Solve"]


def test_the_servers_spans_fit_inside_the_clients_rpc_span(
        served, small_catalog):
    hist = served["reg"].histogram(TRACE_SPAN_DURATION)

    def server_s():
        return sum(hist.sums.get((("span", s),), 0.0)
                   for s in DOOR + ("solve",))

    s0 = server_s()
    client = _by_name(_solve(served, small_catalog, app="fit"))
    assert 0 < server_s() - s0 <= client["rpc"][0]


def test_the_clients_spans_split_its_remote_span(served, small_catalog):
    client = _by_name(_solve(served, small_catalog, app="split"))
    parts = sum(client[s][0] for s in ("encode", "rpc", "decode"))
    remote = client["remote"][0]
    assert parts <= remote
    # remote's own time is what its children leave, to the microsecond ...
    assert client["remote"][1] == pytest.approx(remote - parts, abs=1e-6)
    # ... and that is the few statements between them: an absolute bound,
    # since 5 % of a 30 ms solve is one preemption on a loaded machine
    assert remote - parts < 0.05, client


def _sibling_overlap(span: dict) -> float:
    """Seconds that children of one parent spend side by side (two threads
    at work at once), over the whole tree: each of them keeps that time as
    its own while the parent gives it up once."""
    kids = [c for c in span.get("spans", ()) if c["end"] is not None]
    ivs = sorted((max(c["start"], span["start"]), min(c["end"], span["end"]))
                 for c in kids)
    ivs = [(a, b) for a, b in ivs if b > a]
    union, edge = 0.0, float("-inf")
    for a, b in ivs:
        if b > edge:
            union += b - max(a, edge)
            edge = b
    return (sum(b - a for a, b in ivs) - union
            + sum(_sibling_overlap(c) for c in kids))


def test_a_served_solves_self_times_sum_to_its_root(served, small_catalog):
    _solve(served, small_catalog, app="sum")
    trace = served["flight"].traces()[-1]
    spans = trace.closed_spans()
    names = [n for n, _d, _s in spans]
    assert {"solve", "dispatch", "fence", "tensorize", "reseat",
            "respond"} <= set(names)
    before_root = sum(s for n, _d, s in spans
                      if n in ("request_parse", "request_decode"))
    inside = sum(s for _n, _d, s in spans) - before_root
    # `admission` (the queue wait) lies inside `window` (the coalescer's):
    # the one place the served path has two siblings side by side.  How
    # long the two overlap is the dispatcher's wake-up, not this test's to
    # bound; that nothing ELSE overlaps is
    tree = trace.to_dict()
    kids = {c["name"]: c for c in tree["spans"]}
    side_by_side = _sibling_overlap(tree)
    assert side_by_side == pytest.approx(
        max(0.0, min(kids["admission"]["end"], kids["window"]["end"])
            - max(kids["admission"]["start"], kids["window"]["start"])),
        abs=1e-6)
    assert inside == pytest.approx(trace.duration_s + side_by_side,
                                   abs=1e-6)


# ---- ISSUE 37: leaves beneath `bucket` and `extract` ----------------------


def _find(span: dict, name: str) -> list:
    """Every span called ``name`` in the tree under ``span``."""
    out = [span] if span["name"] == name else []
    for child in span.get("spans", ()):
        out += _find(child, name)
    return out


def _own_share(span: dict) -> float:
    covered = sum(c["end"] - c["start"] for c in span.get("spans", ()))
    return 1.0 - covered / (span["end"] - span["start"])


def test_the_bucket_probe_is_leaves_and_counts_its_miss_where_it_is_built(
        served, small_catalog):
    misses = served["reg"].counter(TENSORIZE_CACHE_MISSES)
    hits = served["reg"].counter(TENSORIZE_CACHE_HITS)
    # a batch large enough that the probe's passes take milliseconds and
    # the few statements `bucket` keeps for itself are lost beside them
    m0, h0 = misses.get(), hits.get({"tier": "identity"})
    _solve(served, small_catalog, n=2000, app="leaf")  # a shape never seen
    tree = served["flight"].traces()[-1].to_dict()
    (bucket,) = _find(tree, "bucket")
    inner = bucket["spans"]
    # in the order the work happens, each marked as the probe's pass
    assert [c["name"] for c in inner] == [
        "harden", "carve", "tensorize", "signature"]
    assert all(c["attrs"]["where"] == "probe" for c in inner)
    builds = [c for c in _find(tree, "tensorize")
              if c["attrs"]["tier"] == "miss"]
    assert builds == [inner[2]]
    # the probe's build is the request's one miss; every later `tensorize`
    # of the request (the solve's, relax's) is handed the probe's tensors
    # by identity
    assert misses.get() - m0 == 1
    later = [c for c in _find(tree, "tensorize") if c is not inner[2]]
    assert later and all(c["attrs"] == {"tier": "identity"} for c in later)
    assert hits.get({"tier": "identity"}) - h0 == len(later)
    # the passes over the batch, countable off the span family: the
    # probe's and the solve's `harden`; the probe's `carve`, the two of
    # the solve (`_solve_once`, `_solve_tpu`) and relax's where it scans
    assert len(_find(tree, "harden")) == 2
    assert len(_find(tree, "carve")) in (3, 4)
    # the ladder's depth is asked of every batch whatever it holds; `gang`
    # stays what it was, the epilogue of a batch that holds one
    (ladder,) = _find(tree, "ladder")
    assert ladder["attrs"] == {"depth": 0}
    assert _find(tree, "gang") == []
    # `bucket` keeps next to nothing for itself
    assert _own_share(bucket) < 0.10, bucket


def test_extract_is_leaves(served, small_catalog):
    _solve(served, small_catalog, app="ext")
    tree = served["flight"].traces()[-1].to_dict()
    (extract,) = _find(tree, "extract")
    assert [c["name"] for c in extract["spans"]] == [
        "readback", "nodes", "assign", "coalesce"]
    assert extract["spans"][3]["attrs"]["nodes_in"] >= 1


def test_a_delta_step_passes_the_door_too(served, small_catalog):
    hist = served["reg"].histogram(TRACE_SPAN_DURATION)
    before = {s: hist.count({"span": s}) for s in DOOR}
    sess = DeltaSession(f"127.0.0.1:{served['port']}", backend="oracle",
                        registry=served["creg"])
    prov = Provisioner(name="default").with_defaults()
    try:
        sess.solve(batch(12, "ds"), [prov], small_catalog)
        sess.solve_delta(added=batch(3, "ds-more"))
    finally:
        sess.close()
    for span, n in before.items():
        assert hist.count({"span": span}) == n + 2, span


def test_the_slo_engine_is_fed_door_to_door_not_the_fence(
        small_catalog, monkeypatch):
    clock, reg, tracer = fake_tracer()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg,
                                       tracer=tracer), registry=reg)
    inner = codec.decode_request

    def slow_decode(request, *table):
        clock.advance(2.0)  # the 50,000 pods of the north-star request
        return inner(request, *table)

    monkeypatch.setattr(codec, "decode_request", slow_decode)
    prov = Provisioner(name="default").with_defaults()
    try:
        svc.Solve(codec.encode_request(batch(8), [prov], small_catalog),
                  None)
    finally:
        svc.close()
    hist = reg.histogram(SLO_LATENCY)
    (key,) = [k for k, n in hist.totals.items() if n]
    assert hist.sums[key] == pytest.approx(2.0)


# ---- the profiler's clock, and the collector ----------------------------


def _host_event_names(trace_dir):
    import glob

    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name for pl in data.planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events}


def test_spans_phases_and_gen2_pauses_reach_the_profilers_host_plane(
        tmp_path):
    import jax

    reg = Registry()
    tracer = Tracer(registry=reg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the benchmark's sidecar traces so
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracer.phase("request_decode"):
            pass
        with tracer.start("solve") as trace:
            with trace.span("reseat"):
                gc.collect()
        with tracer.phase("response_serialize", detached=True):
            pass
        # the leaves ISSUE 37 names, as the program opens them
        with tracer.start("solve") as trace:
            with trace.span("bucket"):
                for leaf in ("tensorize", "signature"):
                    with trace.span(leaf, where="probe"):
                        pass
            with trace.span("extract"):
                for leaf in ("readback", "nodes", "assign"):
                    with trace.span(leaf):
                        pass
        # and the door's wait, begun on this thread and ended on another
        door = _Door(tracer)
        door.leave()
        other = threading.Thread(target=door.arrive)
        other.start()
        other.join()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert {"request_decode", "reseat", "response_serialize",
            "gc_gen2"} <= names
    assert {"tensorize", "signature", "readback", "nodes", "assign",
            "await_request"} <= names
    # the root would cover every gap of the device and name them all
    assert "solve" not in names


def test_collector_pauses_are_counted_by_one_callback_per_process():
    reg_a, reg_b = Registry(), Registry()
    Tracer(registry=reg_a)
    Tracer(registry=reg_b)
    ours = [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None) is trace_mod._GC_WATCH]
    assert len(ours) == 1
    for reg in (reg_a, reg_b):  # zero-initialised, every generation
        assert all(reg.counter(GC_PAUSE_SECONDS).has({"generation": g})
                   for g in "012")
    gc.collect()
    for reg in (reg_a, reg_b):
        assert reg.counter(GC_PAUSE_SECONDS).get({"generation": "2"}) > 0


def test_each_pause_is_put_down_to_the_span_it_stopped():
    reg = Registry()
    tracer = Tracer(registry=reg)
    by_span, by_gen = (reg.counter(GC_SPAN_PAUSE_SECONDS),
                       reg.counter(GC_PAUSE_SECONDS))
    # zero-initialised: the spans a metric selects by name, and `none`
    assert all(by_span.has({"span": s}) for s in GC_SPANS_ZEROED)

    def totals():
        return sum(by_span.values.values()), sum(by_gen.values.values())

    auto = gc.isenabled()
    gc.disable()  # the forced collections below are the only ones
    try:
        s0, g0 = totals()
        with tracer.start("solve") as trace:
            with trace.span("extract"):
                with trace.span("nodes"):
                    gc.collect()
                gc.collect(0)
            gc.collect()          # on the root's own thread: the root's
            in_span = {s: by_span.get({"span": s})
                       for s in ("nodes", "extract", "solve")}
        s1, g1 = totals()
        with tracer.phase("response_serialize", detached=True):
            gc.collect(1)
        gc.collect()              # outside any
        # a phase's exit hands the registries nothing: the next trace's
        # finish does
        assert totals()[0] == s1
        with tracer.start("solve"):
            pass
        s2, g2 = totals()
    finally:
        if auto:
            gc.enable()
    # nothing reaches the family before a trace finishes ...
    assert in_span == {"nodes": 0.0, "extract": 0.0, "solve": 0.0}
    # ... then each pause is under the innermost span open on its thread,
    for span in ("nodes", "extract", "solve", "response_serialize", "none"):
        assert by_span.get({"span": span}) > 0, span
    # and over `span` the family sums to the per-generation one
    assert g1 > g0 and s1 - s0 == pytest.approx(g1 - g0, rel=1e-9)
    assert g2 > g1 and s2 - s0 == pytest.approx(g2 - g0, rel=1e-9)


def test_a_pause_on_another_thread_is_not_this_threads_spans():
    reg = Registry()
    tracer = Tracer(registry=reg)
    by_span = reg.counter(GC_SPAN_PAUSE_SECONDS)
    auto = gc.isenabled()
    gc.disable()
    try:
        with tracer.start("solve") as trace:
            with trace.span("reseat"):
                t = threading.Thread(target=gc.collect)
                t.start()
                t.join()
    finally:
        if auto:
            gc.enable()
    assert by_span.get({"span": "reseat"}) == 0.0
    assert by_span.get({"span": "none"}) > 0


def test_a_phase_ended_on_another_thread_stops_no_pause_here_afterwards():
    """The door's wait begins on the thread that serialised and ends on
    gRPC's parsing thread: while it is open a pause on the first thread is
    the wait's, and once it has ended there, no longer."""
    reg = Registry()
    tracer = Tracer(registry=reg)
    by_span = reg.counter(GC_SPAN_PAUSE_SECONDS)
    door = _Door(tracer)
    auto = gc.isenabled()
    gc.disable()
    try:
        door.leave()
        gc.collect()
        t = threading.Thread(target=door.arrive)
        t.start()
        t.join()
        with tracer.start("solve"):
            pass
        waited = by_span.get({"span": "await_request"})
        none = by_span.get({"span": "none"})
        gc.collect()
        with tracer.start("solve"):
            pass
    finally:
        if auto:
            gc.enable()
    assert waited > 0
    assert by_span.get({"span": "await_request"}) == waited
    assert by_span.get({"span": "none"}) > none
    assert trace_mod._open_here() == []


def test_spans_of_two_traces_interleave_on_one_thread():
    """The dispatcher works for several requests at once: one stack a
    thread, and a span's parent is the innermost open span of ITS trace."""
    _clock, _reg, tracer = fake_tracer()
    with tracer.start("solve") as a, tracer.start("solve") as b:
        a_out = a.span("dispatch")
        b_out = b.span("dispatch")
        with a.span("fence"):
            assert a.wire_context() == (a.trace_id, "s3")
            assert b.wire_context() == (b.trace_id, b_out.span_id)
        a_out.__exit__(None, None, None)   # closed under b's, still open
        with b.span("fence"):
            pass
        b_out.__exit__(None, None, None)
        with a.span("extract"):
            pass
    for tree in (a.to_dict(), b.to_dict()):
        (dispatch,) = _find(tree, "dispatch")
        assert [c["name"] for c in dispatch["spans"]] == ["fence"]
    assert [c["name"] for c in a.to_dict()["spans"]] == [
        "dispatch", "extract"]
    assert trace_mod._open_here() == []


def test_a_scrape_says_what_the_heap_holds():
    reg = Registry()
    Tracer(registry=reg)
    blocks = reg.gauge(ALLOCATED_BLOCKS)
    assert blocks.has() and blocks.get() == 0   # there before any scrape
    kept = [[] for _ in range(20000)]
    reg.expose()
    held = blocks.get()
    assert held > 20000
    del kept
    (line,) = [ln for ln in reg.expose().splitlines()
               if ln.startswith(ALLOCATED_BLOCKS)]
    # what is given back shows, and to the block: no ``%g`` rounding
    assert int(line.split()[1]) == blocks.get() < held - 15000


# ---- the door's await_request ---------------------------------------------


def test_the_door_waits_only_while_the_sidecar_holds_no_request():
    clock, reg, tracer = fake_tracer()
    hist = reg.histogram(TRACE_SPAN_DURATION)
    door = _Door(tracer)
    for _client in range(2):      # two clients at once
        door.arrive()
        door.enter()
    clock.advance(1)
    door.leave()                  # one answered, the other still in hand
    clock.advance(1)
    assert hist.count({"span": "await_request"}) == 0
    door.leave()                  # none left: the wait begins here
    clock.advance(3)
    door.arrive()                 # ... and ends at the next parse
    assert hist.count({"span": "await_request"}) == 1
    assert hist.sums[(("span", "await_request"),)] == pytest.approx(3.0)
    assert self_s(reg, "await_request") == pytest.approx(3.0)
    door.arrive()                 # no wait was open: nothing to close
    assert hist.count({"span": "await_request"}) == 1
    assert tracer.flight.traces() == []  # detached: in no trace


def test_a_request_parsed_and_never_handled_leaves_no_count_behind(
        small_catalog):
    """gRPC cancels an RPC between its deserialiser and the handler: the
    wait it arrived in has ended, nothing counts it as in hand, and the
    next answered request opens the wait again."""
    reg = Registry()
    svc = SolverService(BatchScheduler(backend="oracle", registry=reg),
                        registry=reg)
    hist = reg.histogram(TRACE_SPAN_DURATION)
    prov = Provisioner(name="default").with_defaults()

    def request(app):
        return codec.encode_request(
            batch(3, app), [prov], small_catalog).SerializeToString()

    def answered(app):
        svc.serialize_response(svc.Solve(svc.parse_request(request(app)),
                                         None))

    answered("d0")                          # a wait is open after it
    assert svc._door._idle is not None
    for k in range(3):
        svc.parse_request(request(f"lost{k}"))  # parsed, never handled
    assert hist.count({"span": "await_request"}) == 1
    assert svc._door._held == 0 and svc._door._idle is None
    answered("d1")
    assert svc._door._held == 0 and svc._door._idle is not None
    answered("d2")
    assert hist.count({"span": "await_request"}) == 2


def test_await_request_runs_from_the_last_serialise_to_the_next_parse(
        served, small_catalog):
    hist = served["reg"].histogram(TRACE_SPAN_DURATION)
    key = (("span", "await_request"),)
    _solve(served, small_catalog, n=20, app="aw0")  # a wait is open after it
    n0, s0 = hist.count({"span": "await_request"}), hist.sums[key]
    time.sleep(0.2)
    t0 = time.perf_counter()
    _solve(served, small_catalog, n=20, app="aw1")
    wall = time.perf_counter() - t0
    assert hist.count({"span": "await_request"}) == n0 + 1
    # the client's pause, and no more than that plus the client's own turn
    assert 0.2 <= hist.sums[key] - s0 < 0.2 + wall
    # a request that fails at the door leaves it too (no response_serialize
    # will): the sidecar forgets its catalogs, so the next request by digest
    # is refused and sent again in full — two parses, two waits closed
    remote = RemoteScheduler(f"127.0.0.1:{served['port']}", backend="tpu",
                             registry=served["creg"])
    prov = Provisioner(name="default").with_defaults()
    unknown = served["reg"].counter(REQUEST_CATALOG)
    try:
        remote.solve(batch(20, "aw2"), [prov], small_catalog)
        served["svc"].catalogs = KeptCatalogs()
        u0 = unknown.get({"how": "unknown"})
        remote.solve(batch(20, "aw3"), [prov], small_catalog)
        assert unknown.get({"how": "unknown"}) == u0 + 1
    finally:
        remote.close()
    assert hist.count({"span": "await_request"}) == n0 + 4
    assert served["svc"]._door._held == 0


# ---- off is off ----------------------------------------------------------


def test_a_disabled_tracer_builds_no_phase_no_annotation_no_sample(
        monkeypatch):
    def no_annotation(name):
        raise AssertionError(f"annotation built for {name!r}")

    monkeypatch.setattr(trace_mod, "_annotate", no_annotation)
    reg = Registry()
    tracer = Tracer(registry=reg, enabled=False)
    assert tracer.phase("request_decode") is NULL_PHASE
    assert tracer.phase("response_serialize", detached=True) is NULL_PHASE
    door = _Door(tracer)
    door.leave()
    door.enter()  # no wait was begun
    with tracer.phase("request_decode") as ph:
        ph.annotate(bytes=1)
    with tracer.start("solve") as trace:
        with trace.span("reseat"):
            pass
    assert reg.histogram(TRACE_SPAN_DURATION).totals == {}
    assert dict(reg.counter(TRACE_SPAN_SELF).values) == {(): 0.0}
    assert GC_PAUSE_SECONDS not in reg.counters
    assert GC_SPAN_PAUSE_SECONDS not in reg.counters
    assert ALLOCATED_BLOCKS not in reg.gauges


def _python(code, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    env.pop("KT_SANITIZE", None)  # the sanitizer's import pulls jax
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_importing_the_tracer_does_not_import_jax():
    out = _python(
        "import sys, json, gc\n"
        "import karpenter_tpu.obs.trace as t\n"
        "tr = t.Tracer()\n"
        "with tr.start('solve') as trace:\n"
        "    with trace.span('reseat') as sp: pass\n"
        "print(json.dumps({'jax': 'jax' in sys.modules,\n"
        "                  'ann': sp._ann is None,\n"
        "                  'callbacks': len(gc.callbacks)}))\n")
    assert out == {"jax": False, "ann": True, "callbacks": 1}


def test_kt_trace_0_serves_with_no_new_family_moving_and_no_gc_callback():
    out = _python(
        "import gc, json\n"
        "from karpenter_tpu.metrics import Registry\n"
        "from karpenter_tpu.models.catalog import generate_catalog\n"
        "from karpenter_tpu.models.pod import PodSpec\n"
        "from karpenter_tpu.models.provisioner import Provisioner\n"
        "from karpenter_tpu.service.client import RemoteScheduler\n"
        "from karpenter_tpu.service.server import SolverService, make_server\n"
        "from karpenter_tpu.solver.scheduler import BatchScheduler\n"
        "reg = Registry()\n"
        "svc = SolverService(BatchScheduler(backend='oracle', registry=reg),\n"
        "                    registry=reg)\n"
        "srv, port = make_server(svc, port=0)\n"
        "remote = RemoteScheduler(f'127.0.0.1:{port}', registry=Registry())\n"
        "pods = [PodSpec(name=f'p{i}', requests={'cpu': 1.0}) "
        "for i in range(9)]\n"
        "res = remote.solve(pods, [Provisioner(name='default')"
        ".with_defaults()], generate_catalog(full=False))\n"
        "remote.close(); srv.stop(grace=None); svc.close()\n"
        "text = reg.expose()\n"
        "print(json.dumps({'placed': len(res.assignments),\n"
        "  'callbacks': sum(type(getattr(cb, '__self__', None)).__name__\n"
        "      == '_GcWatch' for cb in gc.callbacks),\n"
        "  'gc_family': 'karpenter_process_gc_pause' in text\n"
        "      or 'gc_pause' in text or 'allocated_blocks' in text,\n"
        "  'self': [l for l in text.splitlines() if l.startswith(\n"
        "      'karpenter_trace_span_self_seconds_total')],\n"
        "  'spans': [l for l in text.splitlines() if l.startswith(\n"
        "      'karpenter_trace_span_duration_seconds_count')]}))\n",
        KT_TRACE="0")
    assert out == {"placed": 9, "callbacks": 0, "gc_family": False,
                   "self": ["karpenter_trace_span_self_seconds_total 0"],
                   "spans": []}
