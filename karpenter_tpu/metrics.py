"""Prometheus-style metrics registry + the cloud-provider method decorator.

Mirrors the reference's metric surface (concepts/metrics.md:11-93): counters,
gauges and histograms keyed by (name, labels), plus ``decorate(provider)``
which wraps every CloudProvider method in a duration histogram exactly like
core's ``metrics.Decorate`` (cmd/controller/main.go:46).  Exposition is
text-format compatible so a scraper can consume ``registry.expose()``.
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _lkey(labels: Optional[Dict[str, str]]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((labels or {}).items()))


class Counter:
    def __init__(self) -> None:
        self.values: Dict[tuple, float] = defaultdict(float)

    def inc(self, labels: Optional[Dict[str, str]] = None, value: float = 1.0) -> None:
        self.values[_lkey(labels)] += value

    def get(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self.values.get(_lkey(labels), 0.0)

    def has(self, labels: Optional[Dict[str, str]] = None) -> bool:
        """Whether the SAMPLE exists (get() returns 0.0 either way — the
        distinction is exactly the zero-init contract, KT003)."""
        return _lkey(labels) in self.values


class Gauge:
    def __init__(self) -> None:
        self.values: Dict[tuple, float] = {}

    def set(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        self.values[_lkey(labels)] = value

    def get(self, labels: Optional[Dict[str, str]] = None) -> float:
        return self.values.get(_lkey(labels), 0.0)

    def has(self, labels: Optional[Dict[str, str]] = None) -> bool:
        """Whether the sample exists (a live series must not be clobbered
        by a later default set — see BatchScheduler's INFLIGHT_DEPTH init)."""
        return _lkey(labels) in self.values


class Histogram:
    def __init__(self, buckets=_DEFAULT_BUCKETS) -> None:
        self.buckets = buckets
        self.counts: Dict[tuple, List[int]] = defaultdict(lambda: [0] * (len(buckets) + 1))
        self.sums: Dict[tuple, float] = defaultdict(float)
        self.totals: Dict[tuple, int] = defaultdict(int)

    def observe(self, value: float, labels: Optional[Dict[str, str]] = None) -> None:
        key = _lkey(labels)
        for i, b in enumerate(self.buckets):
            if value <= b:
                self.counts[key][i] += 1
                break
        else:
            self.counts[key][-1] += 1
        self.sums[key] += value
        self.totals[key] += 1

    def count(self, labels: Optional[Dict[str, str]] = None) -> int:
        return self.totals.get(_lkey(labels), 0)


class Registry:
    def __init__(self) -> None:
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}
        #: gauge name -> reading taken when the registry is exposed
        self._at_scrape: Dict[str, object] = {}

    def at_scrape(self, name: str, read) -> None:
        """The unlabelled gauge ``name`` is ``read()`` at every
        :meth:`expose`: for a reading too dear to take per request (a walk
        over the heap) and only ever wanted by a scraper."""
        self.gauge(name).set(0)
        self._at_scrape[name] = read

    def counter(self, name: str) -> Counter:
        return self.counters.setdefault(name, Counter())

    def gauge(self, name: str) -> Gauge:
        return self.gauges.setdefault(name, Gauge())

    def histogram(self, name: str) -> Histogram:
        return self.histograms.setdefault(name, Histogram())

    @staticmethod
    def _sample(name: str, lkey: tuple, value) -> str:
        lbl = ",".join(f'{k}="{val}"' for k, val in lkey)
        # bucket/count samples are ints — keep them exact (``:g`` would turn
        # 1000000 into 1e+06); float samples keep the compact form
        v = str(value) if isinstance(value, int) else f"{value:g}"
        return f"{name}{{{lbl}}} {v}" if lbl else f"{name} {v}"

    def expose(self) -> str:
        """Prometheus text exposition: ``# HELP`` (from :data:`INVENTORY`) +
        ``# TYPE`` per family; histograms emit the full exposition format —
        cumulative ``_bucket`` samples with ``le`` labels (including
        ``+Inf``), ``_sum`` and ``_count`` — so quantile queries
        (``histogram_quantile``) work against the scrape, not just counts."""
        lines: List[str] = []
        for name, read in self._at_scrape.items():
            self.gauge(name).set(read())

        def header(name: str, kind: str) -> None:
            inv = INVENTORY.get(name)
            if inv is not None:
                lines.append(f"# HELP {name} {inv[2]}")
            lines.append(f"# TYPE {name} {kind}")

        for name, c in sorted(self.counters.items()):
            header(name, "counter")
            for lkey, v in sorted(c.values.items()):
                lines.append(self._sample(name, lkey, v))
        for name, g in sorted(self.gauges.items()):
            header(name, "gauge")
            for lkey, v in sorted(g.values.items()):
                lines.append(self._sample(name, lkey, v))
        for name, h in sorted(self.histograms.items()):
            header(name, "histogram")
            for lkey, total in sorted(h.totals.items()):
                cum = 0
                for i, b in enumerate(h.buckets):
                    cum += h.counts[lkey][i]
                    lines.append(self._sample(
                        f"{name}_bucket", lkey + (("le", f"{b:g}"),), cum))
                lines.append(self._sample(
                    f"{name}_bucket", lkey + (("le", "+Inf"),), total))
                lines.append(self._sample(f"{name}_sum", lkey, h.sums[lkey]))
                lines.append(self._sample(f"{name}_count", lkey, total))
        return "\n".join(lines)


# global default registry (controllers accept an override)
registry = Registry()

# metric names mirroring concepts/metrics.md
SCHEDULING_DURATION = "karpenter_scheduling_duration_seconds"
CLOUDPROVIDER_DURATION = "karpenter_cloudprovider_duration_seconds"
NODES_CREATED = "karpenter_nodes_created_total"
NODES_TERMINATED = "karpenter_nodes_terminated_total"
DEPROVISIONING_ACTIONS = "karpenter_deprovisioning_actions_performed_total"
DEPROVISIONING_DURATION = "karpenter_deprovisioning_evaluation_duration_seconds"
INTERRUPTION_RECEIVED = "karpenter_interruption_received_messages_total"
INTERRUPTION_LATENCY = "karpenter_interruption_message_latency_seconds"
PODS_STARTUP_DURATION = "karpenter_pods_startup_time_seconds"
PROVISIONER_USAGE = "karpenter_provisioner_usage"
PROVISIONER_LIMIT = "karpenter_provisioner_limit"
BATCH_SIZE = "karpenter_provisioner_batch_size"
SOLVER_BACKEND_DURATION = "karpenter_solver_backend_duration_seconds"
SOLVER_COMPILE_IN_PROGRESS = "karpenter_solver_compile_in_progress"
SOLVER_COMPILE_DURATION = "karpenter_solver_compile_duration_seconds"
SOLVER_COLD_FALLBACKS = "karpenter_solver_cold_start_fallbacks_total"
SOLVER_DEVICE_HANGS = "karpenter_solver_device_hangs_total"
SOLVER_DEVICE_HEALTHY = "karpenter_solver_device_healthy"
SOLVER_DEGRADED_SOLVES = "karpenter_solver_degraded_solves_total"
REMOTE_FALLBACK_SOLVES = "karpenter_solver_remote_fallback_solves_total"
REMOTE_DEGRADED = "karpenter_solver_remote_degraded"
MEGABATCH_SLOTS = "karpenter_solver_megabatch_slots"
MEGABATCH_FLUSH = "karpenter_solver_megabatch_flush_total"
#: the full flush-reason label population (KT003 zero-init source shared by
#: BatchScheduler and SolvePipeline): coalescer boundaries (full/deadline/
#: bucket) plus 'mesh_serial' — a mesh-configured scheduler serving a
#: would-be sharded megabatch serially (cold sharded rung, unshardable
#: mesh, or a degraded flush)
MEGABATCH_FLUSH_REASONS = ("full", "deadline", "bucket", "mesh_serial")
PRECOMPILE_DURATION = "karpenter_solver_precompile_duration_seconds"
TENSORIZE_CACHE_HITS = "karpenter_solver_tensorize_cache_hits_total"
TENSORIZE_CACHE_MISSES = "karpenter_solver_tensorize_cache_misses_total"
TENSORIZE_DURATION = "karpenter_solver_tensorize_duration_seconds"
INFLIGHT_DEPTH = "karpenter_solver_inflight_depth"
TRACE_TRACES = "karpenter_trace_traces_total"
TRACE_SPAN_DURATION = "karpenter_trace_span_duration_seconds"
TRACE_SPAN_SELF = "karpenter_trace_span_self_seconds_total"
GC_PAUSE_SECONDS = "karpenter_process_gc_pause_seconds_total"
#: the collector's generations (KT003 zero-init source)
GC_GENERATIONS = ("0", "1", "2")
GC_SPAN_PAUSE_SECONDS = "karpenter_trace_span_gc_pause_seconds_total"
#: the spans whose share of the collector a dashboard or a benchmark metric
#: selects by name, and ``none`` (KT003 zero-init source; any other span a
#: pause lands in appears with its first pause)
GC_SPANS_ZEROED = ("none", "await_request", "request_parse", "request_decode",
                   "response_serialize", "extract", "readback", "nodes",
                   "assign", "coalesce", "reseat", "relax", "gang")
ALLOCATED_BLOCKS = "karpenter_process_allocated_blocks"
# ---- the sidecar's door: pods decoded by template (service/codec.py) ----
REQUEST_DECODE_PODS = "karpenter_solver_request_decode_pods_total"
#: how a pod of a request became a PodSpec (KT003 zero-init source):
#: 'templated' (stamped from the first pod of its shape) vs 'plain' (built
#: field by field: the first pod of each shape, a pod whose bytes give no
#: shape key, and every pod after the table gave up on its request)
REQUEST_DECODE_HOW = ("templated", "plain")
# ---- the client's side of the same wire (service/codec.py PodShapes) ----
REQUEST_ENCODE_PODS = "karpenter_solver_request_encode_pods_total"
#: how a pod of a request became a pb.Pod (KT003 zero-init source):
#: 'templated' (its name plus the bytes of the first pod of its shape) vs
#: 'plain' (built field by field: the first pod of each shape, a pod whose
#: values give no shape key, and every pod after the table gave up)
REQUEST_ENCODE_HOW = ("templated", "plain")
# ---- the catalog by name (service/server.py SolverService.Solve) ---------
REQUEST_CATALOG = "karpenter_solver_request_catalog_total"
#: where a Solve's instance types came from (KT003 zero-init source):
#: 'held' (the request named a list this sidecar kept), 'decoded' (built
#: from the request's own instance_types) and 'unknown' (the request named
#: a list this sidecar does not hold, and was refused)
REQUEST_CATALOG_HOW = ("held", "decoded", "unknown")
REQUEST_CATALOG_SENT = "karpenter_solver_request_catalog_sent_total"
#: how a RemoteScheduler put the catalog on a Solve (KT003 zero-init
#: source): 'digest' (by the sidecar's name for it), 'full' (the list) and
#: 'resent' (the list again, after the sidecar did not know the name)
REQUEST_CATALOG_SENT_HOW = ("digest", "full", "resent")
# ---- the device scan's axes (solver/tpu.py TpuSolver._count_scan) --------
SCAN_AXIS = "karpenter_solver_scan_axis_total"
#: what a device scan ran at (KT003 zero-init source): 'groups' (the batch's
#: pod groups: one per distinct pod shape), 'groups_padded' (the G rung the
#: program was compiled at: the shape of its group axis), 'node_slots' (the
#: NR rung every step carries), 'nodes_used' (slots in use when the scan
#: ended) and 'steps_run' (the serial steps the program took, as it reports
#: them: up to the last group that has pods)
SCAN_AXES = ("groups", "groups_padded", "node_slots", "nodes_used",
             "steps_run")
SCAN_SLOT_RETRIES = "karpenter_solver_scan_slot_retries_total"
# ---- the host's merge pass over a scan's new nodes (solver/coalesce.py) --
COALESCE = "karpenter_solver_coalesce_total"
#: what a pass saw and did (KT003 zero-init source): 'nodes_in' (new nodes
#: the scan opened), 'merges' (each takes one node off the answer) and
#: 'pairs' (pair verdicts computed to find them)
COALESCE_WHAT = ("nodes_in", "merges", "pairs")
TRACE_RING_EVICTIONS = "karpenter_trace_ring_evictions_total"
FLIGHT_DUMPS = "karpenter_trace_flight_recorder_dumps_total"
# ---- fleet-wide tracing (ISSUE 15: wire-propagated trace context) -------
TRACE_REMOTE_SPANS = "karpenter_trace_remote_spans_total"
#: how each server-side RPC trace rooted (KT003 zero-init source, shared by
#: Tracer construction): 'adopted' (the request carried a wire trace
#: context and this trace joined the remote parent's tree) vs 'local' (no
#: context on the wire — an old client, a direct call, or an unsampled
#: origin; the trace rooted locally)
TRACE_REMOTE_OUTCOMES = ("adopted", "local")
# ---- trace-replay harness (ISSUE 15: obs/replay.py) ---------------------
REPLAY_REQUESTS = "karpenter_replay_requests_total"
#: replayed-request outcomes (KT003 zero-init source): 'ok' (served),
#: 'shed' (typed admission shed/deadline — the replayed traffic found the
#: server's protection posture, which is a result, not an error),
#: 'error' (transport or server failure)
REPLAY_OUTCOMES = ("ok", "shed", "error")
REPLAY_LAG = "karpenter_replay_lag_seconds"
ADMISSION_ADMITTED = "karpenter_admission_admitted_total"
ADMISSION_SHED = "karpenter_admission_shed_total"
ADMISSION_QUEUE_DEPTH = "karpenter_admission_queue_depth"
ADMISSION_QUEUE_DELAY = "karpenter_admission_queue_delay_seconds"
ADMISSION_BREAKER_STATE = "karpenter_admission_breaker_state"
ADMISSION_BREAKER_TRANSITIONS = "karpenter_admission_breaker_transitions_total"
ADMISSION_BROWNOUT_LEVEL = "karpenter_admission_brownout_level"
ADMISSION_HOST_ROUTED = "karpenter_admission_host_routed_total"
DELTA_RPC = "karpenter_solver_delta_rpc_total"
#: the full session-RPC outcome label population (KT003 zero-init source —
#: service/delta.DeltaSessionTable and the pipeline both init from it):
#: 'delta' (an incremental warm-start tier served the step), 'fallback_full'
#: (a warm-start guard tripped and the step re-solved from the stripped
#: base — the session survives), 'establish' (a full solve created or
#: replaced the session chain), 'reseed' (a catalog/price epoch bump
#: re-solved the chain from the stripped base server-side instead of
#: cold-starting the client), 'session_unknown' (no live chain for the
#: client's (session, epoch) — the client re-establishes with ONE full
#: solve)
DELTA_RPC_OUTCOMES = ("delta", "fallback_full", "establish", "reseed",
                      "session_unknown", "drain_refused")
DELTA_RPC_DURATION = "karpenter_solver_delta_rpc_duration_seconds"
DELTA_SESSIONS = "karpenter_solver_delta_sessions"
DELTA_EVICTIONS = "karpenter_solver_delta_session_evictions_total"
#: eviction-reason label population (KT003).  'fault' is the injected
#: session-table wipe (docs/RESILIENCE.md) — production never emits it.
#: 'drain' is the graceful fleet handoff (record spooled + lease released
#: + entry dropped so a sibling replica adopts the chain WARM); and
#: 'lease_lost' is the zombie-writer guard — this replica's session lease
#: was stolen after expiry, so the chain is dropped rather than served or
#: spooled over the new owner's record.
DELTA_EVICT_REASONS = ("ttl", "capacity", "stop", "error", "fault",
                       "drain", "lease_lost")
# ---- session durability (ISSUE 12: crash-safe delta serving) ------------
SNAPSHOT_WRITES = "karpenter_solver_session_snapshot_writes_total"
#: snapshot write outcomes (KT003 zero-init source): 'written' (spool file
#: atomically replaced), 'empty' (no live sessions — nothing written),
#: 'error' (serialization or I/O failed; the previous spool survives)
SNAPSHOT_WRITE_OUTCOMES = ("written", "empty", "error")
SNAPSHOT_SKIPPED = "karpenter_solver_session_snapshot_skipped_total"
#: per-session skip reasons: 'in_step' (a delta step was mid-mutation at
#: capture — an epoch-atomic snapshot must not persist a half-applied
#: chain), 'torn' (a step started or committed while the lock-free
#: writer was pickling this chain; the possibly-inconsistent bytes are
#: discarded)
SNAPSHOT_SKIP_REASONS = ("in_step", "torn", "lease_lost")
SNAPSHOT_RESTORE = "karpenter_solver_session_snapshot_restore_total"
#: restore outcomes — every refusal is a COLD START plus this label, never
#: a crash or a diverged chain (docs/RESILIENCE.md)
SNAPSHOT_RESTORE_OUTCOMES = ("restored", "missing", "corrupt", "truncated",
                             "version", "catalog_epoch", "error")
SNAPSHOT_DURATION = "karpenter_solver_session_snapshot_duration_seconds"
SNAPSHOT_SESSIONS = "karpenter_solver_session_snapshot_sessions"
# ---- fleet failover (ISSUE 13: warm delta-session handoff) --------------
SESSION_ADOPTIONS = "karpenter_solver_session_adoptions_total"
#: adoption outcomes (KT003 zero-init source; docs/RESILIENCE.md adoption
#: state machine): 'adopted' (free lease claimed, record consumed, chain
#: live), 'stolen' (the previous owner's lease had EXPIRED — a dead
#: replica's session adopted after the lease TTL), 'lease_held' (typed
#: refusal: a sibling replica holds an unexpired lease — exactly-one-owner
#: by construction), 'missing' (no spool record for the session),
#: 'refused' (the record failed the envelope checks — corrupt/version/
#: catalog skew, also counted per-reason in the restore family), 'error'
#: (unexpected failure; cold start)
SESSION_ADOPTION_OUTCOMES = ("adopted", "stolen", "lease_held", "missing",
                             "refused", "error")
SESSION_LEASES = "karpenter_solver_session_leases_owned"
FLEET_ENDPOINTS = "karpenter_fleet_endpoints"
#: endpoint-state label population (client-side, FleetClient): 'known'
#: (configured), 'healthy' (serving), 'draining' (answered a DRAINING
#: hint; new sessions route elsewhere until the pod dies)
FLEET_ENDPOINT_STATES = ("known", "healthy", "draining")
FLEET_FAILOVERS = "karpenter_fleet_failovers_total"
#: why a session was re-homed to a different replica: 'death' (transport
#: failure outlived the retry budget) or 'drain' (the serving replica
#: answered the graceful-drain hint)
FLEET_FAILOVER_REASONS = ("death", "drain")
# ---- fault-injection plane (ISSUE 12: KT_FAULTS, karpenter_tpu/faults/) -
FAULTS_INJECTED = "karpenter_faults_injected_total"
FAULTS_RECOVERED = "karpenter_faults_recovered_total"
#: every choke point the plane can fire at (label population + the site
#: vocabulary scripts and docs share)
FAULT_SITES = ("dispatch", "fence", "delta_step", "delta_commit",
               "session_table", "snapshot_write", "snapshot_read",
               "transport", "breaker", "adopt")
#: the injectable fault catalog (docs/RESILIENCE.md)
FAULT_KINDS = ("device_hang", "dispatch_exc", "slow_fence", "slow_step",
               "rpc_unavailable", "rpc_reset", "session_wipe", "clock_jump",
               "snapshot_corrupt", "snapshot_truncate", "breaker_trip",
               "lease_steal")
#: recovery outcomes the serving stack reports per site (KT016 pins that
#: every recovering except on a faultable path lands here)
FAULT_RECOVERY_OUTCOMES = ("ok", "retried", "fallback", "evicted", "cold",
                           "skipped", "failed")
RELAX_TOTAL = "karpenter_solver_relax_total"
#: the full relax-rung outcome label population (KT003 zero-init source —
#: BatchScheduler and solver/relax.py both init from it): 'improved' (the
#: relax+round solution cost strictly less and shipped), 'tied' (the rung
#: matched the scan's cost; the scan solution ships), 'fallback' (rounding/
#: repair could not reach a valid cheaper solution, or the rung errored —
#: the scan solution ships), 'skipped' (the rung was enabled but did not
#: run: no eligible unconstrained groups, cold relax program, cold-served
#: or budget-constrained solve)
RELAX_OUTCOMES = ("improved", "tied", "fallback", "skipped")
RELAX_DURATION = "karpenter_solver_relax_duration_seconds"
RELAX_IMPROVEMENT = "karpenter_solver_relax_improvement_ratio"
WARMSTART_SOLVES = "karpenter_solver_warmstart_solves_total"
WARMSTART_DURATION = "karpenter_solver_warmstart_duration_seconds"
WARMSTART_DISPLACED = "karpenter_solver_warmstart_displaced_pods"
CONSOLIDATION_SWEEPS = "karpenter_solver_consolidation_sweeps_total"
CONSOLIDATION_SWEEP_SLOTS = "karpenter_solver_consolidation_sweep_slots"
CONSOLIDATION_SWEEP_DURATION = (
    "karpenter_solver_consolidation_sweep_duration_seconds")
MULTIHOST_FENCE_BYTES = "karpenter_solver_multihost_fence_bytes_total"
#: the per-host fence's byte accounting scopes: what this process actually
#: read (its addressable slot shards) vs what a whole-batch readback would
#: have transferred — read/whole per host converges to 1/N at N hosts
MULTIHOST_FENCE_SCOPES = ("read", "whole")
MULTIHOST_SLOTS = "karpenter_solver_multihost_slots_total"
#: per-host demux ownership of real (non-padding) megabatch slots
MULTIHOST_SLOT_OWNERSHIP = ("owned", "foreign")
MULTIHOST_FORWARDS = "karpenter_solver_multihost_forwards_total"
#: forwarding-shim outcomes for foreign-slot requests
MULTIHOST_FORWARD_OUTCOMES = ("forwarded", "error", "unrouted")
MULTIHOST_UNIFIED = "karpenter_solver_multihost_unified_flushes_total"
HIER_SOLVES = "karpenter_solver_hier_solves_total"
#: routing outcomes for batches at/above KT_HIER_THRESHOLD (KT003 zero-init
#: source — solver/hierarchy.py inits from it): 'hierarchical' (block
#: decomposition served the batch), 'fallback_cold' (the block program was
#: still compiling — flat served, compile-behind warm started),
#: 'fallback_structure' (one reachability component, inexpressible pods, or
#: an existing-node batch — flat IS the right program), 'fallback_degraded'
#: (a block wave hit the hang guard or errored; flat's degradation ladder
#: served)
HIER_PATHS = ("hierarchical", "fallback_cold", "fallback_structure",
              "fallback_degraded")
HIER_BLOCKS = "karpenter_solver_hier_blocks"
HIER_PRICE_ITERATIONS = "karpenter_solver_hier_price_iterations"
HIER_REPAIR_PODS = "karpenter_solver_hier_repair_pods"
HIER_DURATION = "karpenter_solver_hier_duration_seconds"
# ---- time-resolved telemetry (ISSUE 18: obs/timeseries.py sampler) ------
TS_SAMPLES = "karpenter_ts_samples_total"
TS_SERIES = "karpenter_ts_series"
TS_SAMPLE_DURATION = "karpenter_ts_sample_duration_seconds"
# ---- per-class SLOs (ISSUE 18: obs/slo.py burn-rate engine) -------------
SLO_REQUESTS = "karpenter_slo_requests_total"
#: per-request SLO accounting outcomes (KT003 zero-init source): 'ok'
#: (served), 'shed' (typed admission shed / deadline — availability-bad
#: by the objective's definition even though the protection worked),
#: 'error' (unexpected server failure)
SLO_REQUEST_OUTCOMES = ("ok", "shed", "error")
#: the priority classes objectives are declared over — the admission
#: vocabulary (admission.parse_class), shared so the SLO engine's label
#: population can never drift from the admission queue's
SLO_CLASSES = ("critical", "batch", "best_effort")
SLO_LATENCY = "karpenter_slo_latency_seconds"
SLO_BURN_RATE = "karpenter_slo_burn_rate"
#: the declared objectives (label population for the burn/budget gauges)
SLO_OBJECTIVES = ("availability", "latency")
#: the burn-rate evaluation windows (labels; seconds in obs/slo.WINDOWS)
SLO_WINDOW_NAMES = ("5m", "1h")
SLO_BUDGET_REMAINING = "karpenter_slo_budget_remaining"
SLO_VERDICT = "karpenter_slo_verdict"
# ---- device-occupancy accounting (ISSUE 18: obs/occupancy.py) -----------
OCCUPANCY_DEVICE_BUSY = "karpenter_occupancy_device_busy_share"
OCCUPANCY_SLOT_FILL = "karpenter_occupancy_megabatch_slot_fill"
OCCUPANCY_DELTA_INLINE = "karpenter_occupancy_delta_inline_fraction"
# ---- self-tuning controller (ISSUE 19: tuning/) -------------------------
TUNING_STEPS = "karpenter_tuning_steps_total"
#: per-decision outcomes (KT003 zero-init source — tuning/controller.py
#: inits the full knob x outcome population): 'applied' (a lattice step
#: taken, probe window opened), 'kept' (the probe window confirmed the
#: step), 'reverted' (the probe window regressed the objective — or a
#: class went warn mid-probe — and the step was rolled back), 'frozen'
#: (no move: a class burn rate was warn+), 'skipped' (no move: no
#: windowed data, lattice edge, or knob frozen)
TUNING_STEP_OUTCOMES = ("applied", "kept", "reverted", "frozen", "skipped")
TUNING_KNOB_VALUE = "karpenter_tuning_knob_value"
TUNING_STEP_DURATION = "karpenter_tuning_step_duration_seconds"
# ---- gang scheduling (ISSUE 20: karpenter_tpu/gang/) --------------------
GANG_GANGS = "karpenter_solver_gang_gangs_total"
#: per-gang epilogue outcomes (KT003 zero-init source — gang.zero_init_
#: gang_metrics, called from BatchScheduler construction): 'placed' (every
#: member seated, scan placement kept), 'packed' (every member seated and
#: the co-location repack adopted a strictly cheaper spread), 'retracted'
#: (a member was infeasible — the WHOLE gang's seats were retracted and
#: every member surfaced as GangUnplaced; never a partial placement)
GANG_OUTCOMES = ("placed", "packed", "retracted")
GANG_SPREAD_ZONES = "karpenter_solver_gang_spread_zones"
GANG_SPREAD_CLASSES = "karpenter_solver_gang_spread_node_classes"
GANG_DURATION = "karpenter_solver_gang_duration_seconds"
# ---- /fleetz peer-fetch accounting (ISSUE 18 satellite) -----------------
FLEET_PEER_FETCH = "karpenter_fleet_peer_fetch_total"
#: per-peer /fleetz fan-out outcomes (KT003 zero-init source): 'ok'
#: (both documents fetched and decoded), 'timeout' (the per-peer budget
#: expired — a partitioned peer), 'error' (refused / bad JSON / HTTP
#: failure).  Failed peers are marked stale in the merge, never dropped
#: silently.
FLEET_PEER_FETCH_OUTCOMES = ("ok", "timeout", "error")

#: metric inventory: name -> (type, labels, help).  docs/METRICS.md is
#: generated from this table (``karpenter-tpu metrics-doc``), mirroring the
#: reference's docs-from-metric-definitions generation (Makefile:150-153).
INVENTORY = {
    SCHEDULING_DURATION: (
        "histogram", (),
        "End-to-end batch scheduling duration per solve, seconds."),
    CLOUDPROVIDER_DURATION: (
        "histogram", ("controller", "method"),
        "Duration of each CloudProvider method call (metrics decorator)."),
    NODES_CREATED: (
        "counter", ("provisioner",),
        "Nodes launched, by provisioner."),
    NODES_TERMINATED: (
        "counter", ("provisioner",),
        "Nodes terminated, by provisioner."),
    DEPROVISIONING_ACTIONS: (
        "counter", ("action",),
        "Deprovisioning actions performed (kind/mechanism)."),
    DEPROVISIONING_DURATION: (
        "histogram", (),
        "Deprovisioning evaluation pass duration, seconds."),
    INTERRUPTION_RECEIVED: (
        "counter", ("message_type",),
        "Interruption queue messages received, by message type."),
    INTERRUPTION_LATENCY: (
        "histogram", ("message_type",),
        "Delay from interruption event timestamp to handling, seconds."),
    PODS_STARTUP_DURATION: (
        "histogram", (),
        "Time from pod creation to bound-and-running, seconds."),
    PROVISIONER_USAGE: (
        "gauge", ("provisioner", "resource_type"),
        "Resource usage accounted against each provisioner's limits."),
    PROVISIONER_LIMIT: (
        "gauge", ("provisioner", "resource_type"),
        "Configured provisioner resource limits."),
    BATCH_SIZE: (
        "histogram", (),
        "Pending pods per provisioning batch window."),
    SOLVER_BACKEND_DURATION: (
        "histogram", ("backend",),
        "Per-backend (tpu / native / oracle) solve duration, seconds.  On "
        "the pipelined path (SolvePipeline) the tpu series spans dispatch "
        "to fence and therefore includes the overlap window in which the "
        "host tensorizes the NEXT batch — it is the caller-visible stage "
        "latency, not pure device time."),
    SOLVER_COMPILE_IN_PROGRESS: (
        "gauge", (),
        "Background XLA compiles currently in flight (compile-behind + "
        "warmup); callers are served by the warm tier meanwhile."),
    SOLVER_COMPILE_DURATION: (
        "histogram", (),
        "Background XLA compile duration per shape signature, seconds."),
    SOLVER_COLD_FALLBACKS: (
        "counter", ("backend",),
        "Solves served by the native/oracle warm tier because the device "
        "program for their shape was not compiled yet."),
    SOLVER_DEVICE_HANGS: (
        "counter", (),
        "Device calls abandoned by the hang guard (a PJRT call that did "
        "not return within the deadline); "
        "each latches the device tier unhealthy until a probe succeeds."),
    SOLVER_DEVICE_HEALTHY: (
        "gauge", (),
        "1 while the in-process device tier is healthy, 0 while latched "
        "unhealthy after a hang (warm host tiers serve all batches)."),
    SOLVER_DEGRADED_SOLVES: (
        "counter", ("backend",),
        "Solves served by the warm host tiers because the device tier was "
        "latched unhealthy (distinct from cold-start fallbacks: the device "
        "program was compiled, the device was not answering)."),
    REMOTE_FALLBACK_SOLVES: (
        "counter", (),
        "Solves served by the local fallback scheduler while the remote "
        "gRPC solver sidecar was unreachable."),
    REMOTE_DEGRADED: (
        "gauge", (),
        "1 while the remote solver sidecar is unreachable and solves "
        "degrade to the local fallback; 0 when connected."),
    MEGABATCH_SLOTS: (
        "histogram", (),
        "Occupied request slots per megabatch device dispatch (the "
        "cross-request continuous-batching path: one vmapped program solves "
        "every slot in a single device round trip; serial fallbacks while a "
        "slot-rung program compiles behind observe 1 per dispatch).  "
        "sum/count is the mean occupancy."),
    MEGABATCH_FLUSH: (
        "counter", ("reason",),
        "Coalescer batch flushes by reason: 'full' (max-slots reached), "
        "'deadline' (max-wait expired, or the inbound queue went idle with "
        "no wait configured), 'bucket' (an arriving request's shape bucket "
        "differed from the held batch's, or the request cannot ride a "
        "megabatch at all), 'mesh_serial' (a mesh-configured scheduler "
        "served a would-be sharded megabatch serially — the sharded "
        "slot-rung program was still compiling behind, the mesh's device "
        "count exceeds the slot-rung ladder, or the flush degraded; "
        "steady-state meshed serving should hold this near zero)."),
    PRECOMPILE_DURATION: (
        "histogram", (),
        "Wall time of one blocking ahead-of-time bucket-grid precompile "
        "pass (precompile_buckets(wait=True) — the serve --warmup path), "
        "seconds: startup cost paid so the serving path never compiles."),
    TENSORIZE_CACHE_HITS: (
        "counter", ("tier",),
        "Tensorize cache hits by tier: 'identity' (same pod objects re-"
        "solved, pointer-compare fast path) or 'shape' (same deployment "
        "shapes, tensors reused, only the counts vector rebuilt).  A "
        "healthy steady-state provisioning loop runs >90% hits."),
    TENSORIZE_CACHE_MISSES: (
        "counter", (),
        "Tensorize cache misses (full host tensor build — new batch shape "
        "or a provisioner/catalog/daemonset change rotated the context)."),
    TENSORIZE_DURATION: (
        "histogram", (),
        "Host tensorize (pods -> device tensors) duration per solver wave, "
        "seconds; cache hits land in the lowest buckets."),
    INFLIGHT_DEPTH: (
        "gauge", ("backend",),
        "Async device dispatches currently in flight in each backend's "
        "solve pipeline (double-buffered dispatch overlaps host tensorize "
        "of batch N+1 with device execution of batch N)."),
    TRACE_TRACES: (
        "counter", (),
        "Per-solve traces recorded by the tracer (obs/trace.py); one per "
        "sampled solve/provision/deprovision pass.  KT_TRACE=0 disables "
        "sampling entirely, KT_TRACE_SAMPLE_EVERY=N keeps 1 in N."),
    TRACE_SPAN_DURATION: (
        "histogram", ("span",),
        "Duration of each named trace span (window / tensorize / dispatch "
        "/ fence / reseat / respond / ...), seconds — the per-phase "
        "attribution behind /tracez p50/p99."),
    TRACE_SPAN_SELF: (
        "counter", ("span",),
        "Self time of each named trace span, seconds: its duration minus "
        "the part of its own interval that its child spans cover (their "
        "union, clipped to the parent).  Spans nest, so durations overlap "
        "and self times do not: inside one root they sum to the root's "
        "duration (plus the time siblings spend side by side on two "
        "threads), and a phase recorded before the root (request_parse, "
        "request_decode) or detached after it (response_serialize) adds "
        "its own.  The unlabeled sample is the zero-init and stays 0."),
    GC_PAUSE_SECONDS: (
        "counter", ("generation",),
        "Seconds this process spent inside the Python collector, by "
        "generation, from one gc.callbacks entry registered by the first "
        "enabled tracer (KT_TRACE=0: never registered, the family stays "
        "absent).  Generation-2 pauses are also mirrored onto the "
        "profiler's host plane as gc_gen2."),
    GC_SPAN_PAUSE_SECONDS: (
        "counter", ("span",),
        "Seconds this process spent inside the Python collector, by the "
        "innermost trace span or door phase open on the thread the "
        "collection ran on ('none' outside any; the root counts as a span "
        "on the thread that opened it): which layer's time the collector "
        "is hiding in.  Written when a trace finishes, so between two "
        "such moments its sum over span lags "
        "karpenter_process_gc_pause_seconds_total, which it otherwise "
        "equals.  KT_TRACE=0: absent."),
    ALLOCATED_BLOCKS: (
        "gauge", (),
        "sys.getallocatedblocks() of this process, read when /metrics is "
        "scraped (it walks the heap's pools: milliseconds on a heap of "
        "gigabytes, so not per request).  A sidecar that keeps something "
        "of every request (a leak) shows it rising scrape after scrape "
        "under steady traffic; one that does not shows it level once "
        "warm.  Registered by an enabled tracer: KT_TRACE=0, absent."),
    REQUEST_DECODE_PODS: (
        "counter", ("how",),
        "Pods the sidecar decoded off Solve requests (pending pods, "
        "daemonsets and the pods of existing nodes), by how: 'templated' "
        "— the pod's bytes after its name equal an earlier pod's of the "
        "same request, so its PodSpec was stamped from that pod's field "
        "values (own name, next uid, containers shared); 'plain' — built "
        "field by field by decode_pod: the first pod of each shape, a pod "
        "whose bytes give no shape key, and every pod after the request's "
        "table found fewer than half hits in its first 512 pods and gave "
        "up.  Pods of one deployment differ in name only, so a healthy "
        "provisioning batch reads almost all 'templated' (50,000 pods in "
        "20 deployments: 49,980).  No table outlives its request."),
    REQUEST_ENCODE_PODS: (
        "counter", ("how",),
        "Pods a RemoteScheduler encoded onto Solve requests (pending pods, "
        "daemonsets and the pods of existing nodes), on the CLIENT's "
        "registry, by how: 'templated' — every field encode_pod reads but "
        "the name equals an earlier pod's of the same request, so the pod "
        "went out as its name plus that pod's serialized bytes; 'plain' — "
        "built field by field by encode_pod: the first pod of each shape, "
        "a pod whose values give no shape key, and every pod after the "
        "request's table found fewer than half hits in its first 512 pods "
        "and gave up.  The mirror of "
        "karpenter_solver_request_decode_pods_total at the sidecar's door: "
        "a healthy provisioning batch reads almost all 'templated' on both "
        "sides.  No table outlives its request."),
    REQUEST_CATALOG: (
        "counter", ("how",),
        "Solve RPCs by where their instance types came from, once per "
        "request: 'held' — the request carried no instance_types and a "
        "catalog_digest this sidecar handed out earlier, so it was solved "
        "on the list the sidecar kept (the same InstanceType objects as "
        "last time: nothing parsed, decoded or re-signed); 'decoded' — "
        "built from the request's own instance_types (a sessionless one "
        "is then digested, kept — the last 4 distinct lists — and named "
        "in SolveResponse.catalog_digest); 'unknown' — the digest names "
        "no list this sidecar holds (a restart, an eviction, another "
        "replica): FAILED_PRECONDITION 'CATALOG_UNKNOWN', which costs the "
        "client one resend.  A steady client reads 'held' on every "
        "request after its first."),
    REQUEST_CATALOG_SENT: (
        "counter", ("how",),
        "Solve requests a RemoteScheduler sent, on the CLIENT's registry, "
        "by how the catalog went: 'digest' — instance_types is element "
        "for element the same objects as the last list this sidecar "
        "acknowledged, so its digest went in their place; 'full' — the "
        "list itself (the first request, a refreshed catalog, a sidecar "
        "that acknowledges nothing); 'resent' — the list again, inside "
        "the same solve, after the sidecar answered CATALOG_UNKNOWN (or "
        "answered without naming a catalog at all: a sidecar rolled back "
        "under the client).  The mirror of "
        "karpenter_solver_request_catalog_total at the sidecar's door."),
    SCAN_AXIS: (
        "counter", ("axis",),
        "What the device scans ran at, summed over device solves (one "
        "increment per axis per fenced scan — the single, the pipelined "
        "and each megabatch slot; a slot retry counts both scans): "
        "'groups' — pod groups of the batch, one serial scan step each "
        "(a group is a set of pods with equal PodSpec.group_key(), so "
        "every Deployment is its own); 'groups_padded' — the G rung the "
        "program was compiled at (solve_dims): the shape of its group "
        "axis, what the take matrix and the compile signature are sized "
        "by, not the steps it takes; 'steps_run' — the serial steps the "
        "program took, read off the program at the fence: it stops after "
        "the last group that has pods, so a single solve reads 'groups' "
        "and a megabatch slot the longest slot's; 'node_slots' — the NR "
        "rung, the node rows every step carries, from _nr_estimate or "
        "the full budget; 'nodes_used' — slots in use when the scan "
        "ended, existing nodes included.  Divide by the solves of the "
        "same window for per-solve means; node_slots far above "
        "nodes_used is an estimate that charges device time for rows "
        "nothing lands on, steps_run above groups a megabatch whose "
        "slots differ in length."),
    SCAN_SLOT_RETRIES: (
        "counter", (),
        "Device scans whose optimistic node-slot axis (_nr_estimate) ran "
        "out with pods still unplaced, so the batch was solved again at "
        "the full budget (or, under compile-behind with that program "
        "cold, served from the warm tier while it compiles).  The shape "
        "family goes straight to the full program from then on.  Rare "
        "by construction — the estimate is doubled; a steady rise means "
        "the estimate is short for this fleet's shapes."),
    COALESCE: (
        "counter", ("what",),
        "The host's merge pass over the new nodes of a device scan "
        "(solver/coalesce.py, inside TpuSolver's extraction; one "
        "increment per extracted scan — the single, the pipelined and "
        "each megabatch slot): 'nodes_in' — new nodes the scan opened; "
        "'merges' — pairs of them replaced by one node of a larger type "
        "at no higher price, so nodes_in less merges is what the answer "
        "keeps; 'pairs' — pairs of nodes whose verdict (the cheapest type "
        "that holds both, or none) the pass computed on its way to those "
        "merges.  Work counts: for one input they read the same whatever "
        "the pass costs (the `coalesce` span times it); merges close to "
        "nodes_in is a scan that opened a node per tiny group and left "
        "the packing to the host, and pairs far above merges is a pass "
        "whose windows hold few pairs that can merge."),
    TRACE_RING_EVICTIONS: (
        "counter", (),
        "Traces evicted from the flight recorder's bounded ring to admit "
        "newer ones (ring capacity: KT_FLIGHT_TRACES)."),
    FLIGHT_DUMPS: (
        "counter", ("reason",),
        "Flight-recorder dumps triggered by anomaly, by reason: "
        "device_hang (hang-guard trip), degraded_solve (warm-tier serve "
        "while the device tier is latched unhealthy), budget_breach (a "
        "trace exceeded KT_TRACE_SLOW_S), sanitizer_error (KT_SANITIZE "
        "lock-discipline violation).  Each dump's JSON envelope (and its "
        "KT_FLIGHT_DIR file name) carries the dumping replica_id and, "
        "when attributable, the session_id, so a fleet's dumps correlate "
        "offline."),
    TRACE_REMOTE_SPANS: (
        "counter", ("outcome",),
        "Server-side RPC traces by how they rooted (fleet-wide tracing, "
        "docs/OBSERVABILITY.md): 'adopted' — the request carried a wire "
        "trace context (trace_id + parent_span on SolveRequest) and this "
        "replica's trace joined the remote parent's tree, so the whole "
        "cross-replica request renders as ONE tree in /fleetz; 'local' — "
        "no context on the wire (old client, direct call, unsampled "
        "origin) and the trace rooted locally."),
    REPLAY_REQUESTS: (
        "counter", ("outcome",),
        "Requests driven through the real gRPC stack by the trace-replay "
        "harness (obs/replay.py), by outcome: 'ok' (served), 'shed' "
        "(typed admission shed or deadline — replayed traffic probing the "
        "server's overload posture), 'error' (transport/server failure)."),
    REPLAY_LAG: (
        "histogram", (),
        "Scheduled-send vs actual-send lag of each replayed request, "
        "seconds — the replayer's own pacing fidelity (a loaded driver "
        "host shows up here, not as silently distorted inter-arrivals)."),
    ADMISSION_ADMITTED: (
        "counter", ("class",),
        "Solve requests admitted into the bounded priority queue, by "
        "priority class (critical / batch / best_effort).  Admitted does "
        "not mean solved: a request can still expire its deadline while "
        "queued (counted in karpenter_admission_shed_total{reason="
        "'deadline'})."),
    ADMISSION_SHED: (
        "counter", ("class", "reason"),
        "Solve requests rejected by admission control, by priority class "
        "and reason: 'queue_full' (class or total queue-depth quota), "
        "'rate_limited' (class token bucket empty), 'concurrency' (class "
        "in-flight quota), 'deadline' (enqueue deadline expired before "
        "dispatch — rejected BEFORE tensorize/dispatch so timed-out work "
        "never burns a device round trip), 'preempted' (evicted from a "
        "full queue by a higher-class arrival), 'brownout' (the load-"
        "responsive degradation ladder reached its shed rung for this "
        "class).  Every shed maps to RESOURCE_EXHAUSTED / "
        "DEADLINE_EXCEEDED on the wire."),
    ADMISSION_QUEUE_DEPTH: (
        "gauge", ("class",),
        "Requests currently held in the admission queue, per priority "
        "class (bounded by the per-class and total queue-depth quotas)."),
    ADMISSION_QUEUE_DELAY: (
        "histogram", (),
        "Enqueue-to-dispatch wait of admitted requests, seconds — the "
        "signal driving the brownout ladder's queue-delay EWMA."),
    ADMISSION_BREAKER_STATE: (
        "gauge", (),
        "Device-path circuit breaker state: 0 closed (TPU path open), "
        "1 half-open (probe traffic only), 2 open (all solves routed to "
        "the host FFD tier until the open interval elapses)."),
    ADMISSION_BREAKER_TRANSITIONS: (
        "counter", ("to",),
        "Circuit-breaker state transitions, by target state (closed / "
        "open / half_open).  The breaker trips on accumulated device-"
        "health failures (hang-guard trips, degraded solves) and re-"
        "closes only after a half-open probe window passes clean."),
    ADMISSION_BROWNOUT_LEVEL: (
        "gauge", (),
        "Current brownout degradation rung (0 = normal): 1 shrink the "
        "coalescer max-wait, 2 cap megabatch slots, 3 route best_effort "
        "to the host FFD reference solver, 4 shed best_effort at "
        "admission.  Driven by the queue-delay EWMA with hysteresis."),
    ADMISSION_HOST_ROUTED: (
        "counter", ("class", "reason"),
        "Admitted solves routed to the host FFD tier instead of the "
        "device path, by class and reason: 'breaker' (circuit open / "
        "half-open non-probe) or 'brownout' (degradation ladder rung 3+ "
        "for this class)."),
    DELTA_RPC: (
        "counter", ("outcome",),
        "Session-routed Solve RPCs (delta serving, docs/ARCHITECTURE.md "
        "round 14), by outcome: 'delta' (an incremental warm-start tier "
        "served the step — the sub-ms fast path), 'fallback_full' (a "
        "warm-start guard tripped and the step re-solved from the stripped "
        "base; the session survives), 'establish' (a full solve created or "
        "replaced the session chain), 'reseed' (a catalog/price epoch bump "
        "re-solved the chain server-side from the stripped base), "
        "'session_unknown' (no live chain — and no adoptable spool "
        "record — for the client's (session, epoch); the client "
        "re-establishes with one full solve), 'drain_refused' (an "
        "establishment refused while this replica drains; the client "
        "re-homes and establishes on a sibling).  A healthy steady-state "
        "fleet is dominated by 'delta'; sustained 'session_unknown' "
        "means the table is too small or the TTL too short "
        "(KT_DELTA_SESSIONS / KT_DELTA_TTL_S)."),
    DELTA_RPC_DURATION: (
        "histogram", (),
        "Server-side wall time of one session-routed RPC dispatch "
        "(session lookup + warm-start step + reply snapshot), seconds."),
    DELTA_SESSIONS: (
        "gauge", (),
        "Live delta sessions currently held in the per-pipeline session "
        "table (bounded by KT_DELTA_SESSIONS; TTL KT_DELTA_TTL_S)."),
    DELTA_EVICTIONS: (
        "counter", ("reason",),
        "Delta sessions evicted from the table, by reason: 'ttl' (idle "
        "past KT_DELTA_TTL_S), 'capacity' (LRU eviction at "
        "KT_DELTA_SESSIONS), 'stop' (pipeline shutdown), 'error' (a "
        "delta step raised mid-apply — the half-mutated chain must not "
        "serve another epoch, so the session dies and the client "
        "re-establishes), 'drain' (graceful fleet handoff: the record is "
        "spooled, the lease released and the entry dropped so a sibling "
        "replica adopts the chain WARM — docs/RESILIENCE.md), "
        "'lease_lost' (this replica's session lease was stolen after "
        "expiry; the chain is dropped rather than served or spooled over "
        "the new owner's record).  An evicted session costs its client "
        "AT MOST one re-establishing full solve ('drain' normally costs "
        "zero — the adopting replica serves warm).  'fault' is the "
        "injected session-table wipe (KT_FAULTS chaos runs only)."),
    SNAPSHOT_WRITES: (
        "counter", ("outcome",),
        "Session-table snapshot writes to the KT_SESSION_DIR spool "
        "(docs/RESILIENCE.md), by outcome: 'written' (spool atomically "
        "replaced: write-temp + fsync + rename), 'empty' (no live "
        "sessions; nothing written), 'error' (serialization or I/O "
        "failed — the previous spool file survives untouched)."),
    SNAPSHOT_SKIPPED: (
        "counter", ("reason",),
        "Sessions left OUT of a snapshot, by reason: 'in_step' (a delta "
        "step was mid-mutation at capture), 'torn' (a step started or "
        "committed while the lock-free writer was pickling the chain; "
        "its bytes are discarded), or 'lease_lost' (the session's spool "
        "lease is now held by a sibling replica — a zombie writer must "
        "never clobber the adopter's record).  Epoch-atomicity: a half-"
        "applied chain is never persisted — a skipped session costs its "
        "client one re-establish after a restart, never a replayed "
        "half-step."),
    SNAPSHOT_RESTORE: (
        "counter", ("outcome",),
        "Session-table restore attempts at pipeline startup, by outcome: "
        "'restored' (live chains rehydrated; restarted replica serves "
        "the next delta of every surviving session warm), 'missing' (no "
        "spool file — plain cold start), 'corrupt' (checksum or decode "
        "failure), 'truncated' (payload shorter than the header "
        "declares), 'version' (snapshot format or chain-schema skew), "
        "'catalog_epoch' (spool written under a different catalog epoch — older or newer), 'error' "
        "(unexpected failure).  Every non-'restored' outcome degrades to "
        "today's cold behavior — never a diverged chain."),
    SNAPSHOT_DURATION: (
        "histogram", (),
        "Wall time of one session-table snapshot write or restore, "
        "seconds."),
    SNAPSHOT_SESSIONS: (
        "gauge", (),
        "Sessions persisted in the most recent snapshot write (0 until "
        "the first write)."),
    SESSION_ADOPTIONS: (
        "counter", ("outcome",),
        "Session-spool adoption attempts (fleet failover, docs/"
        "RESILIENCE.md): any replica can restore a specific session from "
        "the shared KT_SESSION_DIR spool on demand, by outcome: 'adopted' "
        "(free lease claimed, record consumed, next delta serves WARM), "
        "'stolen' (the previous owner's lease had expired — a dead "
        "replica's session picked up after KT_SESSION_LEASE_S), "
        "'lease_held' (typed refusal: a sibling holds an unexpired lease "
        "— two replicas can never both adopt a chain), 'missing' (no "
        "record; the client pays the PR-10 exactly-one re-establish), "
        "'refused' (record failed the envelope checks — also counted "
        "per-reason in the restore family), 'error' (unexpected failure; "
        "cold start)."),
    SESSION_LEASES: (
        "gauge", (),
        "Session-spool leases this replica currently holds (owned "
        "sessions with a spool record under the shared KT_SESSION_DIR).  "
        "0 when no spool is configured."),
    FLEET_ENDPOINTS: (
        "gauge", ("state",),
        "Solver-fleet endpoints as seen by the fleet-aware client "
        "(KT_FLEET_ENDPOINTS), by state: 'known' (configured), 'healthy' "
        "(serving), 'draining' (answered the graceful-drain hint; new "
        "sessions route elsewhere until the pod dies)."),
    FLEET_FAILOVERS: (
        "counter", ("reason",),
        "Sessions re-homed to a different solver replica by the fleet-"
        "aware client, by reason: 'death' (transport failure outlived "
        "the retry budget — the replica is gone; the adopting replica "
        "restores the chain from the shared spool and serves the next "
        "delta warm) or 'drain' (the serving replica answered "
        "session_state='draining'; the client proactively re-homes "
        "before the pod dies)."),
    FAULTS_INJECTED: (
        "counter", ("kind", "site"),
        "Faults the KT_FAULTS injection plane fired, by kind and choke-"
        "point site (docs/RESILIENCE.md fault catalog).  Production runs "
        "the zero-cost no-op plane; any sample here means a chaos "
        "schedule is live."),
    FAULTS_RECOVERED: (
        "counter", ("site", "outcome"),
        "Recovery outcomes observed at faultable choke points, by site "
        "and outcome: 'retried' (transport retry rode through), "
        "'fallback' (served by a degraded tier), 'evicted' (session "
        "dropped; client re-establishes), 'cold' (snapshot refused; "
        "cold start), 'skipped' (work bypassed), 'failed' (typed error "
        "surfaced to the caller), 'ok' (recovered in place).  Counted "
        "for REAL faults too, not just injected ones — KT016 pins that "
        "every recovering except on a faultable path lands here."),
    RELAX_TOTAL: (
        "counter", ("outcome",),
        "Convex-relaxation refinement rung evaluations on device-tier "
        "solves (KT_RELAX), by outcome: 'improved' (the relax+round "
        "solution cost strictly less than the scan's and shipped), 'tied' "
        "(the rung reached the scan's cost; the scan solution ships), "
        "'fallback' (rounding/repair could not produce a valid cheaper "
        "solution, or the rung errored — the scan solution ships "
        "unchanged), 'skipped' (the rung was enabled but did not run: no "
        "eligible unconstrained pod groups, relax program still compiling "
        "behind, or a cold-served / budget-constrained solve).  The "
        "shipped solution is min(scan, relax+round) by construction — "
        "never worse than the scan."),
    RELAX_DURATION: (
        "histogram", (),
        "Wall time of one relax-rung evaluation (eligibility partition + "
        "fixed-iteration device solve + rounding/repair + cost compare), "
        "seconds."),
    RELAX_IMPROVEMENT: (
        "gauge", (),
        "Node-cost ratio relax/scan of the most recent relax-rung run "
        "that reached a comparison (improved/tied/fallback): < 1.0 means "
        "the rung found a cheaper packing than the vectorized FFD scan."),
    WARMSTART_SOLVES: (
        "counter", ("mode",),
        "Warm-start delta solves, by serving mode: 'noop' (removals only "
        "— pure host bookkeeping), 'host' (unconstrained added pods "
        "first-fit into surviving residual capacity, no device dispatch), "
        "'scan' (the displaced subproblem ran the device scan seeded from "
        "the previous assignment), 'full' (the perturbation exceeded "
        "KT_DELTA_MAX_FRAC or a coupling guard tripped — full re-solve).  "
        "A healthy steady-state chain is dominated by noop/host."),
    WARMSTART_DURATION: (
        "histogram", (),
        "Wall time of one warm-start delta step (bookkeeping + any "
        "subproblem solve), seconds."),
    WARMSTART_DISPLACED: (
        "histogram", (),
        "Pods the delta step had to (re-)place: added pods plus pods "
        "displaced off reclaimed nodes."),
    CONSOLIDATION_SWEEPS: (
        "counter", ("path",),
        "Consolidation what-if sweeps, by execution path: 'batched' "
        "(every candidate served as a slot of a vmapped device dispatch — "
        "one dispatch, one fence), 'serial' (every candidate on the "
        "per-candidate fallback: non-device backend, cold sweep program, "
        "or a candidate set the batch guards rejected), or 'mixed' (some "
        "slots batched, the rest re-solved serially — infeasible / "
        "needs-new-node slots and per-candidate carve-outs)."),
    CONSOLIDATION_SWEEP_SLOTS: (
        "histogram", (),
        "Candidate what-ifs per batched sweep dispatch (the N that used "
        "to cost N sequential solver round trips)."),
    CONSOLIDATION_SWEEP_DURATION: (
        "histogram", (),
        "Wall time of one consolidation what-if sweep (all candidates, "
        "either path), seconds."),
    MULTIHOST_FENCE_BYTES: (
        "counter", ("scope",),
        "Per-host megabatch fence byte accounting (ISSUE 14): 'read' is "
        "what this serving process actually transferred D2H (only its "
        "jax.process_index()-addressable slot shards of the carry), "
        "'whole' is what the legacy whole-batch readback would have "
        "transferred.  read/whole per host sits at ~1/N on an N-host "
        "mesh; KT_MULTIHOST=0 forces the legacy path (read == whole)."),
    MULTIHOST_SLOTS: (
        "counter", ("ownership",),
        "Real (non-padding) megabatch slots demuxed by a multi-process "
        "fence, by ownership: 'owned' (this process held the slot's "
        "shards, extracted and responded locally) vs 'foreign' (another "
        "host owns it — resolved typed SlotNotOwned and handed to the "
        "forwarding shim)."),
    MULTIHOST_FORWARDS: (
        "counter", ("outcome",),
        "Foreign-slot requests routed through the cross-host result-"
        "forwarding shim (parallel/forward.py, KT_MULTIHOST_PEERS): "
        "'forwarded' (served by the owning host over the fleet "
        "transport), 'error' (the owner's endpoint failed), 'unrouted' "
        "(shim disabled / owner unknown — the typed error surfaced to "
        "the caller)."),
    MULTIHOST_UNIFIED: (
        "counter", (),
        "Mixed-bucket flushes whose dims UNIFIED into the dominant "
        "bucket's program (solver/tpu.py unify_mega_keys): the whole "
        "flush shared one mesh dispatch instead of serial per-bucket "
        "ones.  Counted once per unified DISPATCH, at the collector's "
        "group merge (the coalescer's unify join feeds the same flush, "
        "so it does not count separately)."),
    HIER_SOLVES: (
        "counter", ("path",),
        "Batches at/above KT_HIER_THRESHOLD pods by routing outcome: "
        "'hierarchical' (block decomposition + price reconciliation "
        "served), 'fallback_cold' (block program still compiling; flat "
        "served while compile-behind warms), 'fallback_structure' (one "
        "coupling component / inexpressible pods / existing-node batch — "
        "flat is the right program), 'fallback_degraded' (a block wave "
        "hung or errored; flat's degradation ladder served)."),
    HIER_BLOCKS: (
        "histogram", (),
        "Weakly-coupled blocks per hierarchical solve after LPT packing "
        "of the constraint-reachability components into megabatch slots."),
    HIER_PRICE_ITERATIONS: (
        "histogram", (),
        "Price-ascent waves actually run per hierarchical solve (0 = no "
        "shared-capacity contention after the first block wave; capped at "
        "KT_HIER_PRICE_ITERS)."),
    HIER_REPAIR_PODS: (
        "histogram", (),
        "Straggler pods re-seated by the host-side repair pass after the "
        "price budget expired (limit-evicted nodes' pods + block-"
        "infeasible pods)."),
    HIER_DURATION: (
        "histogram", (),
        "End-to-end hierarchical solve duration, seconds (partition + "
        "block waves + price loop + repair; excludes tensorize, reported "
        "separately like flat's solve_ms)."),
    TS_SAMPLES: (
        "counter", (),
        "Registry snapshots taken by the time-series sampler "
        "(obs/timeseries.py; one per KT_TS_INTERVAL_S tick)."),
    TS_SERIES: (
        "gauge", (),
        "Distinct (family, label-set) series currently held in the "
        "sampler's ring buffers (each bounded at KT_TS_CAPACITY points)."),
    TS_SAMPLE_DURATION: (
        "histogram", (),
        "Wall time of one sampler tick (registry snapshot + occupancy "
        "hooks), seconds — the sampler's own cost."),
    SLO_REQUESTS: (
        "counter", ("class", "outcome"),
        "Solve RPCs by priority class and SLO outcome: 'ok' served, "
        "'shed' typed admission shed or deadline (availability-bad by "
        "the objective even though the protection worked), 'error' "
        "unexpected failure.  The availability objective's numerator/"
        "denominator source."),
    SLO_LATENCY: (
        "histogram", ("class",),
        "Served solve latency by priority class, seconds (solve_ms as "
        "reported to the caller).  Windowed bucket deltas feed the "
        "latency objective's p99-above-threshold burn rate."),
    SLO_BURN_RATE: (
        "gauge", ("class", "objective", "window"),
        "Error-budget burn rate per class/objective/window: 1.0 burns "
        "exactly the budget over the window; >= KT_SLO_FAST_BURN on a "
        "short window pages (breach verdict).  Refreshed by each "
        "SloEngine.evaluate() (/sloz)."),
    SLO_BUDGET_REMAINING: (
        "gauge", ("class", "objective"),
        "Lifetime error budget remaining, 1.0 = untouched, <= 0 = "
        "exhausted (breach).  budget = 1 - target."),
    SLO_VERDICT: (
        "gauge", ("class",),
        "Per-class SLO verdict: -1 no_data, 0 ok, 1 warn (a window "
        "burning faster than budget), 2 breach (budget exhausted or "
        "fast-burn page)."),
    OCCUPANCY_DEVICE_BUSY: (
        "gauge", (),
        "Share of wall time the device spent in dispatch/fence spans "
        "over the last sampler interval (span-derived, scaled by the "
        "tracer's sampling rate); ~1.0 = device-bound fleet, ~0 = "
        "over-provisioned."),
    OCCUPANCY_SLOT_FILL: (
        "gauge", (),
        "Mean occupied megabatch slots per dispatch over the last "
        "sampler interval (windowed mean of "
        "karpenter_solver_megabatch_slots); 0 when no megabatch was "
        "dispatched in the window."),
    OCCUPANCY_DELTA_INLINE: (
        "gauge", (),
        "Fraction of delta solves served inline on the RPC thread "
        "(no dispatcher window span) over the last sampler interval — "
        "high values mean the pipeline is idle enough that the delta "
        "shortcut dominates."),
    TUNING_STEPS: (
        "counter", ("knob", "outcome"),
        "Feedback-controller decisions by knob and outcome: 'applied' a "
        "lattice step taken (probe window opened), 'kept' the probe "
        "window confirmed it, 'reverted' the window regressed the "
        "objective and the step rolled back, 'frozen' no move while a "
        "class burn rate was warn+, 'skipped' no move (no windowed "
        "data, lattice edge, or frozen knob)."),
    TUNING_KNOB_VALUE: (
        "gauge", ("knob",),
        "Current live value of each registry knob (bools as 0/1) — the "
        "value serving decision points snapshot, env default or tuned "
        "override."),
    TUNING_STEP_DURATION: (
        "histogram", (),
        "Wall time of one controller decision (windowed reads + SLO "
        "evaluation + the move), seconds — the controller's own cost."),
    FLEET_PEER_FETCH: (
        "counter", ("outcome",),
        "Per-peer /fleetz fan-out fetches by outcome ('ok' / 'timeout' "
        "/ 'error'); failed peers are marked stale in the merged view "
        "instead of degrading the whole aggregation."),
    GANG_GANGS: (
        "counter", ("outcome",),
        "Gangs judged by the all-or-nothing epilogue (docs/GANGS.md), by "
        "outcome: 'placed' (every member seated; scan placement kept), "
        "'packed' (every member seated and the co-location repack adopted "
        "a strictly cheaper node-cost + spread objective), 'retracted' (a "
        "member was infeasible, so the whole gang's seats were retracted "
        "and every member surfaced with the typed GangUnplaced reason — a "
        "partial gang placement is impossible by construction)."),
    GANG_SPREAD_ZONES: (
        "histogram", (),
        "Distinct zones each fully-placed gang's members landed on (1 = "
        "perfectly co-located; the spread penalty the gang epilogue "
        "minimizes weighs zones first, node classes second)."),
    GANG_SPREAD_CLASSES: (
        "histogram", (),
        "Distinct node classes (instance types — the rack proxy) each "
        "fully-placed gang's members landed on."),
    GANG_DURATION: (
        "histogram", (),
        "Wall time of one gang epilogue pass (membership audit + any "
        "retraction re-solve + co-location repack what-ifs), seconds; "
        "gang-free batches skip the pass entirely."),
}


def decorate(provider, reg: Optional[Registry] = None):
    """Wrap every public method of a CloudProvider in a duration histogram
    (core metrics.Decorate analog)."""
    reg = reg or registry
    hist = reg.histogram(CLOUDPROVIDER_DURATION)

    class Decorated:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            attr = getattr(self._inner, name)
            if not callable(attr) or name.startswith("_"):
                return attr

            def wrapped(*args, **kw):
                t0 = time.perf_counter()
                try:
                    return attr(*args, **kw)
                finally:
                    hist.observe(
                        time.perf_counter() - t0,
                        {"controller": "cloudprovider", "method": name},
                    )

            return wrapped

    return Decorated(provider)
