PYTHON ?= python
CXX ?= g++
CXXFLAGS ?= -O2 -fPIC -shared -Wall -std=c++17

.PHONY: all test native proto bench clean battletest lint modelcheck obs-demo obs-fleet-demo overload-demo slo-demo chaos chaos-fleet multihost-dryrun hier-demo tune-demo

all: native proto

# The binding compiles (and loads) a source-hash-keyed .so; this target just
# forces the build eagerly and prints the ABI version.
native: native/ffd.cpp
	$(PYTHON) -c "from karpenter_tpu.solver import native; print(native.version())"

proto: karpenter_tpu/service/solver_pb2.py

# protoc is not in the image; gen_proto.py re-emits the module from the
# protobuf runtime's serialized descriptor (idempotent, --check in CI)
karpenter_tpu/service/solver_pb2.py: karpenter_tpu/service/solver.proto
	$(PYTHON) scripts/gen_proto.py

test:
	$(PYTHON) -m pytest tests/ -x -q

# ktlint: the repo-specific AST analyzer (rule catalog in docs/ANALYSIS.md);
# exits non-zero on any unsuppressed KT001-KT023 finding — includes the
# whole-program call-graph passes (KT012 lock-order deadlocks, KT013
# interprocedural fence reachability, KT014 compile-surface audit) and the
# v3 gates (KT021 proto wire-compat vs the golden descriptor, KT022
# KT_* knob/README drift);
# tests/test_lint.py speed-gates the full run (<5s cold, <1.5s warm cache)
lint:
	$(PYTHON) -m karpenter_tpu.analysis

# protocol model checking (docs/ANALYSIS.md v3, ISSUE 17): bounded
# exhaustive exploration of the delta-session epoch protocol and the
# lease/claim/steal/drain protocol over ALL thread/replica interleavings
# — exactly-one lease winner, per-session epoch monotonicity, no serve
# from a half-mutated chain, drained-never-served-by-drainer, cumulative
# retry convergence — plus the automaton-simulation relation the runtime
# conformance checker (chaos-fleet + replay) judges traces against.
# Prints state-space sizes; exits 1 with a counterexample trace on any
# violation.  tests/test_model.py speed-gates the bounded config.
modelcheck:
	$(PYTHON) -m karpenter_tpu.analysis --model

# the reference's battletest analog (Makefile:69-76: -race + randomized
# order + random delays): lint gate, then widened seeded churn/fuzz/race
# sweep and the suite, both under KT_SANITIZE=1 — the lock-discipline
# sanitizer (analysis/sanitize.py) wraps BatchScheduler / SolvePipeline /
# InflightQueue / TensorizeCache in lock-assertion proxies that raise on
# cross-thread re-entrancy, and every tracked component lock in an
# order-asserting proxy that raises on a runtime inversion of the KT012
# global lock order (the -race analog for our threading contracts)
battletest: lint
	KT_SANITIZE=1 KT_BATTLE_SEEDS=24 KT_FUZZ_SEEDS=40 $(PYTHON) -m pytest tests/test_battle.py tests/test_fuzz_parity.py -q
	KT_SANITIZE=1 $(PYTHON) -m pytest tests/ -q

# one cell of the benchmark the driver runs (BENCHMARK.json lists them
# all): the served path on one TPU, one JSON line last; exits non-zero
# without a TPU
bench:
	$(PYTHON) benchmarks/run.py --workload c2.burst --seed 1 --seconds 45 --trace 0

# observability demo (docs/OBSERVABILITY.md): run the fake-cloud operator
# demo with tracing on and print a /tracez + /statusz snapshot — per-span
# p50/p99 over the run plus the recent per-solve trace trees
obs-demo:
	JAX_PLATFORMS=cpu $(PYTHON) -m karpenter_tpu.operator --demo --small --pods 60 --tracez

# fleet-tracing demo (docs/OBSERVABILITY.md fleet section, ISSUE 15):
# 3 unix-socket replicas sharing one spool, each with its own obs HTTP
# endpoint; a delta session establishes, its home replica is hard-killed
# mid-chain, the chain continues WARM on a steal-adopting sibling, and
# the merged /fleetz view is fetched over real HTTP from a survivor —
# printing per-replica load, the session-ownership map, and the
# session's cross-replica trace timeline (ONE remote-parent-linked tree
# spanning the dead replica's establishment and the sibling's deltas)
obs-fleet-demo:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/fleet_trace_demo.py

# SLO burn-rate demo (docs/OBSERVABILITY.md SLO section, ISSUE 18): an
# overdriven mixed-class replay against an in-process replica with
# best_effort admission throttled to a trickle — best_effort sheds and
# burns its availability budget to breach while critical rides its
# reserved quota and stays green; prints the per-class /sloz verdict
# table (multi-window burn rates, budget remaining) plus the occupancy
# gauges, and exits non-zero if the split does not show
slo-demo:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/slo_demo.py

# admission demo (docs/ADMISSION.md): 4x closed-loop overdrive of mixed
# critical/best_effort clients through the solve pipeline with tight
# quotas — prints the per-class admitted/shed scoreboard, p50/p99,
# breaker state and brownout level
overload-demo:
	JAX_PLATFORMS=cpu $(PYTHON) -m karpenter_tpu.admission

# chaos harness (docs/RESILIENCE.md, ISSUE 12): a composed seeded
# KT_FAULTS schedule (8 fault kinds: transport UNAVAILABLE/reset,
# mid-step + mid-commit exceptions, injected latency, session-table wipe,
# TTL clock jump, spool corruption/truncation) drives a churn chain over
# real gRPC judged against a fault-free oracle chain — every recovery
# must end byte-identical, every error typed, recovery cost <= 1 full
# solve per fault — then the kill-and-restart scenario both WITH the
# session snapshot (zero re-establishes; every session resumes warm) and
# WITHOUT (exactly one re-establish per client).  A tier-1-sized seeded
# rung of the same schedules runs in tests/test_faults.py.
chaos:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_drive.py
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_drive.py --restart
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_drive.py --restart --no-snapshot

# fleet-failover chaos (docs/RESILIENCE.md, ISSUE 13): 3 replicas sharing
# one session spool behind fleet-aware clients, judged per step against a
# fault-free oracle.  The seed matrix (KT_FLEET_SEEDS, CI-friendly: each
# seed re-rolls the session ids and therefore the rendezvous placement,
# the victim, and the kill timing) runs every scenario per seed:
#   kill       hard kill-one-of-three -> lease-steal adoption, ZERO
#              re-establishes, byte-parity vs the oracle chain
#   drain      graceful drain-one-of-three -> DRAINING hints, proactive
#              re-home, ZERO re-establishes
#   kill-cold  the no-spool baseline -> exactly one re-establish per
#              orphaned session (the PR-10 floor)
#   contend    two survivors adopt the same dead session concurrently ->
#              exactly one lease winner, typed refusal for the loser
#   stale      spool rolled back to pre-kill records -> adoption succeeds
#              but the epoch check refuses the stale chain: one typed
#              re-establish per session, never a silent divergence
# Every scenario also runs under the ISSUE-17 conformance tap: the
# per-session protocol-transition sequences observed across the whole
# fleet must each be a path of the model-checked session automaton
# (analysis/conformance.py; violations fail the run).
KT_FLEET_SEEDS ?= 23 24 25
chaos-fleet:
	for seed in $(KT_FLEET_SEEDS); do \
	  for mode in kill drain kill-cold contend stale; do \
	    JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_drive.py --fleet \
	      --mode $$mode --seed $$seed || exit 1; \
	  done; \
	done

# multi-host megabatch dryrun (ISSUE 14): 2 real jax.distributed
# processes x 4 virtual CPU devices each serve one coalesced megabatch
# SPMD — per-host fences read EXACTLY 1/2 of the whole-batch bytes
# (addressable shards only), foreign slots resolve typed SlotNotOwned
# with the true owner, owned slots byte-identical to single-process
# serial solves; then the single-process lone-request A/B (per-host
# fence vs whole-batch readback).  Skips cleanly when the jaxlib has no
# gloo CPU collectives (the tests/test_parallel.py capability probe).
multihost-dryrun:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/dryrun_multihost.py
	JAX_PLATFORMS=cpu $(PYTHON) scripts/dryrun_multihost.py --lone-ab

# million-pod hierarchical walk (ISSUE 16): partition the real 1M-pod
# group shape into megabatch blocks, run a CPU-sized hierarchical solve
# end to end (one vmapped block wave, dual price loop under a contended
# provisioner limit, warm-start repair + cross-block tail repack), and
# print the dev-host 1M scale model (its device wave is "not measured":
# no run on the chip reaches this path).
hier-demo:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/hier_demo.py

# self-tuning demo (docs/TUNING.md, ISSUE 19): replay a seeded bursty
# capture three ways — static env-default knobs, the feedback controller
# learning live (KT_TUNE=1 on a compressed cadence), and a fresh replica
# judged on the learned posture with the controller off — then print the
# before/after knob table and the throughput / critical-p99 scoreboard.
# Exits non-zero if the learned posture breaks the never-worse contract
# (the three constants at the top of scripts/tune_demo.py).
tune-demo:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/tune_demo.py

clean:
	rm -f karpenter_tpu/solver/_native*.so
