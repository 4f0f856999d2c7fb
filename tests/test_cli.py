"""CLI surface (`karpenter-tpu` / karpenter_tpu/cli.py)."""

import json

import pytest

from karpenter_tpu.cli import main


def test_solve_generated(capsys):
    rc = main(["solve", "--small", "--pods", "12", "--backend", "oracle",
               "--compact"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["scheduled"] == 12
    assert out["infeasible"] == 0
    assert out["new_nodes"] >= 1


def test_solve_scenario_file(tmp_path, capsys):
    doc = {
        "pods": [{"name": f"w{i}", "requests": {"cpu": 2.0}} for i in range(4)],
        "provisioners": [{"name": "default"}],
    }
    f = tmp_path / "scenario.json"
    f.write_text(json.dumps(doc))
    rc = main(["solve", "--small", "--scenario", str(f), "--backend", "oracle",
               "--assignments", "--compact"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert set(out["assignments"]) == {"w0", "w1", "w2", "w3"}


def test_solve_infeasible_exit_code(tmp_path, capsys):
    doc = {"pods": [{"name": "giant", "requests": {"cpu": 10000.0}}]}
    f = tmp_path / "s.json"
    f.write_text(json.dumps(doc))
    rc = main(["solve", "--small", "--scenario", str(f), "--backend", "oracle",
               "--compact"])
    assert rc == 3


def test_metrics_doc_up_to_date(tmp_path, capsys):
    """docs/METRICS.md must match the inventory (regenerate via
    `karpenter-tpu metrics-doc` after metric changes)."""
    rc = main(["metrics-doc", "--check", "--out", "docs/METRICS.md"])
    assert rc == 0


def test_version(capsys):
    assert main(["version"]) == 0
    assert "karpenter-tpu" in capsys.readouterr().out


def test_inventory_metrics_are_emitted(small_catalog):
    """Every metric documented in metrics.INVENTORY must actually be emitted
    by a full provision -> interrupt -> consolidate controller pass (the
    generated docs must not advertise dead series)."""
    from karpenter_tpu.cloud.fake import FakeCloudProvider
    from karpenter_tpu.controllers.deprovisioning import (
        MIN_NODE_LIFETIME, DeprovisioningController,
    )
    from karpenter_tpu.controllers.interruption import (
        SPOT_INTERRUPTION, InterruptionController, InterruptionMessage,
        MessageQueue,
    )
    from karpenter_tpu.controllers.provisioning import ProvisioningController
    from karpenter_tpu.controllers.state import ClusterState
    from karpenter_tpu.controllers.termination import TerminationController
    from karpenter_tpu.events import Recorder
    from karpenter_tpu.metrics import INVENTORY, Registry, decorate
    from karpenter_tpu.models.pod import PodSpec
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.solver.scheduler import BatchScheduler
    from karpenter_tpu.utils.clock import FakeClock

    clock = FakeClock()
    state = ClusterState(clock=clock)
    reg = Registry()
    cloud = decorate(FakeCloudProvider(small_catalog, clock=clock), reg)
    rec = Recorder()
    sched = BatchScheduler(backend="oracle", registry=reg)
    prov_ctrl = ProvisioningController(state, cloud, scheduler=sched,
                                       recorder=rec, registry=reg, clock=clock)
    term = TerminationController(state, cloud, recorder=rec, registry=reg, clock=clock)
    deprov = DeprovisioningController(state, cloud, term, provisioning=prov_ctrl,
                                      scheduler=sched, recorder=rec,
                                      registry=reg, clock=clock)
    queue = MessageQueue()
    ic = InterruptionController(state, term, queue, recorder=rec,
                                registry=reg, clock=clock)
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.requirements import IN, Requirement

    state.apply_provisioner(Provisioner(
        name="default", consolidation_enabled=True, limits={"cpu": 1000.0},
        requirements=[Requirement(L.INSTANCE_TYPE, IN, ["c5.2xlarge"])],
    ))
    for i in range(30):
        state.add_pod(PodSpec(name=f"p{i}", requests={"cpu": 0.5}, owner_key="d"))
    prov_ctrl.reconcile(); clock.advance(1.5); prov_ctrl.reconcile()
    assert len(state.nodes) >= 2
    ns = next(iter(state.nodes.values()))
    queue.send(InterruptionMessage(SPOT_INTERRUPTION,
                                   ns.machine.provider_id, clock.now()))
    ic.reconcile()
    prov_ctrl.reconcile(); clock.advance(1.5); prov_ctrl.reconcile()
    # shrink the workload so consolidation finds a delete
    for p in list(state.pods)[: len(state.pods) - 3]:
        state.delete_pod(p)
    clock.advance(MIN_NODE_LIFETIME + 1)
    assert deprov.reconcile() is None  # proposes; 15s validation TTL armed
    clock.advance(16)
    action = deprov.reconcile()        # re-validated and executed
    assert action is not None

    # compile-behind metrics: a cold device shape served by the warm tier
    import time as _time

    auto_sched = BatchScheduler(backend="auto", registry=reg, native_batch_limit=4)
    auto_sched.solve(
        [PodSpec(name=f"cold{i}", requests={"cpu": 1.0}) for i in range(8)],
        [Provisioner(name="default").with_defaults()],
        small_catalog,
    )
    # generous cap: on the 1-core CI host a background XLA compile under
    # full-suite load can take minutes; a timeout here surfaces as a
    # missing compile-duration metric below
    t0 = _time.time()
    while auto_sched._tpu.compiles_in_flight() > 0 and _time.time() - t0 < 600:
        _time.sleep(0.05)

    emitted = (set(reg.counters) | set(reg.gauges) | set(reg.histograms))
    # the remote-solver pair is emitted only by the split-topology
    # deployment's RemoteScheduler (zero-initialized at its construction);
    # their emission is asserted by tests/test_split_topology.py:118-144 and
    # tests/test_service.py:217-232, so this single-process scenario carves
    # them out rather than spinning up a gRPC sidecar here
    from karpenter_tpu.metrics import (
        REMOTE_DEGRADED,
        REMOTE_FALLBACK_SOLVES,
        REQUEST_CATALOG,
        REQUEST_CATALOG_SENT,
        REQUEST_DECODE_PODS,
        REQUEST_ENCODE_PODS,
    )

    # likewise the admission family: emitted by the solver SERVICE's
    # AdmissionControl (one per SolvePipeline), which this in-process
    # scenario never constructs; full-population zero-init is asserted by
    # tests/test_metrics_init.py::TestAdmissionSeries and exercised end to
    # end by tests/test_admission.py
    admission_family = {m for m in INVENTORY if m.startswith("karpenter_admission_")}

    # the delta-serving family rides the SolvePipeline's session table
    # (service/delta.py), same service-side precedent as admission: full-
    # population zero-init is asserted by tests/test_metrics_init.py::
    # TestDeltaSeries and exercised end to end by tests/test_delta_serving.py
    delta_family = {m for m in INVENTORY
                    if m.startswith("karpenter_solver_delta_")}

    # session durability + fault plane (ISSUE 12): service-side like the
    # two families above — the snapshot spool rides the SolvePipeline
    # (KT_SESSION_DIR) and the injection plane only exists under KT_FAULTS;
    # full-population zero-init is asserted by tests/test_metrics_init.py::
    # TestResilienceSeries and exercised end to end by tests/test_faults.py
    resilience_family = {m for m in INVENTORY
                         if m.startswith("karpenter_solver_session_")
                         or m.startswith("karpenter_faults_")}

    # the fleet family is CLIENT-side (FleetClient, service/client.py):
    # zero-inited at its construction, asserted by tests/test_metrics_init
    # ::TestFleetSeries and exercised end to end by tests/test_fleet.py
    fleet_family = {m for m in INVENTORY if m.startswith("karpenter_fleet_")}

    # the multihost forwarding shim is service-side (SolvePipeline's
    # ResultForwarder) like the admission precedent: full-population
    # zero-init asserted by tests/test_metrics_init.py::TestMultihostSeries
    # and exercised by tests/test_multihost.py (the scheduler-side
    # multihost families — fence bytes, slot ownership, unified flushes —
    # ARE emitted here via BatchScheduler's zero-init)
    multihost_shim = {m for m in INVENTORY
                      if m.startswith("karpenter_solver_multihost_forwards")}

    # the time-resolved telemetry plane (ISSUE 18) is service-side like
    # admission: the sampler/SLO-engine/occupancy trio rides the solver
    # SERVICE (server.make_server wires Sampler + SloEngine +
    # OccupancyAccountant per replica), which this in-process controller
    # scenario never constructs; full-population zero-init is asserted by
    # tests/test_metrics_init.py::TestSloSeries and exercised end to end
    # by tests/test_timeseries.py and scripts/slo_demo.py
    slo_family = {m for m in INVENTORY
                  if m.startswith("karpenter_ts_")
                  or m.startswith("karpenter_slo_")
                  or m.startswith("karpenter_occupancy_")}

    # the self-tuning family (ISSUE 19) is service-side for the same
    # reason: SolverService wires the TuningController/knob gauges per
    # replica; full-population zero-init is asserted by tests/
    # test_tuning.py::test_zero_init_registers_full_population and the
    # family is exercised end to end by the controller tests and
    # scripts/tune_demo.py
    tuning_family = {m for m in INVENTORY
                     if m.startswith("karpenter_tuning_")}

    # the replay family is DRIVER-side (obs/replay.Replayer): zero-inited
    # at its construction, asserted by tests/test_metrics_init.py::
    # TestFleetTracingSeries and exercised end to end by
    # tests/test_fleet_trace.py::TestReplayCapture (the trace-remote
    # family, by contrast, IS emitted here via the Tracer's zero-init)
    replay_family = {m for m in INVENTORY
                     if m.startswith("karpenter_replay_")}

    # the door's pod counter (ISSUE 26) is service-side as well: zero-
    # inited where SolverService is constructed and moved by every Solve
    # RPC, both asserted by tests/test_codec_templates.py (the ``served``
    # fixture and test_the_door_counts_what_it_stamped); its client-side
    # mirror (ISSUE 30) belongs to RemoteScheduler like the remote-solver
    # pair (tests/test_codec_client_templates.py); the catalog's pair
    # (ISSUE 33) sits at the same two places: tests/test_catalog_digest.py
    missing = (set(INVENTORY) - emitted - admission_family - delta_family
               - resilience_family - fleet_family - multihost_shim
               - replay_family - slo_family - tuning_family
               - {REMOTE_DEGRADED, REMOTE_FALLBACK_SOLVES,
                  REQUEST_DECODE_PODS, REQUEST_ENCODE_PODS,
                  REQUEST_CATALOG, REQUEST_CATALOG_SENT})
    assert not missing, (
        f"documented metrics never emitted: {sorted(missing)} "
        f"(warm debug: in_flight={auto_sched._tpu.compiles_in_flight()} "
        f"ready={len(auto_sched._tpu._ready)} queued={auto_sched._tpu._queued} "
        f"failed={auto_sched._tpu._failed_until} "
        f"stopped={auto_sched._tpu._stopped})"
    )


def _run_py(code_or_argv, env_extra, cwd=None):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.update(env_extra)
    argv = (["-c", code_or_argv] if isinstance(code_or_argv, str)
            else list(code_or_argv))
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=600, env=env, cwd=cwd or repo)


def test_jit_cache_dir_populates(tmp_path):
    """The persistent compile cache is placed from OUTSIDE the program:
    with ``JAX_COMPILATION_CACHE_DIR`` exported (deploy/solver.yaml does) a
    device-path solve must write a cache entry there that a restarted
    process can reload (the cross-restart half of the cold-start story).
    Run as a subprocess — the cache wiring is process-global jax config.
    The cache-write assertion relies on the solver compile exceeding the
    0.5 s min-compile-time threshold ``_init_jit_cache`` sets — solver
    compiles are seconds on any backend, so the margin is structural."""
    import json as _json

    out = _run_py(
        ["-m", "karpenter_tpu.cli", "solve", "--backend", "tpu",
         "--pods", "8", "--small", "--compact"],
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-500:]
    doc = _json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["scheduled"] == 8 and doc["infeasible"] == 0
    assert any(tmp_path.iterdir()), "persistent compile cache is empty"


_CACHE_PROBE = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from karpenter_tpu.solver import tpu\n"
    "tpu.TpuSolver()\n"
    "print(repr(before), repr(jax.config.jax_compilation_cache_dir),\n"
    "      repr(tpu.jit_cache_dir()),\n"
    "      jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def test_jit_cache_env_set_means_no_directory_set_in_code(tmp_path):
    """Env set: JAX has already taken the directory at import; constructing
    a solver changes nothing about it and only adds the threshold."""
    out = _run_py(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-500:]
    before, after, resolved, secs = out.stdout.split()
    assert before == after == resolved == repr(str(tmp_path))
    assert float(secs) == 0.5


def test_jit_cache_env_unset_resolves_to_one_fixed_in_checkout_path(tmp_path):
    """Env unset: the fixed ``<repo>/.jax_cache`` — the same path from two
    processes started in different working directories (the directory is
    part of jax's cache key: a path that moves never hits twice)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = []
    for cwd in (repo, str(tmp_path)):
        out = _run_py(_CACHE_PROBE, {}, cwd=cwd)
        assert out.returncode == 0, out.stderr[-500:]
        before, after, resolved, secs = out.stdout.split()
        assert before == "None"
        assert after == resolved
        assert float(secs) == 0.5
        seen.append(after)
    assert seen[0] == seen[1] == repr(os.path.join(repo, ".jax_cache"))
