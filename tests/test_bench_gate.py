"""The bench's absolute budget gates (bench.py:check_budgets) and its refusal
to measure anywhere but on a TPU."""

import importlib.util
import json

import pytest

spec = importlib.util.spec_from_file_location(
    "benchmod_gate", __file__.rsplit("/tests/", 1)[0] + "/bench.py")
benchmod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(benchmod)


class TestBudgetGate:
    """Absolute per-round budgets (bench.check_budgets): steady-state
    tensorize under threshold, cached-path byte parity, FFD cost parity."""

    BASE = {"tensorize_steady_ms": 3.2, "tensorize_parity": True,
            "cost_ratio_vs_ffd": 0.99,
            "tensorize_cold_ms": 200.0, "tensorize_shape_ms": 110.0}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.BASE)) == {}

    def test_steady_tensorize_over_budget_flagged(self):
        out = benchmod.check_budgets(
            dict(self.BASE, tensorize_steady_ms=31.0))
        assert any("tensorize" in f for f in out["budget_flags"])

    def test_shape_tier_regression_flagged(self):
        # the shape tier (fresh objects) regressing back toward the cold
        # build must trip the gate even while the identity tier stays fast
        out = benchmod.check_budgets(
            dict(self.BASE, tensorize_shape_ms=190.0))
        assert any("shape-tier" in f for f in out["budget_flags"])

    def test_parity_break_flagged(self):
        out = benchmod.check_budgets(dict(self.BASE, tensorize_parity=False))
        assert any("diverged" in f for f in out["budget_flags"])

    def test_cost_ratio_over_ceiling_flagged(self):
        out = benchmod.check_budgets(dict(self.BASE, cost_ratio_vs_ffd=1.03))
        assert any("cost_ratio" in f for f in out["budget_flags"])

    def test_missing_fields_not_flagged(self):
        # records from before the cached-tensorize round carry none of the
        # new fields; the gate must not fire on their absence
        assert benchmod.check_budgets({"value": 100.0}) == {}

    def test_trace_overhead_over_budget_flagged(self):
        out = benchmod.check_budgets(
            dict(self.BASE, trace_overhead_pct=3.5))
        assert any("trace overhead" in f for f in out["budget_flags"])

    def test_trace_overhead_within_budget_clean(self):
        assert benchmod.check_budgets(
            dict(self.BASE, trace_overhead_pct=1.2)) == {}
        # the noise floor can read slightly negative — never a flag
        assert benchmod.check_budgets(
            dict(self.BASE, trace_overhead_pct=-0.8)) == {}


# --- ISSUE 5: overload budget gates (bench.py:check_budgets) ---------------

OVERLOAD_OK = {
    "admission_overhead_pct": 0.4,
    "unloaded_critical_p99_ms": 90.0,
    "overload_critical_p99_ms": 150.0,
    "overload_critical_p99_ratio": 1.67,
    "overload_critical_sheds": 0.0,
    "overload_best_effort_sheds": 120.0,
}


def test_overload_budgets_clean():
    assert benchmod.check_budgets(dict(OVERLOAD_OK)) == {}


def test_critical_p99_blowout_flagged():
    rec = dict(OVERLOAD_OK, overload_critical_p99_ratio=2.4)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("critical p99 under 4x overload" in f for f in flags)


def test_critical_shed_flagged():
    rec = dict(OVERLOAD_OK, overload_critical_sheds=2.0)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("critical" in f and "shed" in f for f in flags)


def test_no_best_effort_sheds_flagged():
    # zero sheds under overdrive means admission never engaged
    rec = dict(OVERLOAD_OK, overload_best_effort_sheds=0.0)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("did not engage" in f for f in flags)


def test_admission_overhead_flagged():
    rec = dict(OVERLOAD_OK, admission_overhead_pct=3.5)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("admission budget" in f for f in flags)


# --- ISSUE 7: sharded (meshed) megabatch gates -----------------------------


SHARDED_OK = {
    "sharded_devices": 8,
    "sharded_serial_per_sec": 2.5,
    "sharded_mega_per_sec": 39.8,
    "sharded_megabatch_speedup": 15.9,
    "sharded_single_latency_ratio": 0.95,
    "sharded_batch_occupancy": 8.0,
}


def test_sharded_budgets_clean():
    assert benchmod.check_budgets(dict(SHARDED_OK)) == {}


def test_sharded_megabatch_not_beating_serial_flagged():
    # the acceptance bar: meshed megabatch must be STRICTLY above the
    # meshed serial baseline (<=1.0 means the unlock regressed away)
    rec = dict(SHARDED_OK, sharded_megabatch_speedup=0.97)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("meshed serial baseline" in f for f in flags)
    rec = dict(SHARDED_OK, sharded_megabatch_speedup=1.0)
    assert any("meshed serial baseline" in f
               for f in benchmod.check_budgets(rec)["budget_flags"])


def test_sharded_single_latency_tax_flagged():
    rec = dict(SHARDED_OK, sharded_single_latency_ratio=1.2)
    flags = benchmod.check_budgets(rec)["budget_flags"]
    assert any("meshed single-request latency" in f for f in flags)


def test_sharded_phase_missing_not_flagged():
    # a record without the sharded phase carries no gate keys — absent keys
    # must not fail other rounds' budgets
    assert benchmod.check_budgets({"value": 100.0}) == {}


# --- ISSUE 21: no CPU fallback — the bench entry points refuse a non-TPU ----


def _json_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        if ln.startswith("{"):
            out.append(json.loads(ln))
    return out


class TestNoCpuFallback:
    """Every number the benches print is a statement about the chip: on any
    other backend they exit non-zero and print no metric value (the tests
    themselves run pinned to the CPU, which is exactly that case)."""

    REPO = __file__.rsplit("/tests/", 1)[0]

    def _run(self, *argv):
        import os
        import subprocess
        import sys

        return subprocess.run(
            [sys.executable, *argv], capture_output=True, text=True,
            timeout=300, cwd=self.REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def test_bench_refuses_cpu_and_prints_no_value(self):
        p = self._run("bench.py")
        assert p.returncode != 0
        recs = _json_lines(p.stdout)
        assert recs and all(r.get("value") is None for r in recs)
        assert "not 'tpu'" in recs[-1]["error"]

    def test_bench_all_refuses_cpu_before_any_config(self):
        p = self._run("bench_all.py", "--configs", "1")
        assert p.returncode != 0
        assert _json_lines(p.stdout) == []
        assert "refusing to measure" in p.stderr

    def test_require_tpu_exits_nonzero(self):
        with pytest.raises(SystemExit) as exc:
            benchmod.require_tpu()
        assert exc.value.code not in (0, None)

    def test_phase_error_reaches_the_exit_code(self, monkeypatch, capsys):
        def boom():
            raise benchmod.PhaseError("cold-restart child rc=1: boom")

        monkeypatch.setattr(benchmod, "measure_fresh_process_phases", boom)
        assert benchmod.main() == 1
        rec = _json_lines(capsys.readouterr().out)[-1]
        assert rec["value"] is None and "PhaseError" in rec["error"]

    def test_watchdog_exits_nonzero_and_reruns_nothing(self):
        p = self._run("-c", (
            "import importlib.util, time\n"
            "spec = importlib.util.spec_from_file_location('b', 'bench.py')\n"
            "b = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(b)\n"
            "b.arm_watchdog(0.2)\n"
            "time.sleep(30)\n"))
        assert p.returncode == 1
        recs = _json_lines(p.stdout)
        assert len(recs) == 1 and recs[0]["value"] is None
        assert "watchdog" in recs[0]["error"]


class TestWarmstartAndSweepGates:
    """ISSUE 6 budget gates: steady-state delta p50, warm-start cost
    parity, and the consolidation sweep's speedup/one-dispatch/decision
    contracts."""

    GOOD = {"warmstart_p50_ms": 0.7, "warmstart_cost_ratio": 1.004,
            "warmstart_full_fallbacks": 0,
            "sweep_speedup": 5.6, "sweep_candidates": 16,
            "sweep_dispatches": 1, "sweep_decisions_match": True}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_delta_p50_over_budget_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, warmstart_p50_ms=1.4))
        assert any("delta solve p50" in f for f in out["budget_flags"])

    def test_warmstart_cost_ratio_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, warmstart_cost_ratio=1.05))
        assert any("warm-start chain cost" in f for f in out["budget_flags"])

    def test_steady_state_full_fallbacks_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, warmstart_full_fallbacks=3))
        assert any("fell back" in f for f in out["budget_flags"])

    def test_sweep_speedup_under_budget_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, sweep_speedup=3.1))
        assert any("sweep speedup" in f for f in out["budget_flags"])

    def test_sweep_decision_divergence_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, sweep_decisions_match=False))
        assert any("diverged" in f for f in out["budget_flags"])

    def test_sweep_multi_dispatch_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, sweep_dispatches=3))
        assert any("one vmapped dispatch" in f for f in out["budget_flags"])


class TestDeltaServingGates:
    """ISSUE 10 budget gates: the end-to-end delta-RPC p50, wire-protocol
    losslessness, chain cost parity, zero unexplained fallbacks, the
    KT_DELTA=0 kill-switch parity, and the persistent-compile-cache
    cold-restart contract."""

    GOOD = {"delta_rpc_p50_ms": 2.4, "delta_parity": True,
            "delta_chain_cost_ratio": 1.003,
            "delta_unexplained_fallbacks": 0, "delta_off_parity": True,
            "cold_restart_first_ms": 8400.0,
            "cold_restart_second_ms": 900.0,
            "cold_restart_cache_populated": True}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_rpc_p50_over_budget_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, delta_rpc_p50_ms=3.6))
        assert any("delta RPC p50" in f for f in out["budget_flags"])

    def test_wire_divergence_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, delta_parity=False))
        assert any("not lossless" in f for f in out["budget_flags"])

    def test_chain_cost_over_ceiling_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, delta_chain_cost_ratio=1.05))
        assert any("chain cost ratio" in f for f in out["budget_flags"])

    def test_unexplained_fallbacks_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, delta_unexplained_fallbacks=2))
        assert any("fell back" in f for f in out["budget_flags"])

    def test_kill_switch_divergence_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, delta_off_parity=False))
        assert any("KT_DELTA=0" in f for f in out["budget_flags"])

    def test_unpopulated_jit_cache_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, cold_restart_cache_populated=False))
        assert any("JAX_COMPILATION_CACHE_DIR" in f
                   for f in out["budget_flags"])

    def test_cold_restart_no_improvement_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, cold_restart_second_ms=9000.0))
        assert any("persistent cache" in f for f in out["budget_flags"])

    def test_missing_delta_fields_not_flagged(self):
        # pre-delta records carry none of these fields
        assert benchmod.check_budgets({"value": 100.0}) == {}


class TestRestartRecoveryGates:
    """ISSUE 12 budget gates (measure_restart_recovery): a snapshot
    restart costs ZERO per-client full re-solves, a snapshot-less restart
    costs exactly N, and the restored first delta p50 stays bounded."""

    GOOD = {"restart_recovery_clients": 4,
            "restart_recovery_resends_with_snapshot": 0,
            "restart_recovery_resends_without": 4,
            "restart_first_delta_p50_ms": 2.8}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_resends_after_snapshot_restart_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, restart_recovery_resends_with_snapshot=2))
        assert any("WITH a session snapshot" in f
                   for f in out["budget_flags"])

    def test_wrong_no_spool_baseline_flagged(self):
        # fewer than N means the scenario never exercised the restart;
        # more than N means a retry storm — both must flag
        for wrong in (2, 7):
            out = benchmod.check_budgets(
                dict(self.GOOD, restart_recovery_resends_without=wrong))
            assert any("exactly one full solve per client" in f
                       for f in out["budget_flags"])

    def test_slow_restore_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, restart_first_delta_p50_ms=900.0))
        assert any("restore budget" in f for f in out["budget_flags"])

    def test_missing_restart_fields_not_flagged(self):
        assert benchmod.check_budgets({"value": 100.0}) == {}


class TestFleetFailoverGates:
    """ISSUE 13 budget gates (measure_fleet_failover): kill-one-of-N with
    the shared spool costs ZERO re-establishing solves (every orphaned
    session steal-adopted by a survivor), and the no-spool baseline costs
    exactly one re-establish per orphaned session."""

    GOOD = {"fleet_victim_sessions": 3,
            "fleet_warm_failover_resends": 0,
            "fleet_steal_adoptions": 3,
            "fleet_cold_victim_sessions": 2,
            "fleet_cold_failover_resends": 2}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_warm_failover_resends_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, fleet_warm_failover_resends=2))
        assert any("kill-one-of-N failover WITH the shared spool" in f
                   for f in out["budget_flags"])

    def test_unexercised_scenario_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, fleet_victim_sessions=0,
                 fleet_steal_adoptions=0))
        assert any("never exercised" in f for f in out["budget_flags"])

    def test_missing_steal_adoptions_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, fleet_steal_adoptions=1))
        assert any("not adopting" in f for f in out["budget_flags"])

    def test_wrong_cold_baseline_flagged(self):
        # fewer than N means the scenario never orphaned anything; more
        # means a retry storm — both must flag
        for wrong in (0, 5):
            out = benchmod.check_budgets(
                dict(self.GOOD, fleet_cold_failover_resends=wrong))
            assert any("exactly one full solve per session" in f
                       for f in out["budget_flags"])

    def test_missing_fleet_fields_not_flagged(self):
        assert benchmod.check_budgets({"value": 100.0}) == {}


class TestMultihostFenceGates:
    """ISSUE 14 budget gates (measure_multihost_fence): per-host fence
    reads ~1/N of the whole-batch bytes at N processes, per-slot demux
    byte-identical to single-process serial, and the per-host readback
    machinery never taxes a lone meshed flush past the standard
    single-latency budget."""

    GOOD = {"multihost_processes": 2,
            "multihost_fence_frac": 0.5,
            "multihost_parity": True,
            "multihost_lone_latency_ratio": 0.97}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_whole_batch_fence_frac_flagged(self):
        # a host reading (nearly) the whole batch back is exactly the
        # DCN-transfer-tax bug class this round removes
        out = benchmod.check_budgets(
            dict(self.GOOD, multihost_fence_frac=1.0))
        assert any("DCN for slots they do not own" in f
                   for f in out["budget_flags"])

    def test_exact_share_with_tolerance_clean(self):
        assert benchmod.check_budgets(
            dict(self.GOOD, multihost_fence_frac=0.6)) == {}

    def test_parity_divergence_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, multihost_parity=False))
        assert any("byte-identical" in f for f in out["budget_flags"])

    def test_lone_latency_tax_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, multihost_lone_latency_ratio=1.31))
        assert any("lone meshed flush" in f for f in out["budget_flags"])

    def test_skipped_run_not_flagged(self):
        # a jaxlib without gloo CPU collectives publishes
        # multihost_skipped and none of the gated fields
        assert benchmod.check_budgets(
            {"multihost_skipped": "no gloo"}) == {}

    def test_second_process_not_faster_flagged(self):
        # the cold-restart children run one after the other (one chip, one
        # process): the second must beat the first off the persisted cache
        out = benchmod.check_budgets(
            {"cold_restart_first_ms": 8000.0,
             "cold_restart_second_ms": 9000.0})
        assert any("persistent cache is not" in f
                   for f in out["budget_flags"])


class TestHierarchicalGates:
    """ISSUE 16 budget gates (measure_hierarchical): the dev-host scale
    model must put 1M pods under the target, hierarchical must be
    never-worse-than-flat on the overlap scenario, byte-identical on
    block-disjoint batches, Pallas byte-compatible, and every block wave
    exactly ONE device dispatch."""

    GOOD = {"hier_model_1m_ms": 130.0, "hier_cost_ratio": 1.008,
            "hier_infeasible_regressions": 0,
            "hier_disjoint_parity": True, "hier_pallas_parity": True,
            "hier_dispatches_per_wave": 1}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_model_over_target_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, hier_model_1m_ms=251.0))
        assert any("1M-pod hierarchical solve" in f
                   for f in out["budget_flags"])
        # the budget is a strict ceiling: AT the target also flags
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_model_1m_ms=benchmod.HIER_MODEL_1M_BUDGET_MS))
        assert any("1M-pod" in f for f in out["budget_flags"])

    def test_cost_ratio_over_ceiling_flagged(self):
        out = benchmod.check_budgets(dict(self.GOOD, hier_cost_ratio=1.03))
        assert any("not reconciling cross-block contention" in f
                   for f in out["budget_flags"])
        # the 1.02 ceiling itself is inclusive-OK
        assert benchmod.check_budgets(
            dict(self.GOOD, hier_cost_ratio=benchmod.COST_PARITY_CEILING)
        ) == {}

    def test_infeasible_regression_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_infeasible_regressions=3))
        assert any("no straggler" in f for f in out["budget_flags"])

    def test_disjoint_divergence_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_disjoint_parity=False))
        assert any("fully decoupled blocks" in f
                   for f in out["budget_flags"])

    def test_pallas_divergence_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_pallas_parity=False))
        assert any("KT_PALLAS" in f for f in out["budget_flags"])

    def test_extra_dispatches_per_wave_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_dispatches_per_wave=2.0))
        assert any("ONE vmapped dispatch" in f for f in out["budget_flags"])

    def test_unmeasured_model_is_not_gated(self):
        # without a device rate from a chip run the scale model's total is
        # the string "not measured": nothing to gate, nothing to crash on
        out = benchmod.check_budgets(
            dict(self.GOOD, hier_model_1m_ms="not measured"))
        assert out == {}

    def test_phase_missing_not_flagged(self):
        # absent keys must not fail other rounds' budget records
        assert benchmod.check_budgets({"solve_p50_ms": 30.0}) == {}


class TestTuningGates:
    """ISSUE 19 budget gates (measure_tuning): the self-tuning replay
    judgment — tuned throughput never below the static floor, the
    protected critical class's p99 inside the slack, zero critical sheds
    the static run did not pay, the controller's own decision loop under
    the overhead budget, and clean replays."""

    GOOD = {"tuning_throughput_ratio": 1.01,
            "tuning_critical_p99_ratio": 0.97,
            "tuning_new_critical_sheds": 0,
            "tuning_overhead_pct": 0.2,
            "tuning_steps": 48,
            "tuning_replay_errors": 0}

    def test_within_budgets_clean(self):
        assert benchmod.check_budgets(dict(self.GOOD)) == {}

    def test_throughput_below_floor_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, tuning_throughput_ratio=0.9))
        assert any("static run's throughput" in f
                   for f in out["budget_flags"])
        # the floor itself (0.98) is inclusive-OK: never-worse within noise
        assert benchmod.check_budgets(
            dict(self.GOOD,
                 tuning_throughput_ratio=benchmod.TUNING_THROUGHPUT_FLOOR)
        ) == {}

    def test_critical_p99_over_slack_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, tuning_critical_p99_ratio=1.31))
        assert any("protected class" in f for f in out["budget_flags"])
        # AT the 1.05x slack is inclusive-OK
        assert benchmod.check_budgets(
            dict(self.GOOD,
                 tuning_critical_p99_ratio=benchmod.TUNING_CRITICAL_P99_SLACK)
        ) == {}

    def test_new_critical_sheds_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, tuning_new_critical_sheds=1))
        assert any("guardrails are not holding" in f
                   for f in out["budget_flags"])

    def test_controller_overhead_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, tuning_overhead_pct=3.1))
        assert any("feedback loop itself became load" in f
                   for f in out["budget_flags"])

    def test_replay_errors_flagged(self):
        out = benchmod.check_budgets(
            dict(self.GOOD, tuning_replay_errors=2))
        assert any("errored during the self-tuning" in f
                   for f in out["budget_flags"])

    def test_missing_tuning_fields_not_flagged(self):
        # records from rounds before the self-tuning bench carry none of
        # the new fields; absence must never flag
        assert benchmod.check_budgets({"value": 100.0}) == {}


@pytest.mark.slow
def test_500k_pod_solve_stretch():
    """ISSUE 6 stretch rung: the solve bench ceiling lifted from 50k
    toward 500k pods.  10x the bench scenario's deployments through the
    full device path; gates completion, feasibility, and FFD cost parity
    at the 50k ceiling's 1.02 — run via `-m slow` only (the scan compile
    and solve are minutes-scale on the CPU dev host)."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.instancetype import GIB
    from karpenter_tpu.models.pod import (
        LabelSelector,
        PodSpec,
        TopologySpreadConstraint,
    )
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.models.tensorize import tensorize
    from karpenter_tpu.solver import reference
    from karpenter_tpu.solver.tpu import TpuSolver

    catalog = generate_catalog(full=True)
    pods = []
    for d in range(200):
        cpu = 0.25 * (1 + d % 8)
        mem = (0.5 + (d % 6)) * GIB
        sel = LabelSelector.of({"app": f"big{d}"})
        for i in range(2500):
            pods.append(PodSpec(
                name=f"big{d}-{i}", labels={"app": f"big{d}"},
                requests={"cpu": cpu, "memory": mem},
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"big{d}",
            ))
    assert len(pods) == 500_000
    provs = [Provisioner(name="default").with_defaults()]
    st = tensorize(pods, provs, catalog)
    out = TpuSolver().solve(st, track_assignments=False)
    assert not out.result.infeasible
    oracle = reference.solve(pods, provs, catalog)
    ratio = out.result.new_node_cost / oracle.new_node_cost
    assert ratio <= 1.02, f"500k cost ratio {ratio:.4f}"
