"""Million-pod hierarchical solving (ISSUE 16): block decomposition, the
dual price loop, and the packed score kernel.

Six surfaces:

1. **Partition** — constraint-reachability components (selector-disjoint
   deployments never couple; a shared selector fuses them), the
   never-split LPT packing invariant, and per-block node budgets.
2. **price_adjusted** — the dual multiplier over the solver's real
   ``[C, D]`` per-domain price layout (regression: the first cut assumed
   ``[C]`` and only blew up once a provisioner limit actually bound),
   with the 3.0e38/inf no-offering sentinels byte-preserved.
3. **packed_scan_scores** — int8/bf16 correctness on the lax program,
   all-infeasible rows, and lax↔Pallas byte parity incl. tie-breaks and
   non-tile-aligned shapes.
4. **scale_model** — host-linear stages, block-share wave scaling, and
   the measured-device-rate override.
5. **solve_hierarchical end-to-end** — disjoint parity vs the flat
   program, the stats/dispatch contract (ONE dispatch per wave), the
   structural fallback, threshold routing, and a contended provisioner
   limit driving real price iterations that repair then enforces exactly.
6. **Metrics** — KT003 zero-init of every routing-path series.
"""

import os

import numpy as np
import pytest

from karpenter_tpu.metrics import HIER_PATHS, HIER_SOLVES, Registry
from karpenter_tpu.models import labels as L
from karpenter_tpu.models.instancetype import GIB
from karpenter_tpu.models.pod import (
    LabelSelector,
    PodSpec,
    TopologySpreadConstraint,
)
from karpenter_tpu.models.provisioner import Provisioner
from karpenter_tpu.models.tensorize import (
    pack_feasibility,
    pack_scores,
    tensorize,
)
from karpenter_tpu.solver import hierarchy as hier
from karpenter_tpu.solver.scheduler import BatchScheduler


def deployments(nd, per, tag="hd", shared_label=False):
    """``nd`` deployments x ``per`` pods; each spreads over zones against
    its own app selector, so deployments are selector-disjoint components
    unless ``shared_label`` points every selector at one common label."""
    pods = []
    for d in range(nd):
        key = {"tier": "web"} if shared_label else {"app": f"{tag}{d}"}
        sel = LabelSelector.of(key)
        pods.extend(
            PodSpec(
                name=f"{tag}{d}-{i}",
                labels={"app": f"{tag}{d}", **({"tier": "web"}
                                               if shared_label else {})},
                requests={"cpu": 0.25 * (1 + d % 4),
                          "memory": (0.5 + (d % 3)) * GIB},
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"{tag}{d}",
            )
            for i in range(per)
        )
    return pods


def plan(result):
    """Node-plan fingerprint, independent of the node-name counter."""
    return sorted(
        (n.instance_type, n.zone, n.capacity_type, round(n.price, 6),
         tuple(sorted(p.name for p in n.pods)))
        for n in result.nodes
    )


def placements_tie(a, b):
    """The bench/fuzz tolerance: the flat scan and the vmapped megabatch
    are different compiled XLA graphs, so a genuine price tie may break
    differently at the last f32 ulp — same pods seated, same infeasible
    set, bitwise-equal f32 total cost."""
    return (set(a.assignments) == set(b.assignments)
            and set(a.infeasible) == set(b.infeasible)
            and np.float32(sum(n.price for n in a.nodes)).tobytes()
            == np.float32(sum(n.price for n in b.nodes)).tobytes())


@pytest.fixture(scope="module")
def provs():
    return [Provisioner(name="default").with_defaults()]


@pytest.fixture(scope="module")
def sched():
    return BatchScheduler(backend="tpu", compile_behind=False)


# ---------------------------------------------------------------------------
# 1. partition
# ---------------------------------------------------------------------------


class TestPartition:
    def test_selector_disjoint_deployments_are_separate_components(
            self, provs, small_catalog):
        st = tensorize(deployments(5, 4), provs, small_catalog)
        comps = hier.coupling_components(st)
        assert len(comps) == 5
        assert sorted(g for c in comps for g in c) == list(range(st.G))

    def test_shared_selector_couples_everything(self, provs, small_catalog):
        st = tensorize(deployments(5, 4, shared_label=True),
                       provs, small_catalog)
        comps = hier.coupling_components(st)
        assert len(comps) == 1
        assert sorted(comps[0]) == list(range(st.G))

    def test_partition_never_splits_a_component(self, provs, small_catalog):
        st = tensorize(deployments(7, 3), provs, small_catalog)
        comps = hier.coupling_components(st)
        masks = hier.partition_blocks(st, comps, 3)
        assert len(masks) == 3
        # every component's groups land in exactly one mask, intact
        for comp in comps:
            hits = [i for i, m in enumerate(masks)
                    if any(m[g] for g in comp)]
            assert len(hits) == 1
            assert all(masks[hits[0]][g] for g in comp)
        # masks are disjoint and jointly cover every group
        total = np.zeros(st.G, dtype=int)
        for m in masks:
            total += m.astype(int)
        assert (total == 1).all()

    def test_lpt_balances_pod_weight(self, provs, small_catalog):
        # 6 equal-weight components into 3 bins -> perfectly even loads
        st = tensorize(deployments(6, 5), provs, small_catalog)
        comps = hier.coupling_components(st)
        masks = hier.partition_blocks(st, comps, 3)
        counts = np.asarray(st.counts)
        loads = sorted(int(counts[m].sum()) for m in masks)
        assert loads == [10, 10, 10]

    def test_block_budgets_are_block_pod_counts(self, provs, small_catalog):
        st = tensorize(deployments(4, 6), provs, small_catalog)
        masks = hier.partition_blocks(st, hier.coupling_components(st), 2)
        counts = np.asarray(st.counts)
        assert hier.block_budgets(st, masks) == [
            int(counts[m].sum()) for m in masks]


# ---------------------------------------------------------------------------
# 2. price_adjusted
# ---------------------------------------------------------------------------


class TestPriceAdjusted:
    def test_cd_layout_broadcasts_per_candidate(self):
        # the solver's real [C, D] layout: the multiplier is per CANDIDATE
        # (owning provisioner) and must broadcast across the domain axis
        base = np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]],
                        dtype=np.float32)
        prov = np.array([0, 1, 0], dtype=np.int32)
        lam = np.array([0.0, np.log(2.0)])
        out = hier.price_adjusted(base, prov, lam)
        assert out.shape == base.shape and out.dtype == np.float32
        np.testing.assert_allclose(out[0], base[0])
        np.testing.assert_allclose(out[1], base[1] * 2.0, rtol=1e-6)
        np.testing.assert_allclose(out[2], base[2])

    def test_sentinels_survive_byte_for_byte(self):
        big = np.float32(3.0e38)
        base = np.array([[1.0, np.inf], [big, big]], dtype=np.float32)
        out = hier.price_adjusted(
            base, np.array([0, 0], dtype=np.int32), np.array([5.0]))
        # the in-row inf (no offering in that domain) and the all-sentinel
        # padding row both come back untouched — a multiply past 1e38
        # would overflow to inf and change the compiled program's padding
        assert out[0, 1] == np.inf
        assert out[1].tobytes() == base[1].tobytes()
        assert out[0, 0] == pytest.approx(float(np.exp(5.0)), rel=1e-6)

    def test_zero_duals_are_identity(self):
        base = np.array([2.5, 3.0e38, 7.125], dtype=np.float32)
        out = hier.price_adjusted(
            base, np.zeros(3, dtype=np.int32), np.zeros(2))
        assert out.tobytes() == base.tobytes()

    def test_real_tensorized_state_shape(self, provs, small_catalog):
        # regression: the demo's contended run was the FIRST caller to hit
        # the price loop with real tensors, and the [C, D] cand_price
        # broadcast raised.  Drive the exact production inputs here.
        st = tensorize(deployments(3, 4), provs, small_catalog)
        lam = np.full(len(st.prov_names), 0.3)
        adj = hier.price_adjusted(st.cand_price, st.cand_prov, lam)
        assert adj.shape == st.cand_price.shape
        finite = np.asarray(st.cand_price) < 1e37
        np.testing.assert_allclose(
            adj[finite], np.asarray(st.cand_price)[finite]
            * np.float32(np.exp(0.3)), rtol=1e-6)
        # the kernel input: cheapest offering per candidate, 1-D
        assert adj[:st.C].min(axis=1).shape == (st.C,)


# ---------------------------------------------------------------------------
# 3. packed score kernel
# ---------------------------------------------------------------------------


class TestPackedScores:
    def _case(self, G=5, C=7, seed=3):
        rng = np.random.default_rng(seed)
        f = pack_feasibility(rng.random((G, C)) < 0.6)
        price = rng.uniform(0.1, 9.0, size=C).astype(np.float32)
        # force ties so the first-minimum tie-break is actually exercised
        price[C // 2:] = price[: C - C // 2]
        return f, pack_scores(price)

    def test_lax_picks_cheapest_feasible(self):
        f = pack_feasibility(np.array([[1, 0, 1], [0, 1, 1]]))
        p = pack_scores(np.array([5.0, 1.0, 2.0], dtype=np.float32))
        cost, idx = hier.packed_scan_scores(f, p, use_pallas=False)
        np.testing.assert_allclose(cost, [2.0, 1.0])
        assert idx.tolist() == [2, 1]

    def test_all_infeasible_row_returns_sentinel(self):
        f = pack_feasibility(np.array([[0, 0], [1, 1]]))
        p = pack_scores(np.array([1.0, 2.0], dtype=np.float32))
        for use_pallas in (False, True):
            cost, idx = hier.packed_scan_scores(f, p, use_pallas=use_pallas,
                                                interpret=True)
            assert cost[0] >= 1e37 and idx[0] == 0
            assert cost[1] == pytest.approx(1.0)

    def test_pallas_byte_parity_with_ties(self):
        f, p = self._case()
        c0, i0 = hier.packed_scan_scores(f, p, use_pallas=False)
        c1, i1 = hier.packed_scan_scores(f, p, use_pallas=True,
                                         interpret=True)
        assert c0.tobytes() == c1.tobytes()
        assert i0.tobytes() == i1.tobytes()

    def test_pallas_parity_on_tile_aligned_shape(self):
        # exactly one (32, 128) tile: no padding path at all
        f, p = self._case(G=32, C=128, seed=9)
        c0, i0 = hier.packed_scan_scores(f, p, use_pallas=False)
        c1, i1 = hier.packed_scan_scores(f, p, use_pallas=True,
                                         interpret=True)
        assert c0.tobytes() == c1.tobytes()
        assert i0.tobytes() == i1.tobytes()

    def test_interpret_is_asked_for_never_sniffed(self):
        # on the CPU the Mosaic path must FAIL, loudly — not quietly turn
        # itself into interpret mode (or the lax program) by looking at
        # the backend; chip_smoke.py runs it for real on the TPU
        f, p = self._case()
        with pytest.raises(ValueError, match="interpret"):
            hier.packed_scan_scores(f, p, use_pallas=True)

    def test_env_flag_selects_the_kernel(self, monkeypatch):
        monkeypatch.setenv("KT_PALLAS", "1")
        assert hier.pallas_enabled()
        monkeypatch.delenv("KT_PALLAS")
        assert not hier.pallas_enabled()


# ---------------------------------------------------------------------------
# 4. scale model
# ---------------------------------------------------------------------------


class TestScaleModel:
    MEASURED = {"n_pods": 10_000, "blocks": 32, "waves": 2,
                "partition_ms": 1.0, "entries_ms": 3.0, "repair_ms": 0.5}

    def test_host_stages_scale_linearly(self):
        m = hier.scale_model(dict(self.MEASURED), 100_000)
        assert m["host_ms"] == pytest.approx((1.0 + 3.0) * 10.0)
        assert m["repair_ms"] == pytest.approx(0.5 * 10.0)
        assert m["waves"] == 2 and m["blocks"] == 32

    def test_no_measured_device_rate_means_not_measured(self):
        # no default rate: a run that never saw the chip projects the host
        # stages only and says so for everything the device decides
        m = hier.scale_model(dict(self.MEASURED), 1_000_000)
        assert m["wave_ms"] == m["total_ms"] == hier.NOT_MEASURED
        assert m["host_ms"] == pytest.approx((1.0 + 3.0) * 100.0)

    def test_wave_scales_with_block_share_not_batch(self):
        # the decomposition dividend: device time rides n_pods / blocks
        per_pod_us = 0.48
        measured = dict(self.MEASURED, device_per_pod_us=per_pod_us)
        m32 = hier.scale_model(dict(measured), 1_000_000)
        m64 = hier.scale_model(dict(measured, blocks=64), 1_000_000)
        assert m32["wave_ms"] == pytest.approx(
            per_pod_us * (1_000_000 / 32) / 1000.0 + 2.0)
        assert (m64["wave_ms"] - 2.0) == pytest.approx(
            (m32["wave_ms"] - 2.0) / 2.0)
        assert m32["total_ms"] == pytest.approx(
            m32["host_ms"] + 2 * m32["wave_ms"] + m32["repair_ms"])

    def test_measured_device_rate_sets_the_wave(self):
        m = hier.scale_model(
            dict(self.MEASURED, device_per_pod_us=1.0,
                 dispatch_overhead_ms=0.0), 320_000)
        assert m["wave_ms"] == pytest.approx(10.0)  # 10k pods/block x 1us


# ---------------------------------------------------------------------------
# 5. solve_hierarchical end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def disjoint_run(sched, provs, small_catalog):
    """One shared end-to-end solve on a selector-disjoint batch: the flat
    reference (relax skipped — megabatch slots skip it by design), the
    hierarchical result, and its stats."""
    pods = deployments(4, 12, tag="he")
    flat = sched.solve(pods, provs, small_catalog, relax=False)
    stats = {}
    hres = hier.solve_hierarchical(sched, pods, provs, small_catalog,
                                   stats=stats)
    return pods, flat, hres, stats


class TestSolveHierarchical:
    def test_disjoint_blocks_match_flat(self, disjoint_run):
        _, flat, hres, _ = disjoint_run
        assert hres is not None
        assert plan(flat) == plan(hres) or placements_tie(flat, hres)
        assert set(flat.assignments) == set(hres.assignments)
        assert set(flat.infeasible) == set(hres.infeasible)

    def test_one_dispatch_per_wave(self, disjoint_run):
        _, _, hres, stats = disjoint_run
        assert hres is not None
        assert stats["dispatches"] == stats["waves"]
        assert stats["waves"] == 1 + stats["price_iters"]
        assert stats["blocks"] >= 2
        assert len(stats["wave_ms"]) == stats["waves"]

    def test_uncontended_batch_skips_the_price_loop(self, disjoint_run):
        _, _, _, stats = disjoint_run
        # no provisioner limit binds -> zero price iterations, one wave
        assert stats["price_iters"] == 0 and stats["waves"] == 1

    def test_single_component_falls_back_to_flat(self, sched, provs,
                                                 small_catalog):
        reg = Registry()
        out = hier.solve_hierarchical(
            sched, deployments(3, 6, tag="hc", shared_label=True),
            provs, small_catalog, registry=reg)
        assert out is None
        assert reg.counter(HIER_SOLVES).get(
            {"path": "fallback_structure"}) == 1.0

    def test_threshold_routes_the_scheduler(self, sched, provs,
                                            small_catalog, monkeypatch):
        # regression: with the threshold at the batch size, repair's inner
        # _solve_once used to route hierarchically AGAIN and recurse
        # without bound — _hier_depth pins nested solves to the flat path
        pods = deployments(4, 12, tag="he")  # the warmed module shape
        monkeypatch.setenv("KT_HIER_THRESHOLD", str(len(pods)))
        before = sched.registry.counter(HIER_SOLVES).get(
            {"path": "hierarchical"})
        sched.solve(pods, provs, small_catalog, relax=False)
        after = sched.registry.counter(HIER_SOLVES).get(
            {"path": "hierarchical"})
        assert after == before + 1.0
        # below the threshold: flat, no new hierarchical sample
        monkeypatch.setenv("KT_HIER_THRESHOLD", str(len(pods) + 1))
        sched.solve(pods, provs, small_catalog, relax=False)
        assert sched.registry.counter(HIER_SOLVES).get(
            {"path": "hierarchical"}) == after

    def test_contended_limit_prices_then_repairs_exactly(
            self, sched, small_catalog, disjoint_run):
        # a cpu limit just under the unconstrained buy forces the blocks
        # to contend: the dual loop must run, and whatever imperfect
        # equilibrium it lands on, host repair must enforce the limit
        # EXACTLY in the shipped result
        pods, _, free, _ = disjoint_run
        provs = [Provisioner(name="default").with_defaults()]
        st = sched._tensorize(pods, provs, small_catalog, (), ())[0]
        bought = sum(
            float(st.capacity_row(n.instance_type, n.allocatable)[0])
            for n in free.nodes)
        lim = Provisioner(name="default").with_defaults()
        lim.limits = {"cpu": round(bought * 0.99, 1)}
        stats = {}
        res = hier.solve_hierarchical(sched, pods, [lim], small_catalog,
                                      stats=stats)
        assert res is not None
        assert stats["price_iters"] >= 1
        assert stats["dispatches"] == stats["waves"]
        shipped = sum(
            float(st.capacity_row(n.instance_type, n.allocatable)[0])
            for n in res.nodes)
        assert shipped <= lim.limits["cpu"] * (1.0 + 1e-6)
        # every pod is accounted for: seated or typed infeasible
        assert (set(res.assignments) | set(res.infeasible)
                == {p.name for p in pods})


# ---------------------------------------------------------------------------
# 6. metrics + knobs
# ---------------------------------------------------------------------------


class TestMetricsAndKnobs:
    def test_zero_init_registers_every_path(self):
        reg = Registry()
        hier.zero_init_hier_metrics(reg)
        for path in HIER_PATHS:
            c = reg.counter(HIER_SOLVES)
            assert c.has({"path": path}) and c.get({"path": path}) == 0.0

    def test_zero_init_never_clobbers_a_live_series(self):
        reg = Registry()
        reg.counter(HIER_SOLVES).inc({"path": "hierarchical"})
        hier.zero_init_hier_metrics(reg)
        assert reg.counter(HIER_SOLVES).get({"path": "hierarchical"}) == 1.0

    def test_threshold_knob_parses_and_defends(self, monkeypatch):
        monkeypatch.setenv("KT_HIER_THRESHOLD", "250000")
        assert hier.hier_threshold() == 250_000
        monkeypatch.setenv("KT_HIER_THRESHOLD", "not-a-number")
        assert hier.hier_threshold() == hier.DEFAULT_HIER_THRESHOLD
        monkeypatch.setenv("KT_HIER_PRICE_ITERS", "-3")
        assert hier.hier_price_iters() == 0
        monkeypatch.setenv("KT_HIER_PRICE_ITERS", "junk")
        assert hier.hier_price_iters() == hier.DEFAULT_PRICE_ITERS

    def test_module_import_is_jax_free(self):
        # scripts/profile_solve.py --hier depends on this: partition +
        # scale model must import without a backend.  KT_SANITIZE is
        # stripped too: the sanitizer's install wraps the solver-path
        # classes at package import (pulling jax by design), which says
        # nothing about hierarchy's own imports
        import subprocess
        import sys
        env = {k: v for k, v in os.environ.items() if k != "KT_SANITIZE"}
        code = ("import sys; import karpenter_tpu.solver.hierarchy; "
                "sys.exit(1 if 'jax' in sys.modules else 0)")
        assert subprocess.run([sys.executable, "-c", code],
                              env=env).returncode == 0
