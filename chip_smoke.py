#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on the chip.

Drives the deployed topology once, at the full size of BASELINE config 2,
through the entry points a user calls: a solver sidecar that holds the TPU
(``python -m karpenter_tpu.service.server --backend auto --warmup``, the
command of deploy/solver.yaml) and an operator-side client that has no chip
and reaches it over gRPC (``service/client.py``).

    python chip_smoke.py        # no flags; needs one TPU; ~3 min cold

Three children, started ONE AFTER ANOTHER by a parent that imports neither
jax nor karpenter_tpu (a chip belongs to one process at a time):

1. **sidecar** — the only long-lived TPU process.  It refuses to serve
   unless jax reports platform "tpu", builds the C++ cold tier from
   native/ffd.cpp (every stale ``_native_*.so`` is removed first), and blocks
   on the warm-up grid.
2. **client** (``JAX_PLATFORMS=cpu``, the operator) — :func:`client_phase`:
   Health must say "tpu"; the 50,000-pod config-2 batch is sent until the
   sidecar's ``/metrics`` shows the device tier serving it, then 3 more; a
   ``DeltaSession`` takes 5 churn steps on the 20k-pod chain; the same 20k
   unconstrained pods as ONE batch must be refined by the relax rung, whose
   program the warm-up grid compiled; one 100-pod batch is served by the
   oracle BY ROUTING POLICY (listed so nobody reads it as a fallback).
   Every reply is judged by the repo's own means:
   ``solver/validate.py`` and the cost of ``solver/reference.py`` on the
   same input — never against a CPU run's bytes.
3. **device-direct** — after the sidecar has exited and released the chip:
   the bare 4-byte fenced D2H read, whether a re-run on identical inputs
   really executes, config 2 through ``TpuSolver.solve`` in-process (what
   the device path costs without the wire), peak device memory, and the
   Pallas score kernel compiled by Mosaic and compared bit-for-bit with
   the lax program.

Output: progress on stderr; on stdout one summary JSON line and then, last,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Exit code 0 only
if every assertion held, every child exited 0 and the device is a TPU.
Readings printed here are smoke readings, not benchmark metrics.
"""

from __future__ import annotations

import glob
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

#: bounded waits, seconds.  The whole script must end inside 1200 s.
SIDECAR_READY_S = 900.0
DEVICE_SERVED_S = 420.0
COMPILES_LANDED_S = 240.0
SIDECAR_EXIT_S = 240.0
#: a cold wall past this is ROADMAP S2's finding — said in the output
COLD_WALL_NOTE_S = 900.0

COST_CEILING = 1.02

M_BACKEND_COUNT = "karpenter_solver_backend_duration_seconds_count"
M_COLD_FALLBACKS = "karpenter_solver_cold_start_fallbacks_total"
M_DEVICE_HANGS = "karpenter_solver_device_hangs_total"
M_DEGRADED = "karpenter_solver_degraded_solves_total"
M_COMPILING = "karpenter_solver_compile_in_progress"
M_COMPILES = "karpenter_solver_compile_duration_seconds_count"
M_PRECOMPILE_SUM = "karpenter_solver_precompile_duration_seconds_sum"
M_RELAX = "karpenter_solver_relax_total"


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# /metrics: scrape + parse (Prometheus text exposition)
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list:
    """[(name, {label: value}, float)] for every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if m is None:
            raise ValueError(f"unparseable /metrics line: {line!r}")
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                    float(m.group(3))))
    return out


def scrape(url: str) -> list:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return parse_metrics(resp.read().decode())


def metric(samples: list, name: str, **labels: str) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``; a
    family that is absent reads 0 (counters are zero-initialised)."""
    return sum(v for n, lab, v in samples if n == name
               and all(lab.get(k) == want for k, want in labels.items()))


def serving_tiers(before: list, after: list) -> dict:
    """{tier: solves} the sidecar served between two scrapes."""
    out = {}
    for tier in ("tpu", "native", "oracle"):
        d = (metric(after, M_BACKEND_COUNT, backend=tier)
             - metric(before, M_BACKEND_COUNT, backend=tier))
        if d:
            out[tier] = int(d)
    return out


# ---------------------------------------------------------------------------
# client child: the operator side (no chip, by design)
# ---------------------------------------------------------------------------


class Checks:
    """Named assertions with their outcomes — every one is evaluated and
    reported, and any failure fails the run."""

    def __init__(self) -> None:
        self.rows: list = []

    def check(self, name: str, ok: bool, detail: object = "") -> bool:
        self.rows.append({"name": name, "ok": bool(ok),
                          "detail": str(detail)[:300]})
        log(f"{'ok  ' if ok else 'FAILED'} {name}: {detail}")
        return bool(ok)

    @property
    def ok(self) -> bool:
        return all(r["ok"] for r in self.rows)


def _small_batch(n: int) -> list:
    from karpenter_tpu.models.pod import PodSpec

    return [PodSpec(name=f"small-{i}", labels={"app": "small"},
                    requests={"cpu": 0.5, "memory": float(2 ** 30)},
                    owner_key="small") for i in range(n)]


def client_phase(target: str, metrics_url: str, *, scenario=None,
                 chain_pods: int = 20_000, churn_steps: int = 5,
                 churn: int = 8, small_pods: int = 100, steady: int = 3,
                 device_wait_s: float = DEVICE_SERVED_S) -> dict:
    """Everything the operator side does, against the sidecar at ``target``
    whose ``/metrics`` is at ``metrics_url``.  Returns the report; never
    raises for a failed assertion (they are all in ``report["assertions"]``).
    The defaults are the real sizes; the CPU rehearsal in the tests passes
    tiny ones."""
    from karpenter_tpu.metrics import (
        FAULTS_RECOVERED,
        REMOTE_FALLBACK_SOLVES,
        Registry,
    )
    from karpenter_tpu.service.client import (
        DeltaSession,
        RemoteScheduler,
        SolverClient,
        hydrate_node,
    )
    from karpenter_tpu.models.scenarios import (
        config2_scenario,
        unconstrained_pods,
    )
    from karpenter_tpu.solver import reference
    from karpenter_tpu.solver.validate import validate_solution

    checks = Checks()
    reg = Registry()  # the client's own: its transport fallbacks land here

    probe = SolverClient(target, timeout=60.0, registry=reg)
    health = probe.health()
    probe.close()
    checks.check("health_backend_is_tpu", health.backend == "tpu",
                 f"Health says backend={health.backend!r} "
                 f"devices={health.devices}")

    pods, provs, catalog = (scenario or config2_scenario)()
    it_by_name = {it.name: it for it in catalog}
    t0 = time.perf_counter()
    ref = reference.solve(pods, provs, catalog)
    ref_s = time.perf_counter() - t0
    log(f"reference FFD on the client: {len(ref.nodes)} nodes, "
        f"${ref.new_node_cost:.2f}/h, {ref_s:.1f}s")

    remote = RemoteScheduler(target, timeout=600.0, registry=reg)
    requests: list = []

    def send(label: str, batch, ref=None) -> dict:
        """One Solve RPC; judged (all assigned, validator, cost ceiling)
        when the reference solution of the same batch is given."""
        before = scrape(metrics_url)
        t0 = time.perf_counter()
        res = remote.solve(batch, provs, catalog)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        for node in res.nodes:  # the validator reads allocatable, labels
            hydrate_node(node, it_by_name, provs)  # and taints
        after = scrape(metrics_url)
        row = {
            "request": label, "pods": len(batch),
            "wall_ms": round(wall_ms, 1),
            "tiers": serving_tiers(before, after),
            "cold_fallbacks": int(metric(after, M_COLD_FALLBACKS)
                                  - metric(before, M_COLD_FALLBACKS)),
            # what the relax rung did with this reply, by outcome
            "relax": {o: int(d) for o in ("improved", "tied", "fallback",
                                          "skipped")
                      if (d := metric(after, M_RELAX, outcome=o)
                          - metric(before, M_RELAX, outcome=o))},
            "assigned": len(res.assignments),
            "infeasible": len(res.infeasible),
            "nodes": len(res.nodes),
        }
        if ref is not None:
            errs = validate_solution(batch, provs, res, catalog)
            ratio = (res.new_node_cost / ref.new_node_cost
                     if ref.new_node_cost else 1.0)
            row["cost_vs_reference"] = round(ratio, 4)
            checks.check(f"{label}_all_assigned",
                         row["assigned"] == len(batch)
                         and row["infeasible"] == 0,
                         f"{row['assigned']}/{len(batch)} assigned, "
                         f"{row['infeasible']} infeasible")
            checks.check(f"{label}_validator_clean", not errs, errs[:3])
            checks.check(f"{label}_cost_within_ceiling",
                         ratio <= COST_CEILING,
                         f"{ratio:.4f}x reference (ceiling {COST_CEILING})")
        requests.append(row)
        log(f"{label}: {row['wall_ms']:.0f} ms, served by {row['tiers']}, "
            f"{row['nodes']} nodes")
        return row

    # config 2: cold requests are served by a host tier while the program
    # compiles behind — that is the contract, so record which tier — until
    # the device tier answers, within a bounded wait
    deadline = time.monotonic() + device_wait_s
    attempt = 0
    while True:
        attempt += 1
        row = send(f"c2_warming_{attempt}", pods, ref)
        if row["tiers"].get("tpu"):
            break
        if time.monotonic() > deadline:
            break
        time.sleep(2.0)
    checks.check("c2_device_served_within_bounded_wait",
                 bool(row["tiers"].get("tpu")),
                 f"after {attempt} request(s) / {device_wait_s:.0f}s the "
                 f"last reply was served by {row['tiers']}")
    for k in range(steady):
        row = send(f"c2_steady_{k + 1}", pods, ref)
        checks.check(f"c2_steady_{k + 1}_device_served",
                     row["tiers"] == {"tpu": 1} and not row["cold_fallbacks"],
                     f"tiers={row['tiers']} "
                     f"cold_fallbacks={row['cold_fallbacks']}")

    # delta chain: establish once, then churn — the chain's establishment is
    # a NEW shape, so it too is served cold by contract; record the tier
    before = scrape(metrics_url)
    sess = DeltaSession(target, timeout=600.0, registry=reg)
    chain = unconstrained_pods(chain_pods, "dw")
    t0 = time.perf_counter()
    sess.solve(chain, provs, catalog)
    establish_ms = (time.perf_counter() - t0) * 1000.0
    after = scrape(metrics_url)
    epoch0 = sess.epoch
    rng = random.Random(11)
    live = [p.name for p in chain]
    step_ms = []
    for k in range(churn_steps):
        gone = rng.sample(live, churn)
        live = [n for n in live if n not in set(gone)]
        add = unconstrained_pods(churn, f"dwc{k}")
        t0 = time.perf_counter()
        sess.solve_delta(added=add, removed=gone)
        step_ms.append(round((time.perf_counter() - t0) * 1000.0, 2))
        live += [p.name for p in add]
    view = sess.result()
    chain_now = sess.pods()
    errs = validate_solution(chain_now, provs, view, catalog)
    delta = {
        "pods": chain_pods, "establish_ms": round(establish_ms, 1),
        "establish_tiers": serving_tiers(before, after),
        "establish_cold_fallbacks": int(
            metric(after, M_COLD_FALLBACKS)
            - metric(before, M_COLD_FALLBACKS)),
        "step_ms": step_ms, "full_resends": sess.full_resends,
        "epoch_start": epoch0, "epoch_end": sess.epoch,
        "assigned": len(view.assignments),
        "infeasible": len(view.infeasible),
    }
    checks.check("delta_full_resends_is_1", sess.full_resends == 1,
                 f"full_resends={sess.full_resends}")
    checks.check("delta_epoch_advances", sess.epoch == epoch0 + churn_steps,
                 f"epoch {epoch0} -> {sess.epoch} over {churn_steps} steps")
    checks.check("delta_view_validator_clean", not errs, errs[:3])
    checks.check("delta_view_all_assigned",
                 len(view.assignments) == len(chain_now)
                 and not view.infeasible,
                 f"{len(view.assignments)}/{len(chain_now)} assigned, "
                 f"{len(view.infeasible)} infeasible")
    sess.close()
    log(f"delta chain: establish {delta['establish_ms']:.0f} ms via "
        f"{delta['establish_tiers']}, steps {step_ms} ms, "
        f"full_resends={sess.full_resends}")

    # the relax rung: the chain's pods as ONE unconstrained batch are
    # refinable, so the rung's device program (f32 matmuls) must run on the
    # reply — "improved" or "tied" says it did and its rounding held;
    # "skipped" that the program was still cold (it compiles behind),
    # "fallback" that the program or the rounding failed.  (The rung's
    # integrality repair may add a solve of its own to the tiers.)
    rx = unconstrained_pods(chain_pods, "rx")
    rx_ref = reference.solve(rx, provs, catalog)
    deadline = time.monotonic() + device_wait_s
    relax_attempts = 0
    while True:
        relax_attempts += 1
        row = send(f"relax_batch_{relax_attempts}", rx, rx_ref)
        cold = not row["tiers"].get("tpu") or row["relax"].get("skipped")
        if not cold or time.monotonic() > deadline:
            break
        time.sleep(1.0)
    ran = row["relax"].get("improved", 0) + row["relax"].get("tied", 0)
    checks.check("relax_rung_ran_on_the_device_tier",
                 ran == 1 and row["tiers"].get("tpu", 0) >= 1,
                 f"request {relax_attempts}: relax outcomes {row['relax']}, "
                 f"tiers={row['tiers']}")

    # a small batch is ORACLE-served by routing policy (scheduler
    # _route_small), not by a fallback
    small = send("small_batch", _small_batch(small_pods))
    small["served_by_routing_policy"] = small["tiers"] == {"oracle": 1}
    checks.check("small_batch_oracle_served_by_policy",
                 small["served_by_routing_policy"]
                 and small["assigned"] == small_pods
                 and not small["cold_fallbacks"],
                 f"tiers={small['tiers']} assigned={small['assigned']} "
                 f"cold_fallbacks={small['cold_fallbacks']}")
    remote.close()

    # a local-oracle serve on the client would mean the sidecar was not
    # the one answering
    local_serves = (
        reg.counter(FAULTS_RECOVERED).get(
            {"site": "transport", "outcome": "fallback"})
        + reg.counter(REMOTE_FALLBACK_SOLVES).get())
    checks.check("client_never_served_locally", local_serves == 0,
                 f"{local_serves:g} local-fallback serve(s)")
    return {
        "health": {"backend": health.backend, "devices": health.devices},
        "reference": {"nodes": len(ref.nodes),
                      "cost_per_hr": round(ref.new_node_cost, 3),
                      "wall_s": round(ref_s, 2)},
        "requests": requests, "delta": delta,
        "relax_attempts": relax_attempts,
        "cold_fallbacks_observed": (
            sum(r["cold_fallbacks"] for r in requests)
            + delta["establish_cold_fallbacks"]),
        "client_local_serves": local_serves,
        "assertions": checks.rows,
    }


def client_main(target: str, metrics_url: str) -> int:
    import jax

    report = client_phase(target, metrics_url)
    report["platform"] = jax.devices()[0].platform
    print(json.dumps(report), flush=True)
    return 0


# ---------------------------------------------------------------------------
# device-direct child: runs alone on the chip, after the sidecar released it
# ---------------------------------------------------------------------------


def device_main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.provisioner import Provisioner
    from karpenter_tpu.models.scenarios import (
        config2_scenario,
        spread_deployments,
    )
    from karpenter_tpu.models.tensorize import (
        pack_feasibility,
        pack_scores,
        tensorize,
    )
    from karpenter_tpu.solver import hierarchy as hier
    from karpenter_tpu.solver import reference
    from karpenter_tpu.solver.relax import host_feasibility
    from karpenter_tpu.solver.tpu import (
        TpuSolver,
        jit_cache_dir,
        jit_cache_entries,
    )
    from karpenter_tpu.solver.validate import validate_solution

    checks = Checks()
    dev = jax.devices()[0]
    report = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices()),
              "compile_cache": jit_cache_dir()}

    def median(xs):
        return sorted(xs)[len(xs) // 2]

    # the bare fence: a tiny dispatch plus a 4-byte D2H read, x10
    one = jnp.float32(1.0)
    x = jnp.zeros((), jnp.float32)
    np.asarray(x + one)  # compile the add
    reads = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(x + one)
        reads.append((time.perf_counter() - t0) * 1000.0)
    report["d2h_fence_ms"] = {"min": round(min(reads), 4),
                              "median": round(median(reads), 4)}
    # ... and the D2H read alone, of a 4-byte array that is already there
    # (a fresh array per reading: jax keeps the host copy of one it has
    # already read)
    reads = []
    for _ in range(10):
        ready = jax.block_until_ready(x + one)
        t0 = time.perf_counter()
        np.asarray(ready)
        reads.append((time.perf_counter() - t0) * 1000.0)
    report["d2h_read_only_ms"] = {"min": round(min(reads), 4),
                                  "median": round(median(reads), 4)}

    # is a second execution on IDENTICAL inputs really executed?  (The
    # fenced timings in solver/tpu.py and consolidation.py re-run on the
    # same inputs.)  A runtime that deduplicated it would answer in ~0.
    @jax.jit
    def heavy(a):
        return jax.lax.fori_loop(
            0, 200, lambda i, c: jnp.tanh(c @ a) * 0.5 + c * 0.5, a).sum()

    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.standard_normal((1024, 1024)).astype(np.float32))
    np.asarray(heavy(a))  # compile
    same, fresh = [], []
    for r in range(5):
        t0 = time.perf_counter()
        np.asarray(heavy(a))
        same.append((time.perf_counter() - t0) * 1000.0)
    for r in range(5):
        a2 = jax.block_until_ready(a + jnp.float32((r + 1) * 1e-6))
        t0 = time.perf_counter()
        np.asarray(heavy(a2))
        fresh.append((time.perf_counter() - t0) * 1000.0)
    report["rerun_identical_inputs_ms"] = round(median(same), 3)
    report["rerun_fresh_inputs_ms"] = round(median(fresh), 3)
    checks.check("identical_input_rerun_is_executed",
                 median(same) >= 0.5 * median(fresh),
                 f"identical {median(same):.3f} ms vs fresh "
                 f"{median(fresh):.3f} ms")

    # config 2 through TpuSolver.solve in THIS process: what the device path
    # costs without the wire (the served request above pays codec, gRPC and
    # tensorize of fresh objects on top).  measure=True re-runs the compiled
    # program once more, fenced, and reports that as solve_ms; without it
    # solve_ms spans prepare + H2D + execute + fence, and the call's own
    # wall adds the extraction of 50,000 assignments.  The first solve
    # finds the program in the compile cache the sidecar filled.
    pods, provs, catalog = config2_scenario()
    ref = reference.solve(pods, provs, catalog)
    st = tensorize(pods, provs, catalog)
    solver = TpuSolver()
    t0 = time.perf_counter()
    out = solver.solve(st, measure=True)
    first_s = time.perf_counter() - t0
    errs = validate_solution(pods, provs, out.result, catalog)
    ratio = out.result.new_node_cost / ref.new_node_cost
    walls, to_fence, fenced = [], [], [out.solve_ms]
    for _ in range(5):
        t0 = time.perf_counter()
        again = solver.solve(st)
        walls.append((time.perf_counter() - t0) * 1000.0)
        to_fence.append(again.solve_ms)
        fenced.append(solver.solve(st, measure=True).solve_ms)

    def spread(xs):
        return {"min": round(min(xs), 3), "median": round(median(xs), 3)}

    report["c2_in_process"] = {
        "first_solve_s": round(first_s, 2),
        "fenced_rerun_ms": spread(fenced),
        "prepare_to_fence_ms": spread(to_fence),
        "solve_call_wall_ms": spread(walls),
        "nodes": len(out.result.nodes),
        "cost_vs_reference": round(ratio, 4),
    }
    checks.check("c2_in_process_valid",
                 len(out.result.assignments) == len(pods)
                 and not out.result.infeasible and not errs
                 and ratio <= COST_CEILING
                 and len(again.result.nodes) == len(out.result.nodes),
                 f"{len(out.result.assignments)}/{len(pods)} assigned, "
                 f"{len(out.result.infeasible)} infeasible, {errs[:2]}, "
                 f"{ratio:.4f}x reference")

    # the Pallas score kernel through Mosaic (interpret=False), bit-for-bit
    # against the lax program.  First at the hierarchical path's real shape
    # — 40 deployments over the full catalog under three provisioners:
    # 40 x 1275 candidates — with the cheapest offering per candidate as
    # the price row and no-offering rows at the solver's 3.0e38 sentinel
    # (what solve_hierarchical's price loop hands the kernel); then at
    # random feasibility on tile-exact and ragged shapes.
    provs3 = [Provisioner(name="default").with_defaults(),
              Provisioner(name="batch", weight=5).with_defaults(),
              Provisioner(name="burst", weight=10).with_defaults()]
    st3 = tensorize(spread_deployments(40, 25, tag="smk"), provs3,
                    generate_catalog(full=True))
    cases = [(pack_feasibility(host_feasibility(st3)), pack_scores(np.minimum(
        np.asarray(st3.cand_price)[:st3.C].min(axis=1), np.float32(3.0e38))))]
    for g, c in ((32, 1152), (64, 1152), (20, 1100)):
        cases.append((pack_feasibility(rng.random((g, c)) < 0.6), pack_scores(
            rng.uniform(0.01, 40.0, c).astype(np.float32))))
    report["pallas"] = []
    for f_packed, price in cases:
        c0, i0 = hier.packed_scan_scores(f_packed, price, use_pallas=False)
        c1, i1 = hier.packed_scan_scores(f_packed, price, use_pallas=True)
        same_bits = (c0.tobytes() == c1.tobytes()
                     and i0.tobytes() == i1.tobytes())
        report["pallas"].append({"shape": list(f_packed.shape),
                                 "feasible_rows": int((c0 < 1e37).sum()),
                                 "bit_for_bit": same_bits})
    checks.check("pallas_mosaic_matches_lax_bit_for_bit",
                 all(p["bit_for_bit"] for p in report["pallas"]),
                 report["pallas"])

    stats = dev.memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use",
                                            "not reported")
    # this child runs last: what every process of the smoke left persisted
    report["cache_entries"] = jit_cache_entries()
    report["assertions"] = checks.rows
    print(json.dumps(report), flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: a launcher — imports neither jax nor karpenter_tpu
# ---------------------------------------------------------------------------


def _last_json(text: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit("child printed no JSON report")


def _run_child(role: str, argv: list, env: dict, procs: list) -> dict:
    """Run ``python chip_smoke.py <role> ...`` to its end and return its
    JSON report; a non-zero exit ends the smoke."""
    started = time.time()
    p = subprocess.Popen([sys.executable, os.path.abspath(__file__), role,
                          *argv], cwd=ROOT, env=env, stdout=subprocess.PIPE,
                         text=True)
    log(f"{role} child started (pid {p.pid})")
    try:
        out, _ = p.communicate(timeout=1000)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    rec = {"role": role, "pid": p.pid, "rc": p.returncode,
           "started": round(started, 3), "stopped": round(time.time(), 3)}
    procs.append(rec)
    if p.returncode != 0:
        raise SystemExit(f"{role} child exited {p.returncode}")
    report = _last_json(out)
    rec["platform"] = report.get("platform")
    return report


def _startup_fields(log_text: str) -> dict:
    """What the sidecar said about itself (service/server.py main)."""
    out = {}
    for key, pat in (("pid", r"pid=(\d+)"), ("platform", r"platform=(\w+)"),
                     ("device_kind", r"device_kind='([^']*)'"),
                     ("devices", r"devices=(\d+)"),
                     ("cold_tier", r"cold_tier=(\w+)"),
                     ("compile_cache", r"compile_cache=(\S+)"),
                     ("cache_entries_before", r"entries=(\d+)"),
                     ("programs_compiled",
                      r"warmup: (\d+) bucket programs compiled"),
                     ("warmup_wall_s",
                      r"bucket programs compiled in ([\d.]+)s")):
        m = re.search(pat, log_text)
        if m:
            out[key] = m.group(1)
    for key in ("pid", "devices", "cache_entries_before",
                "programs_compiled"):
        if key in out:
            out[key] = int(out[key])
    if "warmup_wall_s" in out:
        out["warmup_wall_s"] = float(out["warmup_wall_s"])
    return out


def main() -> int:
    t_start = time.time()
    checks = Checks()
    procs: list = []

    # the C++ cold tier is built on THIS machine from native/ffd.cpp
    for so in glob.glob(os.path.join(ROOT, "karpenter_tpu", "solver",
                                     "_native_*.so")):
        os.unlink(so)
        log(f"removed stale {os.path.basename(so)}")

    run_dir = tempfile.mkdtemp(prefix="kt-smoke-")
    sock = f"unix:{run_dir}/solver.sock"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        obs_port = s.getsockname()[1]
    metrics_url = f"http://127.0.0.1:{obs_port}/metrics"
    sidecar_log = os.path.join(run_dir, "sidecar.log")

    def sidecar_text() -> str:
        with open(sidecar_log) as f:
            return f.read()

    # ---- 1. the sidecar: deploy/solver.yaml's command on a unix socket ----
    sidecar_started = time.time()
    with open(sidecar_log, "w") as logf:
        sidecar = subprocess.Popen(
            [sys.executable, "-m", "karpenter_tpu.service.server",
             "--host", sock, "--backend", "auto", "--warmup",
             "--obs-port", str(obs_port)],
            cwd=ROOT, stdout=logf, stderr=subprocess.STDOUT)
    log(f"sidecar started (pid {sidecar.pid}); waiting for the warm-up grid")
    try:
        deadline = time.monotonic() + SIDECAR_READY_S
        while "solver sidecar listening" not in sidecar_text():
            if sidecar.poll() is not None:
                sys.stderr.write(sidecar_text()[-4000:])
                raise SystemExit(
                    f"sidecar exited {sidecar.returncode} before serving")
            if time.monotonic() > deadline:
                sys.stderr.write(sidecar_text()[-4000:])
                raise SystemExit(
                    f"sidecar not serving after {SIDECAR_READY_S:.0f}s")
            time.sleep(0.5)
        side = _startup_fields(sidecar_text())
        ready_s = time.time() - sidecar_started
        log(f"sidecar serving after {ready_s:.0f}s: {side}")
        checks.check("sidecar_platform_is_tpu",
                     side.get("platform") == "tpu", side)
        checks.check("sidecar_cold_tier_is_native",
                     side.get("cold_tier") == "native",
                     f"cold_tier={side.get('cold_tier')}")
        # every program of the warm-up grid — the relax program included —
        # reports to the scheduler's _warm_done, which records it here; a
        # grid program that failed or reported nowhere leaves this short
        recorded = metric(scrape(metrics_url), M_COMPILES)
        checks.check("every_warmup_program_recorded_its_compile",
                     recorded == side.get("programs_compiled"),
                     f"{recorded:g} compiles recorded, warm-up counted "
                     f"{side.get('programs_compiled')}")

        # ---- 2. the client: the operator, pinned to the CPU ----
        client = _run_child(
            "client", [sock, metrics_url],
            dict(os.environ, JAX_PLATFORMS="cpu"), procs)
        checks.check("client_platform_is_cpu",
                     client.get("platform") == "cpu", client.get("platform"))
        checks.rows.extend(client.pop("assertions"))
        # the relax program is part of the warm-up grid for that batch's
        # shape: had its warm-up compile been skipped, the first request
        # would have found it cold
        checks.check("relax_program_was_warm_from_the_grid",
                     client["relax_attempts"] == 1,
                     f"refined on request {client['relax_attempts']}")

        # ---- 3. what the sidecar counted ----
        deadline = time.monotonic() + COMPILES_LANDED_S
        while (metric(scrape(metrics_url), M_COMPILING) > 0
               and time.monotonic() < deadline):
            time.sleep(2.0)
        final = scrape(metrics_url)
        counted = {
            "device_hangs": metric(final, M_DEVICE_HANGS),
            "degraded_solves": metric(final, M_DEGRADED),
            "cold_fallbacks": metric(final, M_COLD_FALLBACKS),
            "device_solves": metric(final, M_BACKEND_COUNT, backend="tpu"),
            "background_compiles_recorded": metric(final, M_COMPILES),
            "compiles_still_running": metric(final, M_COMPILING),
            "precompile_wall_s": round(metric(final, M_PRECOMPILE_SUM), 1),
        }
        checks.check("no_device_hangs", counted["device_hangs"] == 0,
                     counted["device_hangs"])
        checks.check("no_degraded_solves", counted["degraded_solves"] == 0,
                     counted["degraded_solves"])
        checks.check(
            "cold_fallbacks_all_accounted_for",
            counted["cold_fallbacks"] == client["cold_fallbacks_observed"],
            f"sidecar counted {counted['cold_fallbacks']:g}, the client saw "
            f"{client['cold_fallbacks_observed']} (config 2 before its "
            "compile landed + the delta chain's establishment)")
        checks.check("behind_compiles_landed",
                     counted["compiles_still_running"] == 0,
                     f"{counted['compiles_still_running']:g} still running "
                     f"after {COMPILES_LANDED_S:.0f}s")
    finally:
        # ---- 4. SIGTERM: the sidecar must stop cleanly and free the chip --
        if sidecar.poll() is None:
            sidecar.send_signal(signal.SIGTERM)
            try:
                sidecar.wait(timeout=SIDECAR_EXIT_S)
            except subprocess.TimeoutExpired:
                sidecar.kill()
                sidecar.wait()
    sidecar_stopped = time.time()
    procs.insert(0, {"role": "sidecar", "pid": sidecar.pid,
                     "rc": sidecar.returncode,
                     "platform": side.get("platform"),
                     "started": round(sidecar_started, 3),
                     "stopped": round(sidecar_stopped, 3)})
    checks.check("sidecar_stopped_cleanly",
                 sidecar.returncode == 0
                 and "solver sidecar stopped" in sidecar_text(),
                 f"rc={sidecar.returncode}")
    if not checks.ok:
        sys.stderr.write(sidecar_text()[-3000:])

    # ---- 5. device-direct: alone on the chip the sidecar released ----
    device = _run_child("device", [], dict(os.environ), procs)
    checks.rows.extend(device.pop("assertions"))
    checks.check("device_child_platform_is_tpu",
                 device.get("platform") == "tpu", device.get("platform"))
    # children run one after another by construction (each is waited for
    # before the next starts; the table below shows it); what can go wrong
    # is the launcher itself taking the chip
    checks.check("parent_imported_neither_jax_nor_the_package",
                 "jax" not in sys.modules
                 and "karpenter_tpu" not in sys.modules,
                 sorted(m for m in sys.modules
                        if m in ("jax", "jaxlib", "karpenter_tpu")))

    wall_s = time.time() - t_start
    summary = {
        "ok": checks.ok,
        "device": {"platform": device.get("platform"),
                   "kind": device.get("device_kind"),
                   "count": device.get("device_count")},
        "wall_s": round(wall_s, 1),
        "processes": procs,
        "sidecar": {**side, "ready_after_s": round(ready_s, 1),
                    "metrics": counted},
        "compile_cache": {"dir": side.get("compile_cache"),
                          "entries_before": side.get("cache_entries_before"),
                          "entries_after": device.get("cache_entries")},
        "client": client,
        "device_direct": device,
        # every assertion and its outcome (details are on stderr)
        "assertions": {r["name"]: r["ok"] for r in checks.rows},
        "note": ("smoke readings, not benchmark metrics" + (
            f"; cold wall {wall_s:.0f}s is past {COLD_WALL_NOTE_S:.0f}s — "
            "ROADMAP S2's finding" if wall_s > COLD_WALL_NOTE_S else "")),
    }
    for rec in procs:
        log(f"process {rec}")
    if not checks.ok:
        failed = [r for r in checks.rows if not r["ok"]]
        sys.stderr.write(json.dumps({"failed": failed}, indent=1) + "\n")
        return 1
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "client":
        sys.exit(client_main(sys.argv[2], sys.argv[3]))
    if len(sys.argv) > 1 and sys.argv[1] == "device":
        sys.exit(device_main())
    sys.exit(main())
