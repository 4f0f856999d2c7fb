"""chip_smoke.py on the CPU: a rehearsal of its client phase, and its refusal.

``chip_smoke.main`` itself needs a TPU and says so with its exit code; what a
CPU can check is (a) that refusal, and (b) the client phase's own logic —
its assertions and its ``/metrics`` parsing — against an in-process sidecar
at a tiny size.  On a CPU exactly one of the client's assertions must FAIL:
the one that Health says "tpu".
"""

import os
import subprocess
import sys
import tempfile
import time

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_scenario():
    """Config 2's shape (zone-spread deployments) cut to 3 x 120 pods over
    the 20-type catalog: above the 256-pod small-batch route, so the device
    tier serves it once its program has compiled behind."""
    from karpenter_tpu.models import labels as L
    from karpenter_tpu.models.catalog import generate_catalog
    from karpenter_tpu.models.pod import (
        LabelSelector,
        PodSpec,
        TopologySpreadConstraint,
    )
    from karpenter_tpu.models.provisioner import Provisioner

    pods = []
    for d in range(3):
        sel = LabelSelector.of({"app": f"r{d}"})
        for i in range(120):
            pods.append(PodSpec(
                name=f"r{d}-{i}", labels={"app": f"r{d}"},
                requests={"cpu": 0.25 * (1 + d), "memory": float(2 ** 30)},
                topology_spread=[TopologySpreadConstraint(
                    1, L.ZONE, "DoNotSchedule", sel)],
                owner_key=f"r{d}"))
    return (pods, [Provisioner(name="default").with_defaults()],
            generate_catalog(full=False))


@pytest.fixture()
def sidecar():
    """An in-process sidecar the way server.main wires one: auto backend,
    unix socket, obs HTTP server on the same registry."""
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.obs import default_flight
    from karpenter_tpu.obs.export import serve as obs_serve
    from karpenter_tpu.service.server import SolverService, make_server
    from karpenter_tpu.solver.scheduler import BatchScheduler

    reg = Registry()
    sched = BatchScheduler(backend="auto", registry=reg)
    service = SolverService(sched, registry=reg)
    sock = f"unix:{tempfile.mkdtemp(prefix='kt-smoke-test-')}/solver.sock"
    srv, _ = make_server(service, host=sock)
    obs, port = obs_serve(reg, service.tracer.flight or default_flight(),
                          port=0)
    try:
        yield sock, f"http://127.0.0.1:{port}/metrics", sched
    finally:
        srv.stop(grace=None)
        service.close()
        obs.shutdown()
        sched.stop_warms()
        deadline = time.monotonic() + 120
        while not sched._tpu.warm_idle() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert sched._tpu.warm_idle()


def test_client_phase_rehearsal_on_cpu(sidecar):
    sock, metrics_url, sched = sidecar
    report = chip_smoke.client_phase(
        sock, metrics_url, scenario=_tiny_scenario, chain_pods=3000,
        churn_steps=3, churn=4, small_pods=40, steady=2, device_wait_s=120)
    failed = [a["name"] for a in report["assertions"] if not a["ok"]]
    # the one thing a CPU cannot give the client
    assert failed == ["health_backend_is_tpu"], report["assertions"]
    assert report["health"]["backend"] == "cpu"
    # cold first, then the compiled program: the tiers came off /metrics
    by_label = {r["request"]: r for r in report["requests"]}
    first = report["requests"][0]
    assert first["tiers"] == {"native": 1} and first["cold_fallbacks"] == 1
    assert by_label["c2_steady_2"]["tiers"] == {"tpu": 1}
    # no warm-up grid here: the relax program compiles behind whichever
    # reply first finds it cold ("skipped"), then refines a later one —
    # the outcomes came off /metrics
    rx = [r for r in report["requests"]
          if r["request"].startswith("relax_batch")]
    assert all(set(r["relax"]) == {"skipped"} or not r["tiers"].get("tpu")
               for r in rx[:-1])
    assert rx[-1]["relax"] in ({"improved": 1}, {"tied": 1})
    assert report["relax_attempts"] == len(rx)
    assert by_label["small_batch"]["served_by_routing_policy"] is True
    assert report["delta"]["full_resends"] == 1
    assert report["delta"]["epoch_end"] == report["delta"]["epoch_start"] + 3
    assert report["client_local_serves"] == 0
    # what the parent cross-checks against the sidecar's final count
    from karpenter_tpu.metrics import SOLVER_COLD_FALLBACKS

    counted = sum(sched.registry.counter(SOLVER_COLD_FALLBACKS).values
                  .values())
    assert counted == report["cold_fallbacks_observed"]


def test_metrics_parsing():
    text = "\n".join([
        "# HELP karpenter_x_total help text with {braces} and 1 number",
        "# TYPE karpenter_x_total counter",
        'karpenter_x_total{backend="tpu"} 3',
        'karpenter_x_total{backend="native",why="a b"} 2',
        "karpenter_y 1.5e+00",
        'karpenter_h_bucket{backend="tpu",le="+Inf"} 7',
    ])
    samples = chip_smoke.parse_metrics(text)
    assert chip_smoke.metric(samples, "karpenter_x_total") == 5
    assert chip_smoke.metric(samples, "karpenter_x_total", backend="tpu") == 3
    assert chip_smoke.metric(samples, "karpenter_y") == 1.5
    assert chip_smoke.metric(samples, "karpenter_absent_total") == 0
    assert chip_smoke.metric(samples, "karpenter_h_bucket", le="+Inf") == 7
    with pytest.raises(ValueError):
        chip_smoke.parse_metrics("not a sample line at all")


def test_serving_tiers_is_a_scrape_difference():
    name = chip_smoke.M_BACKEND_COUNT
    before = [(name, {"backend": "tpu"}, 4.0),
              (name, {"backend": "native"}, 1.0)]
    after = [(name, {"backend": "tpu"}, 5.0),
             (name, {"backend": "native"}, 1.0),
             (name, {"backend": "oracle"}, 2.0)]
    assert chip_smoke.serving_tiers(before, after) == {"tpu": 1, "oracle": 2}


def test_startup_fields_parse_the_sidecars_own_lines():
    log = (
        "solver sidecar starting (pid=41, backend=auto, platform=tpu, "
        "device_kind='TPU v5 lite', devices=1, cold_tier=native, "
        "compile_cache=/r/.jax_cache entries=12)\n"
        "warmup: 10 bucket programs compiled in 61.5s; serving\n")
    assert chip_smoke._startup_fields(log) == {
        "pid": 41, "platform": "tpu", "device_kind": "TPU v5 lite",
        "devices": 1, "cold_tier": "native",
        "compile_cache": "/r/.jax_cache", "cache_entries_before": 12,
        "programs_compiled": 10, "warmup_wall_s": 61.5}


def test_main_refuses_a_cpu():
    """No TPU -> non-zero exit and nothing on stdout: no summary, no
    ``{"ok": true, ...}`` line.  (The sidecar child refuses to serve; the
    parent has no flag, env var or except that would let it pass.)"""
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_main_fails_alone_in_a_directory(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    there is no program to start: non-zero exit, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=str(tmp_path), env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
