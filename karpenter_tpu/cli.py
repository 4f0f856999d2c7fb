"""``karpenter-tpu`` command line interface.

Subcommands (the reference ships a single controller binary; this framework
adds the operational entry points around it):

- ``demo``         run the full controller loop against the fake cloud
- ``solve``        one-shot batch solve of a scenario JSON (or a generated one)
- ``serve``        start the gRPC solver sidecar
- ``bench``        run the BASELINE benchmark configs
- ``metrics-doc``  regenerate docs/METRICS.md from the metric inventory
- ``version``      print the package version

``--profile-port`` on demo/serve starts JAX's profiler server (the
ENABLE_PROFILING pprof analog — reference concepts/settings.md:18): point
TensorBoard or ``jax.profiler.trace`` tooling at it for device timelines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

from . import __version__


def _maybe_profile(port: int) -> None:
    if port:
        import jax

        jax.profiler.start_server(port)
        print(f"jax profiler listening on :{port}", file=sys.stderr)


def cmd_demo(args) -> int:
    from .operator import main as op_main

    _maybe_profile(args.profile_port)
    argv = ["--demo", "--pods", str(args.pods), "--backend", args.backend]
    if args.small:
        argv.append("--small")
    if args.metrics_port:
        argv += ["--metrics-port", str(args.metrics_port)]
    if args.config:
        argv += ["--config", args.config]
    if args.solver_address:
        argv += ["--solver-address", args.solver_address]
    return op_main(argv)


def cmd_solve(args) -> int:
    # one-shot process: a background compile would outlive its usefulness and
    # (non-daemon) delay exit by the full XLA compile — serve cold shapes
    # from the warm tier without compiling.

    from .models.catalog import generate_catalog
    from .models.pod import PodSpec
    from .models.provisioner import Provisioner
    from .solver.scheduler import BatchScheduler

    catalog = generate_catalog(full=not args.small)
    if args.scenario:
        with open(args.scenario) as f:
            doc = json.load(f)
        pods = [PodSpec(name=p["name"], requests=p.get("requests", {}),
                        labels=p.get("labels", {}),
                        node_selector=p.get("node_selector", {}))
                for p in doc["pods"]]
        provs = [Provisioner(name=p["name"], weight=p.get("weight", 0),
                             limits=p.get("limits", {})).with_defaults()
                 for p in doc.get("provisioners", [{"name": "default"}])]
    else:
        pods = [PodSpec(name=f"p{i}", requests={"cpu": 1.0}, owner_key="cli")
                for i in range(args.pods)]
        provs = [Provisioner(name="default").with_defaults()]
    res = BatchScheduler(
        backend=args.backend, compile_behind=False,
    ).solve(pods, provs, catalog)
    out = {
        "scheduled": res.n_scheduled,
        "infeasible": len(res.infeasible),
        "new_nodes": len(res.nodes),
        "node_cost_per_hr": round(res.new_node_cost, 4),
        "solve_ms": round(res.solve_ms, 3),
        "nodes": [
            {"name": n.name, "instance_type": n.instance_type, "zone": n.zone,
             "capacity_type": n.capacity_type, "price": n.price,
             "pods": len(n.pods)}
            for n in res.nodes
        ],
    }
    if args.assignments:
        out["assignments"] = res.assignments
        out["infeasible_reasons"] = res.infeasible
    print(json.dumps(out, indent=None if args.compact else 2))
    return 0 if not res.infeasible else 3


def cmd_serve(args) -> int:
    from .service.server import main as serve_main

    _maybe_profile(args.profile_port)
    argv = ["--port", str(args.port), "--backend", args.backend,
            "--obs-port", str(args.obs_port)]
    if args.max_slots is not None:
        argv += ["--max-slots", str(args.max_slots)]
    if args.max_wait_ms is not None:
        argv += ["--max-wait-ms", str(args.max_wait_ms)]
    if args.admission is not None:
        argv += ["--admission", args.admission]
    if args.default_priority is not None:
        argv += ["--default-priority", args.default_priority]
    if args.default_deadline_ms is not None:
        argv += ["--default-deadline-ms", str(args.default_deadline_ms)]
    if args.session_dir is not None:
        argv += ["--session-dir", args.session_dir]
    if args.warmup:
        argv.append("--warmup")
    if args.small:
        argv.append("--small")
    return serve_main(argv)


def cmd_bench(args) -> int:
    import subprocess

    # bench_all.py lives at the repo root, not in the installed package
    script = Path(__file__).resolve().parent.parent / "bench_all.py"
    if not script.exists():
        print("bench_all.py not found (benchmarks run from a repo checkout, "
              "not an installed package)", file=sys.stderr)
        return 2
    return subprocess.call([sys.executable, str(script),
                            "--configs", args.configs], cwd=str(script.parent))


def cmd_metrics_doc(args) -> int:
    from .metrics import INVENTORY

    lines = [
        "# Metrics",
        "",
        "Prometheus metrics exposed on the operator's `/metrics` endpoint",
        "(`karpenter_tpu/metrics.py`; names mirror the reference's",
        "`concepts/metrics.md`).  Generated by `karpenter-tpu metrics-doc` —",
        "do not edit by hand.",
        "",
        "| Name | Type | Labels | Description |",
        "|---|---|---|---|",
    ]
    for name, (kind, labels, help_) in sorted(INVENTORY.items()):
        lab = ", ".join(labels) if labels else "—"
        lines.append(f"| `{name}` | {kind} | {lab} | {help_} |")
    text = "\n".join(lines) + "\n"
    if args.check:
        try:
            with open(args.out) as f:
                current = f.read()
        except FileNotFoundError:
            current = None  # missing counts as stale
        if current != text:
            print(f"{args.out} is stale; run `karpenter-tpu metrics-doc`",
                  file=sys.stderr)
            return 1
        return 0
    with open(args.out, "w") as f:
        f.write(text)
    print(f"wrote {args.out}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="karpenter-tpu", description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    d = sub.add_parser("demo", help="run the controller loop on the fake cloud")
    d.add_argument("--pods", type=int, default=200)
    d.add_argument("--small", action="store_true")
    d.add_argument("--backend", default="auto", choices=["auto", "tpu", "oracle"])
    d.add_argument("--metrics-port", type=int, default=0)
    d.add_argument("--profile-port", type=int, default=0)
    d.add_argument("--solver-address",
                   default=os.environ.get("KARPENTER_SOLVER_ADDR", ""),
                   help="host:port of a solver sidecar (kt serve); empty "
                        "solves in-process; defaults from "
                        "KARPENTER_SOLVER_ADDR (deploy/operator.yaml)")
    d.add_argument("--config", default="",
                   help="YAML manifest file/dir loaded through admission")
    d.set_defaults(fn=cmd_demo)

    s = sub.add_parser("solve", help="one-shot batch solve")
    s.add_argument("--scenario", help="scenario JSON file (pods/provisioners)")
    s.add_argument("--pods", type=int, default=100)
    s.add_argument("--small", action="store_true")
    s.add_argument("--backend", default="auto", choices=["auto", "tpu", "native", "oracle"])
    s.add_argument("--assignments", action="store_true", help="include per-pod assignments")
    s.add_argument("--compact", action="store_true")
    s.set_defaults(fn=cmd_solve)

    v = sub.add_parser("serve", help="gRPC solver sidecar")
    v.add_argument("--port", type=int, default=50151)
    v.add_argument("--backend", default="auto", choices=["auto", "tpu", "oracle"])
    v.add_argument("--obs-port", type=int, default=0,
                   help="observability HTTP port (/tracez, /statusz, "
                        "/metrics — docs/OBSERVABILITY.md); 0 disables")
    v.add_argument("--profile-port", type=int, default=0)
    v.add_argument("--max-slots", type=int, default=None,
                   help="megabatch request slots per coalescer flush "
                        "(KT_MAX_SLOTS; 1 disables cross-request batching)")
    v.add_argument("--max-wait-ms", type=float, default=None,
                   help="max hold before a partial megabatch flushes "
                        "(KT_MAX_WAIT_MS; 0 = flush on queue idle)")
    v.add_argument("--admission", choices=["on", "off"], default=None,
                   help="admission control & overload protection "
                        "(docs/ADMISSION.md; KT_ADMISSION, default on)")
    v.add_argument("--default-priority", default=None,
                   choices=["critical", "batch", "best_effort"],
                   help="priority class for requests carrying none "
                        "(KT_DEFAULT_PRIORITY_CLASS; default batch)")
    v.add_argument("--default-deadline-ms", type=float, default=None,
                   help="enqueue deadline when the RPC carries none "
                        "(KT_DEFAULT_DEADLINE_MS; 0 = no deadline)")
    v.add_argument("--session-dir", default=None,
                   help="delta-session snapshot spool (KT_SESSION_DIR): "
                        "restored at startup, written on graceful "
                        "shutdown + every KT_SESSION_SNAPSHOT_S "
                        "(docs/RESILIENCE.md)")
    v.add_argument("--warmup", action="store_true",
                   help="block startup on the AOT bucket-grid precompile "
                        "(single ladder + megabatch rungs) so the serving "
                        "path never compiles")
    v.add_argument("--small", action="store_true",
                   help="--warmup against the 20-type catalog")
    v.set_defaults(fn=cmd_serve)

    b = sub.add_parser("bench", help="run BASELINE benchmark configs")
    b.add_argument("--configs", default="1,2,3,4,5,6")
    b.set_defaults(fn=cmd_bench)

    m = sub.add_parser("metrics-doc", help="regenerate docs/METRICS.md")
    m.add_argument("--out", default="docs/METRICS.md")
    m.add_argument("--check", action="store_true")
    m.set_defaults(fn=cmd_metrics_doc)

    ver = sub.add_parser("version", help="print version")
    ver.set_defaults(fn=lambda a: (print(f"karpenter-tpu {__version__}"), 0)[1])

    args = p.parse_args(argv)
    rc = args.fn(args)
    # exit joins non-daemon warm compile threads; bound that wait so a
    # compile hung inside the device runtime cannot pin the process forever
    from .operator import drain_warm_threads

    drain_warm_threads()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
