#!/usr/bin/env python3
"""The benchmark's chip-holding child: the solver sidecar, nothing added.

Does what ``karpenter_tpu.service.server main`` does with ``--backend tpu``
and no ``--warmup``: ``SolverService(BatchScheduler(backend="tpu"))`` behind
``make_server`` on loopback TCP (the deployed operator dials the solver over
TCP, ``deploy/operator.yaml``), ``/metrics`` through ``obs.export.serve``.
Both ports are picked by the kernel and told to the launcher on the first
line.  It refuses to start unless jax reports the platform the launcher
expects (``tpu`` from the command; the CPU rehearsal passes ``cpu``), and
needs as many devices as the cell asks for.

Only the process that holds the chip can trace it or read its memory, so this
wrapper answers one-line commands on stdin with one JSON line each on stdout:

    device            platform, kind, count, memory_peak_bytes (+ the number
                      of programs jax has built or loaded in this process)
    trace_start DIR   jax.profiler.start_trace (Python tracer off)
    trace_stop        jax.profiler.stop_trace; answers the traced window_s

SIGTERM (or ``quit``) stops the server and exits 0.  It changes nothing in
the program.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", required=True)
    ap.add_argument("--chips", type=int, required=True)
    args = ap.parse_args(argv)

    import jax

    # programs jax built in this process, by its own monitoring events: a
    # jit that compiles inline in the request path is in no counter of the
    # program's, and "nothing compiles inside the window" has to see it
    built = {"n": 0}

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            built["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devs = jax.devices()
    if devs[0].platform != args.platform or len(devs) < args.chips:
        print(f"sidecar: needs {args.chips} {args.platform} device(s), jax "
              f"found {len(devs)} of platform {devs[0].platform!r}; "
              "refusing to serve", file=sys.stderr, flush=True)
        return 2

    from karpenter_tpu.obs import default_flight
    from karpenter_tpu.obs.export import serve as obs_serve
    from karpenter_tpu.solver.scheduler import BatchScheduler
    from karpenter_tpu.service.server import SolverService, make_server
    from karpenter_tpu.solver.tpu import jit_cache_dir, jit_cache_entries

    service = SolverService(BatchScheduler(backend="tpu"))
    server, port = make_server(service, host="127.0.0.1", port=0)
    flight = service.tracer.flight or default_flight()
    obs, obs_port = obs_serve(service.registry, flight, port=0,
                              host="127.0.0.1", extra=service.statusz_extra,
                              sloz=service.sloz, tunez=service.tunez)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    def device() -> dict:
        peak = 0
        for d in devs[:max(1, args.chips)]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": peak,
                "jit_programs_built": built["n"]}

    tracing = {"on": False}

    def commands() -> None:
        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            try:
                if words[0] == "device":
                    say(device())
                elif words[0] == "trace_start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(words[1], profiler_options=opts)
                    tracing["on"] = True
                    tracing["t0"] = time.perf_counter()
                    say({"tracing": words[1]})
                elif words[0] == "trace_stop":
                    window_s = time.perf_counter() - tracing["t0"]
                    jax.profiler.stop_trace()
                    tracing["on"] = False
                    say({"tracing": None, "window_s": window_s})
                elif words[0] == "quit":
                    break
                else:
                    say({"error": f"unknown command {words[0]!r}"})
            except Exception as err:  # noqa: BLE001 — reported to the launcher
                say({"error": repr(err)})
        stop.set()

    threading.Thread(target=commands, daemon=True).start()
    say({"ready": True, "pid": os.getpid(), "port": port,
         "obs_port": obs_port,
         "compile_cache": jit_cache_dir(),
         "cache_entries": jit_cache_entries(), **device()})
    stop.wait()
    if tracing["on"]:
        jax.profiler.stop_trace()
    server.stop(grace=2.0)
    service.close()
    for sched in service._schedulers.values():
        sched.stop_warms()
    obs.shutdown()
    say({"stopped": True})
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    raise SystemExit(main())
