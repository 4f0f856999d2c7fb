#!/usr/bin/env python3
"""One cell of the benchmark with the CLIENT traced too (ISSUE 25).

    chiprun -- python scripts/trace_probe.py --workload c2.burst [--seed N]

The benchmark's readers see the sidecar's ``/metrics`` only, so its
``client_ms`` is a subtraction.  This drives the same cell through the
benchmark's own entry (``benchmarks/run.py run_cell``: same sidecar, traffic,
warm-up, window and comparison) and hands every ``RemoteScheduler.solve`` a
trace of a client-side tracer, so the client's ``encode``/``rpc``/``decode``
spans can be set beside that number.  It also scrapes the sidecar after each
request (outside the request's wall, inside the window: this is a probe, not
a measurement of ``solve_ms``), which gives per request the door spans, the
root and the collector's pauses on both sides — the position pattern of a
pass, split by side — and how many of its pods the client wrote from a
template (``encode.hit_share``, off the client's own registry: a client
without the family reads nothing), and beside it how the client put the
catalog on the wire: the ``encode`` span's ``catalog`` attribute and the
client's ``karpenter_solver_request_catalog_sent_total`` (``encode.catalog``,
``encode.catalog_sent``).  Beside the client's spans stand the sidecar's
leaves (ISSUE 37): what each of ``bucket``, ``extract``, the scheduler
outside them and the door keeps under which name (``leaves_ms``; ``own`` is
a parent's self time, the stretch no leaf names yet), the collector's pauses by the span they stopped
(``gc_by_span_ms``), and the identities the ledger's metrics rest on
(``identities``: each ``[left, right]`` pair has to agree).  What the
sidecar's side of the wire costs is in the ledger (``decode_ms``,
``decode_templated_pods``, ``gc_gen2_ms``) and is not computed here again.

Prints one JSON object (also written to
``chiprun_out/trace_probe.<cell>.<platform>.json``): the run's metrics as the benchmark read them, the sidecar's spans per request
(duration and self time), the client's spans per request, and the per-request
rows.  Needs a TPU exactly as the benchmark does.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

M_SUM = "karpenter_trace_span_duration_seconds_sum"
M_COUNT = "karpenter_trace_span_duration_seconds_count"
M_SELF = "karpenter_trace_span_self_seconds_total"
M_GC = "karpenter_process_gc_pause_seconds_total"
M_GC_SPAN = "karpenter_trace_span_gc_pause_seconds_total"
M_TENSORIZE_HITS = "karpenter_solver_tensorize_cache_hits_total"
M_ENCODED = "karpenter_solver_request_encode_pods_total"
M_CATALOG_SENT = "karpenter_solver_request_catalog_sent_total"
CATALOG_SENT_HOW = ("digest", "full", "resent")


def by_label(samples: list, name: str, label: str) -> dict:
    return {lab[label]: v for n, lab, v in samples
            if n == name and label in lab}


def hit_share(by_how: dict):
    """templated / all, or None where nothing was counted."""
    total = sum(by_how.values())
    return by_how.get("templated", 0.0) / total if total else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    sidecar_env = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process is the operator
    for path in (BENCH, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    import scrape as S
    from karpenter_tpu.metrics import Registry
    from karpenter_tpu.obs import FlightRecorder, Tracer

    creg = Registry()
    ctracer = Tracer(registry=creg, flight=FlightRecorder(registry=creg))
    rows: list = []
    state: dict = {}

    def server_now() -> dict:
        samples = S.scrape(state["run"].sidecar.metrics_url)
        return {"sum": by_label(samples, M_SUM, "span"),
                "gc": by_label(samples, M_GC, "generation")}

    def client_gc() -> dict:
        return {g: creg.counter(M_GC).get({"generation": g}) for g in "012"}

    def tamper(remote):
        inner = remote.solve
        encoded = remote.registry.counter(M_ENCODED)
        catalog_sent = remote.registry.counter(M_CATALOG_SENT)

        def client_encoded() -> dict:
            return {how: encoded.get({"how": how})
                    for how in ("templated", "plain")}

        def client_catalog() -> dict:
            return {how: catalog_sent.get({"how": how})
                    for how in CATALOG_SENT_HOW}

        def solve(pods, provisioners, catalog, **kw):
            if "before" not in state:
                state["before"] = server_now()
            gc0, enc0, cat0 = client_gc(), client_encoded(), client_catalog()
            with ctracer.start("provision", n_pods=len(pods)) as trace:
                res = inner(pods, provisioners, catalog, trace=trace, **kw)
            gc1, enc1, after = client_gc(), client_encoded(), server_now()
            cat1 = client_catalog()
            before, state["before"] = state["before"], after
            spans = {n: d for n, d, _s in trace.closed_spans()}
            attrs = {sp.name: sp.attrs for sp in trace.spans()}
            rows.append({
                "wall_ms": trace.duration_s * 1000.0,
                "client_ms": {k: spans.get(k, 0.0) * 1000.0 for k in
                              ("remote", "encode", "rpc", "decode")},
                "encode": {
                    "duration_ms": spans.get("encode", 0.0) * 1000.0,
                    "shapes": attrs.get("encode", {}).get("shapes"),
                    "hit_share": hit_share(
                        {how: enc1[how] - enc0[how] for how in enc1}),
                    # how the catalog went: the span's word for it, and
                    # the client's counter (a client without either reads
                    # nothing / zeros)
                    "catalog": attrs.get("encode", {}).get("catalog"),
                    "catalog_sent": {how: cat1[how] - cat0[how]
                                     for how in cat1}},
                "client_gc_ms": {g: (gc1[g] - gc0[g]) * 1000.0 for g in gc1},
                "server_ms": {
                    k: (after["sum"].get(k, 0.0)
                        - before["sum"].get(k, 0.0)) * 1000.0
                    for k in ("request_parse", "request_decode", "solve",
                              "response_serialize")},
                "server_gc_ms": {
                    g: (after["gc"].get(g, 0.0)
                        - before["gc"].get(g, 0.0)) * 1000.0
                    for g in sorted(after["gc"])},
            })
            return res

        remote.solve = solve
        return remote

    # the benchmark's own window scrapes, as its readers were handed them
    window: dict = {}
    read_layer_metrics = run.read_layer_metrics

    def capture(bench, workload, ctx):
        window.update(ctx)
        return read_layer_metrics(bench, workload, ctx)

    run.read_layer_metrics = capture
    run_init = run.Run.__init__

    def remember(self, *a, **kw):
        run_init(self, *a, **kw)
        state["run"] = self

    run.Run.__init__ = remember

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    try:
        line = run.run_cell(bench, args.workload, args.seed, args.seconds, 0,
                            platform=args.platform, scale=args.scale,
                            tamper=tamper, sidecar_env=sidecar_env)
    except run.RunFailed as err:
        print(f"probe failed: {err}", file=sys.stderr, flush=True)
        return 1
    n = line["attempted"]
    timed = rows[-n:]
    before, after = window["before"], window["after"]
    spans = {}
    for name in sorted(by_label(after, M_COUNT, "span")):
        count = S.delta(before, after, M_COUNT, span=name)
        if count:
            spans[name] = {
                "per_request": count / n,
                "duration_ms": S.delta(before, after, M_SUM,
                                       span=name) / n * 1000.0,
                "self_ms": S.delta(before, after, M_SELF,
                                   span=name) / n * 1000.0}

    def dur(name):
        return spans.get(name, {}).get("duration_ms", 0.0)

    def own(name):
        return spans.get(name, {}).get("self_ms", 0.0)

    # a parent's leaves sum over EVERY span of the name (``harden``,
    # ``carve`` and ``tensorize`` also run outside ``bucket``): the split is
    # of the request, as the ledger's metrics are
    leaves = {
        "bucket": {"own": own("bucket"), **{k: dur(k) for k in (
            "harden", "carve", "tensorize", "signature")}},
        "extract": {"own": own("extract"), **{k: dur(k) for k in (
            "readback", "nodes", "assign", "coalesce")}},
        # what no leaf names: the own time of the spans that only hold
        # others, and beside it `ladder`'s (its rungs are solves)
        "scheduler": {"ladder": own("ladder"), **{
            f"{k}.own": own(k) for k in ("solve", "dispatch", "fence")}},
        "epilogue_beside_extract": {k: dur(k) for k in (
            "reseat", "relax", "gang")},
        "door": {k: dur(k) for k in (
            "await_request", "request_parse", "request_decode",
            "response_serialize")}}
    # a request's `tensorize` spans by what the cache answered: the builds
    # are the ledger's `tensorize_builds`, the hits stand beside them
    leaves["tensorize_hits"] = {
        tier: S.delta(before, after, M_TENSORIZE_HITS, tier=tier) / n
        for tier in sorted(by_label(after, M_TENSORIZE_HITS, "tier"))}
    gc_by_span = {span: S.delta(before, after, M_GC_SPAN, span=span)
                  / n * 1000.0
                  for span in sorted(by_label(after, M_GC_SPAN, "span"))}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    inside = [k for k in spans if k not in leaves["door"]]
    identities = {
        # inside one root the self times sum to the root (plus what
        # `admission` spends side by side with `window`)
        "root = self times inside it": [
            dur("solve"), sum(own(k) for k in inside) - dur("admission")],
        # `signature` and `ladder` are spans route_ms.json never listed
        "root = leaf metrics + route_ms + signature + ladder": [
            dur("solve"), sum(m.get(k, 0.0) for k in (
                "window_ms", "tensorize_ms", "dispatch_ms", "fence_ms",
                "epilogue_ms", "respond_ms", "route_ms",
                "route_signature_ms", "route_ladder_ms"))],
        "route_ms = harden + carve + unnamed": [
            m.get("route_ms"), sum(m.get(k, 0.0) for k in (
                "route_harden_ms", "route_carve_ms", "route_unnamed_ms"))],
        "gc by span = gc by generation": [
            sum(gc_by_span.values()), m.get("gc_ms")],
    }

    def mean(key, sub):
        return sum(r[key][sub] for r in timed) / n

    def encode_mean(sub):
        got = [r["encode"][sub] for r in timed if r["encode"][sub] is not None]
        return sum(got) / len(got) if got else None

    out = {
        "workload": args.workload, "seed": args.seed, "requests": n,
        "correct": line["correct"], "device": line["device"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "sidecar_spans": spans,
        "leaves_ms": leaves,
        "gc_by_span_ms": gc_by_span,
        "identities": identities,
        # beside the client's encode span: the shapes its table held and the
        # share of pods it wrote from one, means over the window's requests
        "encode": {
            "duration_ms": mean("client_ms", "encode"),
            "shapes": encode_mean("shapes"),
            "hit_share": encode_mean("hit_share"),
            # the window's requests by the span's ``catalog`` attribute,
            # and the client's counter over the same requests
            "catalog": dict(collections.Counter(
                str(r["encode"]["catalog"]) for r in timed)),
            "catalog_sent": {how: sum(r["encode"]["catalog_sent"][how]
                                      for r in timed)
                             for how in CATALOG_SENT_HOW}},
        "client_spans_ms": {k: mean("client_ms", k) for k in
                            ("remote", "encode", "rpc", "decode")},
        "client_gc_ms": {g: mean("client_gc_ms", g) for g in "012"},
        "requests_in_order": timed,
    }
    out["client_encode_plus_decode_ms"] = (
        out["client_spans_ms"]["encode"] + out["client_spans_ms"]["decode"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = f"trace_probe.{args.workload}.{args.platform}.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
