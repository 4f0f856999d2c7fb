"""The metrics ISSUE 37 added, each through its own file and the reader that
was there (``counter_per_request``): a reading on a plain context, 0.0 and
no raise on a program without the families (the parent of that PR is traced
with these files laid over it), the entries ``BENCHMARK.json`` declares for
them, and one rehearsed line on which they are readings above 0."""

import json
import os

import pytest

import run
from test_rehearsal import rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
M_SUM = "karpenter_trace_span_duration_seconds_sum"
M_SELF = "karpenter_trace_span_self_seconds_total"
M_GC = "karpenter_process_gc_pause_seconds_total"
M_GC_SPAN = "karpenter_trace_span_gc_pause_seconds_total"
M_MISSES = "karpenter_solver_tensorize_cache_misses_total"
M_HITS = "karpenter_solver_tensorize_cache_hits_total"
M_BLOCKS = "karpenter_process_allocated_blocks"

#: name -> (unit, layer, the reading on PLAIN below)
NEW = {
    "tensorize_builds": ("builds", "tensorize", 1.0),
    "tensorize_regroups": ("passes", "tensorize", 0.5),
    "route_harden_ms": ("ms", "scheduler outside its leaves", 20.0),
    "route_carve_ms": ("ms", "scheduler outside its leaves", 30.0),
    "route_signature_ms": ("ms", "scheduler outside its leaves", 1.0),
    "route_ladder_ms": ("ms", "scheduler outside its leaves", 9.0),
    "route_unnamed_ms": ("ms", "scheduler outside its leaves", 10.0),
    "extract_readback_ms": ("ms", "host epilogues", 15.0),
    "extract_nodes_ms": ("ms", "host epilogues", 40.0),
    "extract_assign_ms": ("ms", "host epilogues", 50.0),
    "reseat_ms": ("ms", "host epilogues", 60.0),
    "relax_ms": ("ms", "host epilogues", 70.0),
    "gc_gen2_ms": ("ms", "collector pauses in the sidecar", 80.0),
    "gc_decode_ms": ("ms", "collector pauses in the sidecar", 45.0),
    "gc_epilogue_ms": ("ms", "collector pauses in the sidecar", 36.0),
    "heap_kept_blocks": ("blocks", "collector pauses in the sidecar", 250.0),
    "await_ms": ("ms", "client codec + transport", 90.0),
}


def ms(per_request: float) -> float:
    """Seconds over the window that read ``per_request`` ms over 4."""
    return per_request * 4 / 1000.0


#: what moved over a window of 4 requests; every family also holds a sample
#: that no file may count
PLAIN = [
    (M_MISSES, {}, 4.0),
    (M_HITS, {"tier": "shape"}, 2.0), (M_HITS, {"tier": "identity"}, 9.0),
    (M_SUM, {"span": "harden"}, ms(20)), (M_SUM, {"span": "carve"}, ms(30)),
    (M_SUM, {"span": "signature"}, ms(1)),
    (M_SUM, {"span": "ladder"}, ms(999)), (M_SELF, {"span": "ladder"}, ms(9)),
    (M_SUM, {"span": "readback"}, ms(15)),
    (M_SUM, {"span": "nodes"}, ms(40)), (M_SUM, {"span": "assign"}, ms(50)),
    (M_SUM, {"span": "reseat"}, ms(60)), (M_SUM, {"span": "relax"}, ms(70)),
    (M_SUM, {"span": "await_request"}, ms(90)),
    (M_SUM, {"span": "extract"}, ms(999)),
    (M_SELF, {"span": "solve"}, ms(4)), (M_SELF, {"span": "dispatch"}, ms(3)),
    (M_SELF, {"span": "fence"}, ms(2)), (M_SELF, {"span": "bucket"}, ms(1)),
    (M_SELF, {"span": "harden"}, ms(999)),
    (M_GC, {"generation": "2"}, ms(80)), (M_GC, {"generation": "0"}, ms(999)),
    (M_GC_SPAN, {"span": "request_parse"}, ms(5)),
    (M_GC_SPAN, {"span": "request_decode"}, ms(40)),
    (M_GC_SPAN, {"span": "none"}, ms(999)),
    (M_GC_SPAN, {"span": "tensorize"}, ms(999)),
    *[(M_GC_SPAN, {"span": s}, ms(k + 1)) for k, s in enumerate(
        ("extract", "readback", "nodes", "assign", "coalesce", "reseat",
         "relax", "gang"))],
    (M_BLOCKS, {}, 1000.0),
]


def reader(name):
    return run._module(os.path.join(os.path.dirname(HERE), "readers",
                                    f"{name}.py"), f"reader_{name}")


def spec(metric):
    with open(os.path.join(os.path.dirname(HERE), "metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


def ctx(before, after, requests=4):
    return {"before": before, "after": after, "requests": requests}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_metric_on_a_plain_context(metric):
    s = spec(metric)
    assert s["reader"] == "counter_per_request"  # no new reader
    before = [(n, lab, 0.0) for n, lab, _v in PLAIN]
    got = reader(s["reader"]).read(ctx(before, PLAIN), **s["args"])
    assert got == pytest.approx(NEW[metric][2])


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_program_without_the_families_reads_zero_and_never_raises(metric):
    s = spec(metric)
    read = reader(s["reader"]).read
    old = [(M_SUM, {"span": "solve"}, 3.0), (M_SELF, {}, 0.0)]
    assert read(ctx(old, old), **s["args"]) == 0.0
    assert read(ctx([], []), **s["args"]) == 0.0
    assert read(ctx(old, old, requests=0), **s["args"]) is None


def test_the_declared_entries_are_the_files_appended_in_order(bench):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("coalesce_pairs") + 1  # what PR 36 left last
    assert set(names[at:at + len(NEW)]) == set(NEW)
    for decl in bench["per_layer"][at:at + len(NEW)]:
        s = spec(decl["name"])
        assert decl == {k: s[k] for k in ("name", "unit", "better", "source",
                                          "layer", "moves")}
        unit, layer, _ = NEW[decl["name"]]
        assert (decl["unit"], decl["layer"], decl["better"], decl["moves"]
                ) == (unit, layer, "lower", "solve_ms")
        assert "workloads" not in decl  # every cell reports solve_ms
    # no layer is new: each metric joins one BENCHMARK.json already names
    assert {m["layer"] for m in bench["per_layer"][at:]} <= {
        m["layer"] for m in bench["per_layer"][:at]}


ROUTE_LEAVES = ("route_harden_ms", "route_carve_ms", "route_signature_ms",
                "route_ladder_ms", "route_unnamed_ms")


def spans_of(metric):
    return sorted(sel["span"] for sel in spec(metric)["args"]["labels"])


def parts_of_route_ms():
    """The route leaves whose spans ``route_ms`` (a file that was there)
    lists: today all but ``route_signature_ms`` and ``route_ladder_ms``,
    whose spans are new names it never listed (ROADMAP: a ``benchmark``
    issue adds them to ``route_ms.json``, and this then holds all five)."""
    whole = set(spans_of("route_ms"))
    return [leaf for leaf in ROUTE_LEAVES if set(spans_of(leaf)) <= whole]


def test_every_span_of_route_ms_is_read_by_one_route_leaf():
    every = [span for leaf in ROUTE_LEAVES for span in spans_of(leaf)]
    assert len(every) == len(set(every))
    parts = [span for leaf in parts_of_route_ms() for span in spans_of(leaf)]
    assert sorted(parts) == spans_of("route_ms")


def test_a_rehearsed_line_reads_the_leaves_the_collector_and_the_door(bench):
    line = rehearse(bench, "c3.burst", 1)
    m = {k: line["metrics"][k]["value"] for k in NEW}
    for name in ("tensorize_builds", "route_harden_ms", "route_carve_ms",
                 "route_signature_ms", "route_ladder_ms", "route_unnamed_ms",
                 "extract_readback_ms", "extract_nodes_ms",
                 "extract_assign_ms", "reseat_ms", "relax_ms", "await_ms"):
        assert m[name] > 0, (name, m)
    # a rehearsal's window is too short to count on a collection in each,
    # and what the heap keeps is the program's to bring to 0 (ROADMAP S3)
    assert all(m[k] >= 0 for k in ("gc_gen2_ms", "gc_decode_ms",
                                   "gc_epilogue_ms", "heap_kept_blocks"))
    have = {k: line["metrics"][k]["value"] for k in (
        "route_ms", "gc_ms", "tensorize_ms", "epilogue_ms", "coalesce_ms")}
    assert sum(m[leaf] for leaf in parts_of_route_ms()) == pytest.approx(
        have["route_ms"])
    assert m["gc_decode_ms"] + m["gc_epilogue_ms"] <= have["gc_ms"] + 1e-9
    # the probe's build is in tensorize_ms now, and the leaves of `extract`
    # with `reseat` and `relax` stay inside epilogue_ms
    assert have["tensorize_ms"] > m["route_harden_ms"]
    assert (m["extract_readback_ms"] + m["extract_nodes_ms"]
            + m["extract_assign_ms"] + have["coalesce_ms"] + m["reseat_ms"] + m["relax_ms"]
            <= have["epilogue_ms"] + 1e-9)
    print(json.dumps(line["metrics"]))
