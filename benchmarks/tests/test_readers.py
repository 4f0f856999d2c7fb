"""The readers this benchmark's later metrics use, on plain contexts — and
one rehearsed line whose new metrics have to be readings, not the 0.0 a
program without the families gives."""

import json
import os

import pytest

import run
from test_rehearsal import rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
M_SUM = "karpenter_trace_span_duration_seconds_sum"
M_COUNT = "karpenter_trace_span_duration_seconds_count"
M_SELF = "karpenter_trace_span_self_seconds_total"
M_GC = "karpenter_process_gc_pause_seconds_total"
NEW = ("decode_ms", "serialize_ms", "client_ms", "route_ms", "gc_ms")


def reader(name):
    return run._module(os.path.join(os.path.dirname(HERE), "readers",
                                    f"{name}.py"), f"reader_{name}")


def spec(metric):
    with open(os.path.join(os.path.dirname(HERE), "metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


def ctx(before, after, requests=4, wall=10.0):
    return {"before": before, "after": after, "requests": requests,
            "client_wall_s": wall}


BEFORE = [(M_SUM, {"span": "solve"}, 1.0), (M_COUNT, {"span": "solve"}, 2.0),
          (M_SUM, {"span": "request_decode"}, 0.5),
          (M_SELF, {}, 0.0), (M_SELF, {"span": "solve"}, 0.25),
          (M_GC, {"generation": "0"}, 0.1), (M_GC, {"generation": "2"}, 0.0)]
AFTER = [(M_SUM, {"span": "solve"}, 3.0), (M_COUNT, {"span": "solve"}, 6.0),
         (M_SUM, {"span": "request_decode"}, 4.5),
         (M_SUM, {"span": "request_parse"}, 0.2),
         (M_SUM, {"span": "response_serialize"}, 0.1),
         (M_SELF, {}, 0.0), (M_SELF, {"span": "solve"}, 0.65),
         (M_SELF, {"span": "fence"}, 0.2), (M_SELF, {"span": "reseat"}, 9.0),
         (M_GC, {"generation": "0"}, 0.3), (M_GC, {"generation": "2"}, 0.6)]


@pytest.mark.parametrize("metric,want", [
    ("decode_ms", (4.0 + 0.2) / 4 * 1000.0),
    ("serialize_ms", 0.1 / 4 * 1000.0),
    ("route_ms", (0.4 + 0.2) / 4 * 1000.0),   # solve + fence, not reseat
    ("gc_ms", (0.2 + 0.6) / 4 * 1000.0),      # every generation
    ("client_ms", (10.0 - 2.0 - 4.0 - 0.2 - 0.1) / 4 * 1000.0),
])
def test_new_metrics_on_a_plain_context(metric, want):
    s = spec(metric)
    got = reader(s["reader"]).read(ctx(BEFORE, AFTER), **s["args"])
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_families_reads_a_number_and_never_raises(
        metric):
    """The parent of the PR that added these is traced with these files laid
    over it: its line has to carry every declared metric."""
    s = spec(metric)
    old = [(n, lab, v) for n, lab, v in AFTER
           if n in (M_SUM, M_COUNT) and lab == {"span": "solve"}]
    old_before = [(n, lab, v) for n, lab, v in BEFORE
                  if n in (M_SUM, M_COUNT) and lab == {"span": "solve"}]
    got = reader(s["reader"]).read(ctx(old_before, old), **s["args"])
    # with the root span alone, what is left of the client's wall is wire_ms
    assert got == ((10.0 - 2.0) / 4 * 1000.0 if metric == "client_ms"
                   else 0.0)


@pytest.mark.parametrize("name,args", [
    ("counter_per_request", {"metric": M_GC}),
    ("client_minus_spans", {"root": "solve", "spans": []}),
])
def test_nothing_to_divide_by_reads_nothing(name, args):
    assert reader(name).read(ctx(BEFORE, AFTER, requests=0), **args) is None
    if name == "client_minus_spans":  # no root span recorded: tracing is off
        assert reader(name).read(ctx([], []), **args) is None


def test_a_rehearsed_line_reads_the_door_the_self_times_and_the_collector(
        bench):
    line = rehearse(bench, "c3.burst", 1)
    m = {k: line["metrics"][k]["value"] for k in NEW}
    assert m["decode_ms"] > 0 and m["serialize_ms"] > 0, m
    assert m["route_ms"] > 0 and m["gc_ms"] >= 0, m
    # the door phases lie outside the root: together they are the wire
    assert m["client_ms"] > 0
    assert (m["client_ms"] + m["decode_ms"] + m["serialize_ms"]
            == pytest.approx(line["metrics"]["wire_ms"]["value"]))
    print(json.dumps(line["metrics"]))
