"""Mean host-clock time per request in the named spans of the program's own
tracer: sum of ``karpenter_trace_span_duration_seconds_sum{span}`` deltas over
the window, over the requests completed in it.  Leaf spans only (spans nest);
nothing when none of the spans was recorded in the window."""

from scrape import M_SPAN_COUNT, M_SPAN_SUM, delta


def read(ctx: dict, spans: list):
    if not ctx["requests"]:
        return None
    seen = sum(delta(ctx["before"], ctx["after"], M_SPAN_COUNT, span=s)
               for s in spans)
    if seen <= 0:
        return None
    total = sum(delta(ctx["before"], ctx["after"], M_SPAN_SUM, span=s)
                for s in spans)
    return total / ctx["requests"] * 1000.0
