"""Mean client wall of a request minus everything the sidecar has a span for:
the sums of the named spans (the root and the phases at its door, which lie
outside it) are taken off the client's wall.  What is left is the client's own
codec and the two transports.  On a program without the door spans only the
root is taken off, and this reads what ``client_minus_span`` reads."""

from scrape import M_SPAN_COUNT, M_SPAN_SUM, delta


def read(ctx: dict, root: str, spans: list):
    if not ctx["requests"]:
        return None
    if delta(ctx["before"], ctx["after"], M_SPAN_COUNT, span=root) <= 0:
        return None
    inside = sum(delta(ctx["before"], ctx["after"], M_SPAN_SUM, span=s)
                 for s in [root] + spans)
    return (ctx["client_wall_s"] - inside) / ctx["requests"] * 1000.0
