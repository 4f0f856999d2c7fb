"""Mean client wall of a request minus the mean of the server's root span:
what the client, the codec both ways, the transport and the server-side decode
(which runs before the root span opens) cost together."""

from scrape import M_SPAN_COUNT, M_SPAN_SUM, delta


def read(ctx: dict, span: str):
    if not ctx["requests"]:
        return None
    if delta(ctx["before"], ctx["after"], M_SPAN_COUNT, span=span) <= 0:
        return None
    inside = delta(ctx["before"], ctx["after"], M_SPAN_SUM, span=span)
    return (ctx["client_wall_s"] - inside) / ctx["requests"] * 1000.0
