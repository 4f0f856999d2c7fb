"""How far a counter of the sidecar's ``/metrics`` moved over the window.
A family that the sidecar does not export reads nothing."""

from scrape import delta


def read(ctx: dict, metric: str, labels: dict = None):
    labels = labels or {}
    if not any(n == metric for n, _, _ in ctx["after"]):
        return None
    return delta(ctx["before"], ctx["after"], metric, **labels)
