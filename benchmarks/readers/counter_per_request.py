"""How far counters of the sidecar's ``/metrics`` moved over the window, per
request, times ``scale``: ``labels`` lists the label selections of ``metric``
whose deltas are summed (none: the whole family).  The ``_sum`` series of a
histogram is such a counter, so a span's mean time per request is read this
way too.  A selection that never moved reads 0.0, not nothing — also on a
program that does not export the family at all: a traced line has to carry
every declared metric (``lastline.py``), and the parent of the PR that adds a
family is measured with these files laid over it."""

from scrape import delta


def read(ctx: dict, metric: str, labels: list = None, scale: float = 1.0):
    if not ctx["requests"]:
        return None
    moved = sum(delta(ctx["before"], ctx["after"], metric, **sel)
                for sel in (labels or [{}]))
    return moved / ctx["requests"] * scale
