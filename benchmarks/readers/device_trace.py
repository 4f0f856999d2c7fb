"""Device busy time per request from the profiler trace of the traced window
(``xplane.py``): nothing in an untraced run."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx.get("trace_requests"):
        return None
    return trace["busy_s"] / ctx["trace_requests"] * 1000.0
