"""``/metrics`` of the sidecar: scrape, parse, sum (Prometheus text format).

Copied from ``chip_smoke.py`` (``parse_metrics``/``scrape``/``metric``/
``serving_tiers``), which stays as it is.
"""

from __future__ import annotations

import re
import urllib.request

M_BACKEND_COUNT = "karpenter_solver_backend_duration_seconds_count"
M_COLD_FALLBACKS = "karpenter_solver_cold_start_fallbacks_total"
M_COMPILING = "karpenter_solver_compile_in_progress"
M_COMPILES = "karpenter_solver_compile_duration_seconds_count"
M_SPAN_SUM = "karpenter_trace_span_duration_seconds_sum"
M_SPAN_COUNT = "karpenter_trace_span_duration_seconds_count"

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list:
    """[(name, {label: value}, float)] for every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if m is None:
            raise ValueError(f"unparseable /metrics line: {line!r}")
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                    float(m.group(3))))
    return out


def scrape(url: str) -> list:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return parse_metrics(resp.read().decode())


def metric(samples: list, name: str, **labels: str) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``; a
    family that is absent reads 0 (counters are zero-initialised)."""
    return sum(v for n, lab, v in samples if n == name
               and all(lab.get(k) == want for k, want in labels.items()))


def delta(before: list, after: list, name: str, **labels: str) -> float:
    return metric(after, name, **labels) - metric(before, name, **labels)


def serving_tiers(before: list, after: list) -> dict:
    """{tier: solves} the sidecar served between two scrapes."""
    out = {}
    for tier in ("tpu", "native", "oracle"):
        d = delta(before, after, M_BACKEND_COUNT, backend=tier)
        if d:
            out[tier] = int(d)
    return out
