"""The contract of a run's last line, as a check the harness runs on itself.

``violations(line, workload, trace, bench)`` returns what is wrong with the
last line of standard output of ``--workload <workload> --trace <trace>``
against ``BENCHMARK.json`` (``bench``): an empty list means the driver can
read it.  ``run.py`` calls it before printing and refuses to print a line it
rejects.

What the driver reads (the builder's contract): one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``, and with
``--trace 1`` optionally ``breakdown``.  ``metrics`` gives each metric as
``{"value": number, "unit": str}``: with ``--trace 0`` every end-to-end metric
of the cell, with ``--trace 1`` every per-layer metric of the cell (a metric
with a ``workloads`` key belongs to the cells it lists, one without to every
cell).  This harness prints both families in both modes where it can read
them, so a metric that is present must be one the file declares for the cell,
with the declared unit.  ``device`` gives ``platform``, ``kind``, ``count``,
``memory_peak_bytes`` and, traced, ``window_s`` and ``busy_s`` with
``0 < busy_s <= window_s``.
"""

from __future__ import annotations

import json
import math
from typing import List

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
#: the key under which the numbers compared stand beside their limits; it
#: comes last in the line
COMPARED = "compared"


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def violations(line: str, workload: str, trace: int, bench: dict) -> List[str]:
    out: List[str] = []
    if "\n" in line.strip():
        return ["the last line is more than one line"]
    try:
        doc = json.loads(line)
    except ValueError as err:
        return [f"not JSON: {err}"]
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        return [f"workload {workload!r} is not in BENCHMARK.json"]
    for key in REQUIRED:
        if key not in doc:
            out.append(f"key {key!r} is missing")
    if out:
        return out
    if not isinstance(doc["correct"], bool):
        out.append("correct is not true/false")
    for key in ("attempted", "failed"):
        if not isinstance(doc[key], int) or isinstance(doc[key], bool) \
                or doc[key] < 0:
            out.append(f"{key} is not a whole number >= 0")
    if not out and doc["attempted"] < 1:
        out.append("attempted is 0")
    if not out and doc["failed"] > doc["attempted"]:
        out.append("failed is more than attempted")

    metrics = doc["metrics"]
    if not isinstance(metrics, dict):
        return out + ["metrics is not an object"]
    e2e = {m["name"]: m for m in bench["end_to_end"] if _applies(m, workload)}
    layer = {m["name"]: m for m in bench["per_layer"]
             if _applies(m, workload)}
    must = layer if trace else e2e
    for name in must:
        if name not in metrics:
            out.append(f"metrics.{name} is missing (--trace {trace})")
    for name, got in metrics.items():
        decl = e2e.get(name) or layer.get(name)
        if decl is None:
            out.append(f"metrics.{name} is not declared for {workload}")
            continue
        if not isinstance(got, dict) or "value" not in got \
                or "unit" not in got:
            out.append(f"metrics.{name} is not {{value, unit}}")
            continue
        if not _number(got["value"]):
            out.append(f"metrics.{name}.value is not a finite number")
        elif name in e2e and got["value"] <= 0:
            out.append(f"metrics.{name}.value is {got['value']}: an "
                       "end-to-end metric is never 0")
        elif got["value"] < 0:
            out.append(f"metrics.{name}.value is negative")
        elif (name.endswith("_roofline") or "mfu" in name.split("_")
              or "mfu" in name.split(".")) and got["value"] > 105:
            out.append(f"metrics.{name}.value is over 105 %")
        if got["unit"] != decl["unit"]:
            out.append(f"metrics.{name}.unit is {got['unit']!r}, declared "
                       f"{decl['unit']!r}")

    dev = doc["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for key in ("platform", "kind"):
        if not isinstance(dev.get(key), str) or not dev.get(key):
            out.append(f"device.{key} is missing")
    if not isinstance(dev.get("count"), int) or isinstance(
            dev.get("count"), bool) or dev.get("count", 0) < cell["chips"]:
        out.append(f"device.count is {dev.get('count')!r}, the cell needs "
                   f"{cell['chips']}")
    peak = dev.get("memory_peak_bytes")
    # a CPU (the rehearsal's platform) reports no memory statistics
    if not isinstance(peak, int) or isinstance(peak, bool) or peak < 0 or (
            peak == 0 and dev.get("platform") == "tpu"):
        out.append(f"device.memory_peak_bytes is {peak!r}")
    if trace:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _number(window) or window <= 0:
            out.append(f"device.window_s is {window!r}")
        if not _number(busy) or busy <= 0:
            out.append(f"device.busy_s is {busy!r}: it has to be above 0")
        elif _number(window) and busy > window:
            out.append(f"device.busy_s {busy} is over window_s {window}")
    if "breakdown" in doc:
        if not trace:
            out.append("breakdown on an untraced line")
        bd = doc["breakdown"]
        for key in ("device_ops", "idle_gaps"):
            rows = bd.get(key) if isinstance(bd, dict) else None
            if not isinstance(rows, list) or len(rows) > 10 or not all(
                    isinstance(r, list) and len(r) == 2
                    and isinstance(r[0], str) and _number(r[1])
                    for r in rows):
                out.append(f"breakdown.{key} is not at most 10 "
                           "[name, seconds] pairs")
    if COMPARED in doc and list(doc)[-1] != COMPARED:
        out.append(f"{COMPARED!r} is not the last key")
    if COMPARED not in doc:
        out.append(f"key {COMPARED!r} (numbers compared, each beside its "
                   "limit) is missing")
    return out
