"""Reduce a profiler trace (``*.xplane.pb``) to device busy time.

``busy_s`` is the union of the intervals in which an operation ran on a
device plane, averaged over the device planes used; ``device_ops`` the
operations that took most time (operations nest on the line — a ``while``
holds its body's — so their sums overlap; the union does not); ``idle_gaps`` the longest gaps between busy
intervals, each named by the host-plane event that overlaps it most (the
program writes no TraceAnnotations yet, so most read ``host:unattributed``).

On a TPU the device planes are ``/device:TPU:<n>`` and the operations sit on
their ``XLA Ops`` line.  The CPU has no device plane: the rehearsal passes the
host plane and the XLA client's thread lines instead, which proves the
arithmetic, not a device number.

A trace with no device events is an ERROR (:class:`NoDeviceEvents`), never a
busy time of 0: the served path's device is busy well under 1 % of a window,
and "nothing found" must not read as "nothing ran".
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

TPU_PLANE = "/device:TPU:"
TPU_OP_LINES = ("XLA Ops",)
CPU_PLANE = "/host:CPU"
CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")


class NoDeviceEvents(RuntimeError):
    pass


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise NoDeviceEvents(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_planes(planes: list, plane_prefix: str, op_lines: tuple) -> dict:
    """``planes`` is ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]`` — plain data, so the arithmetic is testable without a
    trace file."""
    busy_ns: List[float] = []
    ops: Dict[str, float] = {}
    merged_all: List[Tuple[float, float]] = []
    host: List[Tuple[float, float, str]] = []
    seen = []
    for pname, lines in planes:
        is_dev = pname.startswith(plane_prefix)
        ivs = []
        for lname, events in lines:
            op_line = is_dev and any(lname.startswith(p) for p in op_lines)
            if is_dev:
                seen.append(f"{pname}|{lname}|{len(events)}")
            for name, start, dur in events:
                if dur <= 0:
                    continue
                if op_line:
                    ivs.append((start, start + dur))
                    # an HLO event's name is its whole text: keep the result
                    short = name.split(" = ")[0][:120]
                    ops[short] = ops.get(short, 0.0) + dur
                elif not is_dev or plane_prefix == CPU_PLANE:
                    host.append((start, start + dur, name))
        if ivs:
            merged = union(ivs)
            busy_ns.append(sum(e - s for s, e in merged))
            merged_all.extend(merged)
    if not busy_ns:
        raise NoDeviceEvents(
            f"no operation with a duration on a plane {plane_prefix!r} "
            f"line {op_lines!r}; planes seen: "
            f"{[p for p, _ in planes]}; device lines: {seen[:12]}")
    merged_all = union(merged_all)
    gaps = [(merged_all[i + 1][0] - merged_all[i][1], merged_all[i][1],
             merged_all[i + 1][0]) for i in range(len(merged_all) - 1)]
    gaps.sort(reverse=True)
    named: Dict[str, float] = {}
    for length, gs, ge in gaps[:50]:
        best, best_ov = "host:unattributed", 0.0
        for hs, he, hname in host:
            ov = min(he, ge) - max(hs, gs)
            # a host event names a gap only if it covers most of it and is
            # not a far longer span that merely contains it
            if ov > best_ov and ov >= 0.5 * length and (he - hs) < 4 * length:
                best, best_ov = f"host:{hname}", ov
        named[best] = named.get(best, 0.0) + length

    def top(d: Dict[str, float]) -> list:
        return [[k[:120], v / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "device_planes": len(busy_ns),
        "span_s": (merged_all[-1][1] - merged_all[0][0]) / 1e9,
        "device_lines": seen[:12],
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(named)},
    }


def read_planes(path: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [(pl.name, [(ln.name, [(e.name, float(e.start_ns),
                                   float(e.duration_ns)) for e in ln.events])
                       for ln in pl.lines]) for pl in data.planes]


def reduce_trace(trace_dir: str, platform: str = "tpu",
                 planes: Optional[list] = None) -> dict:
    """The reduction of the newest trace under ``trace_dir``."""
    if planes is None:
        planes = read_planes(find_trace(trace_dir))
    if platform == "tpu":
        return reduce_planes(planes, TPU_PLANE, TPU_OP_LINES)
    if platform == "cpu":
        return reduce_planes(planes, CPU_PLANE, CPU_OP_LINES)
    raise NoDeviceEvents(f"no plane names known for platform {platform!r}")
