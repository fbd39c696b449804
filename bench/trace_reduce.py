"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

The traced sub-window is bounded by the benchmark's own host annotations
(``bench.ingest`` and ``bench.tick``, written with
``jax.profiler.TraceAnnotation``): from the start of the first to the end
of the last. On each device plane (``/device:TPU:<i>``) the events of the
``XLA Ops`` line are the operations that ran; busy time is the union of
their intervals inside the sub-window, so overlapping or nested events
count once. Each idle gap is named by the annotation open on the host at
its midpoint.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Tuple

ANNOTATIONS = ("bench.ingest", "bench.tick")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union(intervals) -> List[Interval]:
    """Merged, sorted intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` covered by the union of ``intervals``."""
    return sum(b - a for a, b in clip(union(intervals), lo, hi))


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, t = [], lo
    for a, b in clip(busy, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def label_at(t: float, marks: List[Tuple[float, float, str]]) -> str:
    for a, b, name in marks:
        if a <= t < b:
            return name
    return "between"


def reduce_events(devices: Dict[str, List[Tuple[str, float, float]]],
                  marks: List[Tuple[float, float, str]],
                  top: int = 10) -> dict:
    """Core reduction over plain data: ``devices`` maps a device name to
    its op events ``(name, start_s, end_s)``; ``marks`` are the host
    annotations ``(start_s, end_s, name)``. Returns the sub-window, busy
    seconds per device, their mean, per-op device seconds (summed over
    devices) and the longest idle gaps with their labels."""
    if not marks:
        raise ValueError("no benchmark annotations in the trace")
    if not devices:
        raise ValueError("no device planes in the trace")
    lo = min(a for a, _, _ in marks)
    hi = max(b for _, b, _ in marks)
    marks = sorted(marks)
    busy, ops, calls, idle = {}, {}, {}, []
    for dev, events in sorted(devices.items()):
        ivs = union((a, b) for _, a, b in events)
        busy[dev] = covered(ivs, lo, hi)
        for name, a, b in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
                calls[name] = calls.get(name, 0) + 1
        for a, b in gaps(ivs, lo, hi):
            idle.append((b - a, label_at(0.5 * (a + b), marks), dev))
    idle.sort(reverse=True)
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "mean_busy_s": sum(busy.values()) / len(busy),
        "ops_s": ops,
        "ops_n": calls,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[f"{name} ({dev})", d] for d, name, dev in idle[:top]],
    }


def read_xplane(trace_dir) -> Tuple[dict, list]:
    """``(devices, marks)`` from the one ``*.xplane.pb`` under
    ``trace_dir``, times in seconds."""
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {len(paths)}")
    pd = ProfileData.from_file(str(paths[0]))
    devices, marks = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices[plane.name] = [
                (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            marks += [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                      for line in plane.lines for e in line.events
                      if e.name in ANNOTATIONS]
    return devices, marks


def reduce_trace(trace_dir, top: int = 10) -> dict:
    return reduce_events(*read_xplane(trace_dir), top=top)
