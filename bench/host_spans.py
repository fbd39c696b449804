"""The program's own spans, per window tick, and the device idle time
they explain.

The program's obs spans (``repro.obs.trace``) are kept in its span ring,
which the readers see as ``run.spans``; while a profiler trace records,
each is also written into the trace's host plane as a
``TraceAnnotation`` of the same name, on the device timeline's clock.
There they are told from the runtime's own host events by their names:
lower-case dotted words (``castor.tick``, ``exec.bin``,
``store.read_many``), the form of every obs span and of the benchmark's
own annotations.

``attribute`` takes each device idle interval inside the benchmark's
``bench.tick`` annotations and splits it by the innermost program span
open on the host at each instant. Idle time whose innermost span is
``castor.tick`` or ``bench.tick`` is host work that no layer claims: it
is reported as ``unattributed_s``.

    python3 bench/host_spans.py .bench_out/trace/<cell>.<seed>

prints the per-span idle table of a recorded trace as JSON.
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from trace_reduce import gaps, read_xplane, union

TICK = "bench.tick"
ROOT_SPAN = "castor.tick"
UNCLAIMED = (ROOT_SPAN, TICK)
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

Span = Tuple[float, float, str]


def ms_per_tick(run, name: str) -> Optional[float]:
    """The window's ``name`` spans per window tick, in ms; None where the
    program opened none."""
    spans = [s for s in run.spans if s.name == name]
    if not spans or not run.ticks:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(run.ticks)


def innermost(spans: List[Span]) -> List[Span]:
    """Disjoint, sorted pieces ``(start, end, name)`` giving, for every
    instant some span covers, the innermost one. ``spans`` come from one
    thread, so they nest; a child that outlasts its parent is cut at the
    parent's end."""
    out: List[Span] = []
    stack: List[Tuple[float, str]] = []         # (end, name), open spans
    t = 0.0
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, top = stack.pop()
            out.append((t, end, top))
            t = end
        if stack:
            out.append((t, a, stack[-1][1]))
            b = min(b, stack[-1][0])
        stack.append((b, name))
        t = a
    while stack:
        end, top = stack.pop()
        out.append((t, end, top))
        t = end
    return [p for p in out if p[1] > p[0]]


def _overlaps(idle, pieces):
    """``(name, seconds)`` for each overlap of two sorted, disjoint
    interval lists."""
    i = j = 0
    while i < len(idle) and j < len(pieces):
        a = max(idle[i][0], pieces[j][0])
        b = min(idle[i][1], pieces[j][1])
        if b > a:
            yield pieces[j][2], b - a
        if idle[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1


def attribute(devices: Dict[str, list], spans: List[Span]) -> Optional[dict]:
    """Device idle time inside ``bench.tick`` by innermost host span,
    averaged over the devices. ``devices`` maps a device to its op events
    ``(name, start_s, end_s)``; ``spans`` are one thread's program spans.
    None where the trace holds no tick or no span of the program."""
    ticks = union((a, b) for a, b, n in spans if n == TICK)
    if not ticks or not devices \
            or not any(n == ROOT_SPAN for _, _, n in spans):
        return None
    pieces = innermost(spans)
    by_span: Dict[str, float] = {}
    idle_s = 0.0
    for events in devices.values():
        busy = union((a, b) for _, a, b in events)
        idle = [g for lo, hi in ticks for g in gaps(busy, lo, hi)]
        idle_s += sum(b - a for a, b in idle)
        for name, d in _overlaps(idle, pieces):
            by_span[name] = by_span.get(name, 0.0) + d
    n = len(devices)
    by_span = {k: v / n for k, v in sorted(by_span.items(),
                                            key=lambda kv: -kv[1])}
    return {"ticks": sum(1 for _, _, nm in spans if nm == TICK),
            "idle_s": idle_s / n, "by_span": by_span,
            "unattributed_s": sum(by_span.get(k, 0.0) for k in UNCLAIMED)}


def read_spans(trace_dir) -> List[Span]:
    """The program spans of the host thread that ran the ticks, in
    seconds, from the one ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                              for e in line.events
                              if PROGRAM_SPAN.match(e.name)])
    return max(lines, key=lambda ev: sum(n == TICK for _, _, n in ev),
               default=[])


def read_trace(trace_dir) -> Optional[dict]:
    devices, _ = read_xplane(trace_dir)
    return attribute(devices, read_spans(trace_dir))


def trace_dir(run) -> Path:
    """Where ``execute`` in ``run.py`` has the profiler write a run's
    trace."""
    return (Path(run.cell["bench"]).parent / ".bench_out" / "trace"
            / f"{run.cell['name']}.{run.seed}")


def for_run(run) -> Optional[dict]:
    """``attribute`` over a traced run's trace; None without one."""
    if not getattr(run, "trace", None):
        return None
    d = trace_dir(run)
    if not d.is_dir() or not any(d.rglob("*.xplane.pb")):
        return None
    return read_trace(d)


if __name__ == "__main__":
    print(json.dumps(read_trace(sys.argv[1]), indent=1))
