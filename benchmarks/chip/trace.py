"""Reduction of a profiler trace to device busy time, idle share, the
device operations that took most time, and the longest idle gaps with the
host span each fell in.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events on the ``XLA Ops`` line of each ``/device:TPU``
plane; host spans are the benchmark's own ``TraceAnnotation`` events, whose
names start with ``bench.``.  The traced window runs from the first host
span's start to the last one's end.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(event: str) -> str:
    """An XLA op's name (``%while.54``) without the HLO text after it."""
    return event.split(" = ", 1)[0]


def load(logdir: str):
    """(ops by device, host spans) of the newest trace under ``logdir``;
    each op and span is ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}, []
    data = ProfileData.from_file(files[-1])
    ops: dict[str, list] = {}
    spans = []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                ops.setdefault(plane.name, []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not plane.name.startswith(DEVICE_PLANE):
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return ops, spans


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _span_at(spans, t: float) -> str:
    """Innermost host span covering time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "none"


def reduce(ops: dict, spans: list, top: int = 10) -> dict | None:
    """busy_s (mean over devices), window_s, idle_share, device_ops and
    idle_gaps; None where the trace holds no device operation or no span."""
    if not spans or not any(ops.values()):
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = (hi - lo) * 1e-9
    busy = []
    gaps = []
    by_name: dict[str, float] = {}
    for dev_ops in ops.values():
        merged = union([(s, e) for _, s, e in dev_ops], lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, (s + e) / 2), (e - s) * 1e-9))
        for name, s, e in dev_ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_name[name] = by_name.get(name, 0.0) + d * 1e-9
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        return None
    n = len(ops)
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_share": 1.0 - busy_s / window,
        "device_ops": [[k, v / n] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])
                      [:top]],
    }
