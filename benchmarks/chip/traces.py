"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second can be tested on a small recorded
trace:

* :func:`load` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData``
  into plain lists: per device plane, its program executions (line
  ``XLA Modules``) and its operations (line ``XLA Ops``); every host
  event; and the traced window, the span of the ``bench.traced_window``
  annotation.
* :func:`reduce` turns those lists into the device's busy time in the
  window, each program's executions and device time, the operations that
  took most time, and the longest idle gaps named by the host event that
  overlaps them most.

Times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import collections
import re

WINDOW_ANNOTATION = "bench.traced_window"
TOP = 10


def _short_program(name: str) -> str:
    """``jit_dense_wave(1305...)`` -> ``dense_wave``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _short_op(name: str) -> str:
    """``%fusion.316 = f32[465750]{0} fusion(...), ...`` ->
    ``fusion.316 fusion f32[465750]`` (layouts dropped)."""
    m = re.match(r"%?(\S+) = (.*?) ([a-z][\w\-]*)\(", name)
    if not m:
        return name[:100]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:100]


def load(path: str) -> dict:
    """Plain lists of the events the reduction needs."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices.append({
                "plane": plane.name,
                "modules": [[e.name, e.start_ns, e.duration_ns]
                            for e in lines["XLA Modules"].events]
                if "XLA Modules" in lines else [],
                "ops": [[e.name, e.start_ns, e.duration_ns]
                        for e in lines["XLA Ops"].events],
            })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_ANNOTATION:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    elif e.duration_ns > 0:
                        host.append([e.name, e.start_ns, e.duration_ns])
    if window is None:
        raise ValueError(f"trace {path} has no {WINDOW_ANNOTATION!r} span")
    return {"devices": devices, "host": host, "window": window}


def merge(intervals, lo: float, hi: float) -> list:
    """Sorted, disjoint [start, end] intervals, clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _gaps(merged, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _name_gap(gap, host_sorted, starts) -> str:
    """The host event that overlaps the gap most.  Events more than ten
    times longer than the gap (waits that span many frames) name nothing."""
    g0, g1 = gap
    span = g1 - g0
    best, best_overlap = "no host event", 0.0
    i0 = bisect.bisect_left(starts, g0 - 10 * span)
    i1 = bisect.bisect_right(starts, g1)
    for name, s, d in host_sorted[i0:i1]:
        overlap = min(g1, s + d) - max(g0, s)
        if d <= 10 * span and overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(trace: dict) -> dict:
    """Busy time, program time and the breakdown over the traced window.

    Returns ``devices``: how many device planes had operations;
    ``window_s``; ``busy_s`` averaged over the device planes;
    ``programs``: short name -> {``count``, ``device_s``} over executions
    that lie wholly inside the window, ``device_s`` being the union of the
    operations inside each execution; ``device_ops`` and ``idle_gaps``:
    the ten largest, as ``[name, seconds]``.
    """
    lo, hi = trace["window"]
    window_s = (hi - lo) * 1e-9
    busy, programs = [], collections.defaultdict(lambda: {"count": 0, "device_s": 0.0})
    op_time: dict = collections.defaultdict(float)
    all_gaps = []
    host_sorted = sorted(trace["host"], key=lambda e: e[1])
    starts = [e[1] for e in host_sorted]
    for dev in trace["devices"]:
        ops = sorted(dev["ops"], key=lambda e: e[1])
        merged = merge(((s, s + d) for _, s, d in ops), lo, hi)
        busy.append(_length(merged) * 1e-9)
        all_gaps.extend(_gaps(merged, lo, hi))
        op_starts = [s for _, s, _ in ops]
        for name, s, d in dev["modules"]:
            if s < lo or s + d > hi:
                continue
            i0 = bisect.bisect_left(op_starts, s)
            i1 = bisect.bisect_right(op_starts, s + d)
            inside = merge(((os_, os_ + od) for _, os_, od in ops[i0:i1]), s, s + d)
            prog = programs[_short_program(name)]
            prog["count"] += 1
            prog["device_s"] += _length(inside) * 1e-9
            for op_name, os_, od in ops[i0:i1]:
                op_time[f"{_short_program(name)}:{_short_op(op_name)}"] += od * 1e-9
    n_dev = max(1, len(trace["devices"]))
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "devices": len(trace["devices"]),
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "programs": dict(programs),
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[_name_gap(g, host_sorted, starts), (g[1] - g[0]) * 1e-9]
                      for g in top_gaps],
    }
