"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* The traced window is the host span ``bench.window`` that the harness
  opens and closes around the measured window.
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane),
  clipped to the window and averaged over the devices.
* ``device_ops``: the operations that took most device time, named
  ``<program>/<operation>`` as the trace names them (the program without
  its fingerprint, the operation without its HLO signature).
* ``idle_gaps``: the longest stretches of the window in which no device
  ran an operation, each labelled by the benchmark's own span that covers
  it and by the other host event that overlaps it most, with its share
  of the gap, where that share is at least 5 %.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
TOP = 10
LABEL_SHARE = 0.05
_DEVICE = re.compile(r"^/device:(?!CUSTOM)[A-Z_]+:\d+$")


def find_trace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def _program_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def reduce_trace(path: Path) -> dict:
    """The device numbers of one trace file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    host_events = []
    window = None
    for plane in host:
        for line in plane.lines:
            thread = line.name.rsplit("/", 1)[0]
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                else:
                    host_events.append((ev.start_ns, ev.end_ns, thread,
                                        ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    w0, w1 = window
    busy_total = 0.0
    op_time: dict[str, float] = {}
    union_all: list[list[float]] = []
    n_devices = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        n_devices += 1
        modules = sorted((ev.start_ns, ev.end_ns, _program_name(ev.name))
                         for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in modules]
        spans = []
        for ev in lines["XLA Ops"].events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            spans.append((s, e))
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            program = modules[i][2] if i >= 0 and \
                ev.start_ns < modules[i][1] else "?"
            name = f"{program}/{_op_name(ev.name)}"
            op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged) / 1e9
        union_all = _union([tuple(x) for x in union_all] +
                           [tuple(x) for x in merged])
    if n_devices == 0:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    gaps, cursor = [], w0
    for s, e in union_all:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        gaps.append((cursor, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    host_events.sort()
    ev_starts = [h[0] for h in host_events]
    idle = []
    for g0, g1 in gaps[:TOP]:
        idle.append([_label(host_events, ev_starts, g0, g1), (g1 - g0) / 1e9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / n_devices,
        "devices": n_devices,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": idle,
    }


def _label(events, starts, g0: float, g1: float) -> str:
    """The benchmark span covering a gap, and the other host event that
    overlaps it most with its share of the gap, if that is 5 % or more;
    work on threads the profiler does not trace (numpy, Python) shows as
    none."""
    span, best, best_overlap = None, None, 0.0
    hi = bisect.bisect_left(starts, g1)
    for s, e, thread, name in events[:hi]:
        overlap = min(e, g1) - max(s, g0)
        if overlap <= 0:
            continue
        if name.startswith("bench."):
            span = name
        elif overlap > best_overlap:
            best, best_overlap = f"{thread}:{name}", overlap
    parts = [span or "outside any bench span"]
    share = best_overlap / (g1 - g0)
    parts.append(f"{best} {100 * share:.0f}%" if share >= LABEL_SHARE
                 else "no traced host event")
    return "; ".join(parts)
