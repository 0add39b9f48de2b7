"""Reduce a profiler trace to device busy time, time per program and named
idle gaps.

Input: the ``.xplane.pb`` the JAX profiler writes. Device time comes from the
``XLA Modules`` line of each ``/device:TPU:<n>`` plane: one event per
execution of a compiled program, so the union of those intervals is the
time the device was busy. The benchmark's own host spans (``bench.*``
``TraceAnnotation``\\ s) bound the window (``bench.window``) and name each
idle gap by what the host was doing in it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float              # seconds
    end: float


@dataclasses.dataclass
class Trace:
    devices: list             # per device: [Event] program executions
    spans: list               # [Event] benchmark host spans


def program_name(module: str) -> str:
    """``jit_batch_knn(12)`` -> ``batch_knn``: the jitted function's name."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> Trace:
    """Read a trace file, or the newest one under a profiler log dir."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [Event(program_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == MODULE_LINE
                   for e in line.events]
            devices.append(sorted(evs, key=lambda e: e.start))
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, sorted(spans, key=lambda e: e.start))


def _union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged busy intervals of ``events`` clipped to ``[lo, hi]``."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def _span_at(spans, t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    inner = None
    for s in spans:
        if s.start <= t < s.end and s.name != WINDOW_SPAN and (
                inner is None or s.start >= inner.start):
            inner = s
    return inner.name[len(SPAN_PREFIX):] if inner else "outside"


def reduce(trace: Trace, top: int = 10) -> dict:
    """``window_s``, ``busy_s`` (mean over devices), ``programs`` (device
    seconds per program, summed over devices), ``device_ops`` and
    ``idle_gaps`` (the ``top`` largest, ``[name, seconds]``)."""
    if not trace.devices or not any(trace.devices):
        raise ValueError("the trace holds no device program executions")
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        lo = min(d[0].start for d in trace.devices if d)
        hi = max(max(e.end for e in d) for d in trace.devices if d)
    programs: dict[str, float] = {}
    busy, gaps = [], []
    for events in trace.devices:
        for e in events:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                programs[e.name] = programs.get(e.name, 0.0) + (t - s)
        merged = _union(events, lo, hi)
        busy.append(sum(t - s for s, t in merged))
        prev, last = lo, "start"
        ends = {round(e.end, 9): e.name for e in events}
        for s, t in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((f"{_span_at(trace.spans, (prev + s) / 2)} "
                             f"after {last}", s - prev))
            prev = t
            last = ends.get(round(t, 9), last)
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(programs.items(), key=lambda p: -p[1])
    return {"window_s": hi - lo, "busy_s": sum(busy) / len(busy),
            "programs": programs,
            "device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
