"""Reduction of a profiler trace (``.xplane.pb``) to intervals.

Reads the file with ``jax.profiler.ProfileData``.  The device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run and their ``XLA Modules`` line one per program run.  The benchmark
marks the traced part of its window with a host span ``bench.window``,
and its calls into the scheduler with ``bench.admit`` and ``bench.step``;
all are read on the profiler's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds on the profiler's clock
    end: float


@dataclasses.dataclass
class Trace:
    window: tuple[float, float] | None
    host: list[Event]                  # bench.* spans
    ops: dict[str, list[Event]]        # device plane -> operations
    modules: dict[str, list[Event]]    # device plane -> program runs

    @property
    def window_s(self) -> float | None:
        return None if self.window is None else self.window[1] - self.window[0]

    def clip(self, events: list[Event]) -> list[Event]:
        """Events inside the window, cut at its edges."""
        lo, hi = self.window
        return [Event(e.name, max(e.start, lo), min(e.end, hi))
                for e in events if e.end > lo and e.start < hi]


def op_name(name: str) -> str:
    """An XLA op's name without its HLO text and its instance number:
    "%multi_mass.13 = f32[8,1,128] custom-call(...)" -> "multi_mass"."""
    return name.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]


def find(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: list[Event] = []
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules)
                dest[plane.name] = [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            elif not device:
                host.extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    windows = [e for e in host if e.name == "bench.window"]
    window = (windows[0].start, windows[0].end) if windows else None
    return Trace(window=window, host=host, ops=ops, modules=modules)


def union_s(events: list[Event]) -> float:
    """Seconds covered by at least one event."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> float | None:
    """Device-busy seconds in the window, averaged over the device planes."""
    if trace.window is None or not trace.ops:
        return None
    per = [union_s(trace.clip(evs)) for evs in trace.ops.values()]
    return sum(per) / len(per)


def idle_gaps(trace: Trace, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` longest stretches of the window with no device operation
    running (first device plane), each named by the host span it falls in
    (``host`` when the benchmark was in none: waiting for arrivals or in
    its own loop)."""
    if trace.window is None or not trace.ops:
        return []
    evs = sorted(trace.clip(next(iter(trace.ops.values()))),
                 key=lambda e: e.start)
    gaps, t = [], trace.window[0]
    for e in evs:
        if e.start > t:
            gaps.append((t, e.start))
        t = max(t, e.end)
    if trace.window[1] > t:
        gaps.append((t, trace.window[1]))
    spans = [h for h in trace.host if h.name != "bench.window"]
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (lo + hi) / 2
        where = [h.name[len(HOST_SPAN_PREFIX):] for h in spans
                 if h.start <= mid <= h.end]
        out.append((where[0] if where else "host", hi - lo))
    return out


def top_ops(trace: Trace, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` device operations that took most time in the window
    (first device plane), summed by HLO instruction name.  Operations
    nest (a layer loop holds its layers' fusions), so the list may name
    a loop and its contents both."""
    if trace.window is None or not trace.ops:
        return []
    tot: dict[str, float] = {}
    for e in trace.clip(next(iter(trace.ops.values()))):
        name = e.name.split(" = ", 1)[0]
        tot[name] = tot.get(name, 0.0) + (e.end - e.start)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
