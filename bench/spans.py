"""The program's own host spans in a profiler trace, and what they say
about the device's idle time and its launches.

``repro.serving`` marks each phase of serving with a ``jax.profiler``
span named ``serve.*``: ``serve.admit`` (metadata ``rid``, ``length``)
with ``.prefill``, ``.sample`` and ``.book``; ``serve.step`` (metadata
``live``) with ``.prepare``, ``.launch``, ``.readback`` and ``.commit``;
``serve.queue`` and ``serve.drain`` in the server.  They lie on the
profiler's one clock with the device planes.  ``load`` reads them and
the host's launch events; the functions below reduce them against a
``devtrace.Trace`` of the same file, inside its ``bench.window``.

``devtrace.load`` keeps the benchmark's own ``bench.*`` spans alone, so
no metric reader sees these; ``spanreport.py`` prints them for a trace
kept with ``run.py --keep-trace``.
"""
from __future__ import annotations

import bisect
import dataclasses

from devtrace import DEVICE_PLANE, Trace
from measures import quantile

SPAN_PREFIX = "serve."
LAUNCH = "PjitFunction("       # host event of one call into a jitted program
STEP_PROGRAMS = ("_scheduler_step", "_scheduler_horizon")
ADMIT_PROGRAMS = ("_admit_slot", "_admit_paged")
OUTSIDE = "none"


@dataclasses.dataclass
class Span:
    name: str
    start: float          # seconds on the profiler's clock
    end: float
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Program:
    spans: list[Span]     # serve.* spans, by start
    launches: list[Span]  # outermost host launch events, by start


def load(path: str) -> Program:
    """The ``serve.*`` spans, with their metadata, and the host's launch
    events (``PjitFunction(<program>)``; a call's nested repeats of its
    own event are dropped) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    spans: list[Span] = []
    launches: list[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            line_launches = []
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      dict(e.stats)))
                elif e.name.startswith(LAUNCH):
                    line_launches.append(Span(
                        e.name[len(LAUNCH):-1], e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
            launches.extend(_outermost(line_launches))
    return Program(spans=sorted(spans, key=lambda s: (s.start, -s.end)),
                   launches=sorted(launches, key=lambda s: s.start))


def _outermost(events: list[Span]) -> list[Span]:
    out, end = [], None
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        if end is None or e.start >= end:
            out.append(e)
            end = e.end
    return out


def in_window(trace: Trace, spans: list[Span], name: str) -> list[Span]:
    """The spans named ``name`` that lie wholly inside the window."""
    lo, hi = trace.window
    return [s for s in spans
            if s.name == name and s.start >= lo and s.end <= hi]


def busy(trace: Trace) -> list[tuple[float, float]]:
    """Disjoint intervals, by start, in which some operation ran on the
    first device plane, cut at the window's edges."""
    out: list[list[float]] = []
    for e in sorted(trace.clip(next(iter(trace.ops.values()))),
                    key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def idle(trace: Trace) -> list[tuple[float, float]]:
    """The window's stretches with no device operation running."""
    out, t = [], trace.window[0]
    for a, b in busy(trace):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if trace.window[1] > t:
        out.append((t, trace.window[1]))
    return out


def _covered(intervals, starts, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` covered by disjoint ``intervals`` (sorted;
    ``starts`` their starts)."""
    total = 0.0
    for a, b in intervals[max(0, bisect.bisect_right(starts, lo) - 1):
                          bisect.bisect_left(starts, hi)]:
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def segments(spans: list[Span]) -> list[tuple[float, float, str]]:
    """The time the (nested) spans cover, cut into pieces, each named by
    the innermost span over it."""
    out: list[tuple[float, float, str]] = []
    stack: list[Span] = []
    t = None
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            out.append((t, top.end, top.name))
            t = top.end
        if stack:
            out.append((t, s.start, stack[-1].name))
        stack.append(s)
        t = s.start
    while stack:
        top = stack.pop()
        out.append((t, top.end, top.name))
        t = top.end
    return [seg for seg in out if seg[1] > seg[0]]


def innermost(spans: list[Span]):
    """A function from a time to the name of the innermost span over it
    (``none`` for a time outside every span)."""
    segs = segments(spans)
    starts = [a for a, _, _ in segs]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return segs[i][2] if i >= 0 and t < segs[i][1] else OUTSIDE
    return at


def idle_by_span(trace: Trace, program: Program) -> dict[str, float] | None:
    """Device-idle seconds of the window, summed by the innermost
    ``serve.*`` span over each idle stretch (a stretch that crosses span
    edges is split there); ``none`` for idle time outside every span."""
    if trace.window is None or not trace.ops or not program.spans:
        return None
    segs = segments(program.spans)
    starts = [a for a, _, _ in segs]
    out: dict[str, float] = {}
    for lo, hi in idle(trace):
        inside = 0.0
        for a, b, name in segs[max(0, bisect.bisect_right(starts, lo) - 1):
                               bisect.bisect_left(starts, hi)]:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                inside += d
        if hi - lo > inside:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (hi - lo - inside)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def longest_idle(trace: Trace, program: Program,
                 n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` longest idle stretches, each named by the innermost
    ``serve.*`` span over its midpoint."""
    if trace.window is None or not trace.ops:
        return []
    at = innermost(program.spans)
    return [(at((lo + hi) / 2), hi - lo)
            for lo, hi in sorted(idle(trace), key=lambda g: g[0] - g[1])[:n]]


def _median_ms(values: list[float]) -> float | None:
    q = quantile(values, 0.5)
    return None if q is None else q * 1e3


def _child(spans: list[Span], starts: list[float], parent: Span):
    i = bisect.bisect_left(starts, parent.start)
    if i < len(spans) and spans[i].end <= parent.end:
        return spans[i]
    return None


def _step_pairs(trace: Trace, program: Program):
    """(first's launch, first's readback, next's launch) for consecutive
    ``serve.step`` spans of the window with no ``serve.admit`` between."""
    steps = in_window(trace, program.spans, "serve.step")
    reads = in_window(trace, program.spans, "serve.step.readback")
    launches = in_window(trace, program.spans, "serve.step.launch")
    admits = [s.start for s in
              in_window(trace, program.spans, "serve.admit")]
    r_starts = [s.start for s in reads]
    l_starts = [s.start for s in launches]
    for a, b in zip(steps, steps[1:]):
        i = bisect.bisect_left(admits, a.end)
        if i < len(admits) and admits[i] < b.start:
            continue                     # an admission ran between them
        parts = (_child(launches, l_starts, a), _child(reads, r_starts, a),
                 _child(launches, l_starts, b))
        if None not in parts:
            yield parts


def step_gap_ms_p50(trace: Trace, program: Program) -> float | None:
    """Median host time between two decode steps, in ms: for consecutive
    ``serve.step`` spans of the window with no ``serve.admit`` between
    them, from the end of the first's ``serve.step.readback`` (its tokens
    are on the host) to the start of the next's ``serve.step.launch``.
    The device waits at least this long between the two programs."""
    if trace.window is None or not program.spans:
        return None
    return _median_ms([nxt.start - read.end
                       for _, read, nxt in _step_pairs(trace, program)])


def step_gap_parts(trace: Trace, program: Program) -> dict | None:
    """The device's idle time between two decode steps cut at the host's
    span edges, medians in ms over the pairs ``step_gap_ms_p50`` reads,
    each step matched to the first step program the device started after
    its ``serve.step.launch`` began: ``tail``, the first program's end to
    the end of its read-back; ``host``, ``step_gap_ms_p50``'s gap;
    ``head``, the next launch's start to its program's start; ``device``,
    the whole idle time between the programs.  ``ordered`` is the share of
    pairs in which each program ends before its read-back ends and starts
    after its launch began, as one clock shared by host and device gives."""
    if trace.window is None or not trace.modules or not program.spans:
        return None
    runs = sorted((e for e in trace.clip(next(iter(trace.modules.values())))
                   if any(p in e.name for p in STEP_PROGRAMS)),
                  key=lambda e: e.start)
    starts = [e.start for e in runs]

    def run_of(launch: Span):
        i = bisect.bisect_left(starts, launch.start)
        return runs[i] if i < len(runs) else None

    parts: dict[str, list[float]] = {"tail": [], "host": [], "head": [],
                                     "device": []}
    ordered = 0
    for launch, read, nxt in _step_pairs(trace, program):
        a, b = run_of(launch), run_of(nxt)
        if a is None or b is None or a is b:
            continue
        parts["tail"].append(read.end - a.end)
        parts["host"].append(nxt.start - read.end)
        parts["head"].append(b.start - nxt.start)
        parts["device"].append(b.start - a.end)
        ordered += a.end <= read.end and b.start >= nxt.start
    if not parts["device"]:
        return None
    out = {k: _median_ms(v) for k, v in parts.items()}
    out["ordered"] = ordered / len(parts["device"])
    return out


def device_step_gap_ms_p50(trace: Trace) -> float | None:
    """The same gap on the device alone, in ms: the median idle time
    between consecutive decode-step programs with no admission program
    between them (first device plane, the window)."""
    if trace.window is None or not trace.modules:
        return None
    runs = sorted(trace.clip(next(iter(trace.modules.values()))),
                  key=lambda e: e.start)
    gaps, prev = [], None
    for e in runs:
        if any(p in e.name for p in ADMIT_PROGRAMS):
            prev = None
        elif any(p in e.name for p in STEP_PROGRAMS):
            if prev is not None:
                gaps.append(e.start - prev.end)
            prev = e
    return _median_ms(gaps)


def launches_per_step(trace: Trace, program: Program) -> float | None:
    """Program runs on the first device plane in the window, over the
    ``serve.step.launch`` spans in it.  Every program counts, so the
    admissions' prefills, samples and slot writes are spread over the
    steps; a step with nothing but its own program reads 1."""
    if trace.window is None or not trace.modules or not program.spans:
        return None
    n_steps = len(in_window(trace, program.spans, "serve.step.launch"))
    runs = trace.clip(next(iter(trace.modules.values())))
    if not n_steps or not runs:
        return None
    return len(runs) / n_steps


def admit_idle_ms_p50(trace: Trace, program: Program) -> float | None:
    """Median device-idle time inside one admission, in ms: for each
    ``serve.admit`` span in the window, its length less the time some
    device operation ran inside it."""
    if trace.window is None or not trace.ops or not program.spans:
        return None
    spans = in_window(trace, program.spans, "serve.admit")
    intervals = busy(trace)
    starts = [a for a, _ in intervals]
    return _median_ms([(s.end - s.start)
                       - _covered(intervals, starts, s.start, s.end)
                       for s in spans])


def launches_by_span(trace: Trace, program: Program) -> dict | None:
    """Host launch events in the window by the innermost ``serve.*`` span
    they start in, then by program: {span: {program: count}}."""
    if trace.window is None or not program.spans:
        return None
    lo, hi = trace.window
    at = innermost(program.spans)
    out: dict[str, dict[str, int]] = {}
    for e in program.launches:
        if lo <= e.start < hi:
            per = out.setdefault(at(e.start), {})
            per[e.name] = per.get(e.name, 0) + 1
    return out
