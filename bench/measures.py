"""What a finished run hands to the metric readers (``metrics/<name>.py``),
and the arithmetic they share.  Times are seconds after the window
opened; the window is ``[0, seconds)``."""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os

from model import Dims

METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "metrics")


@dataclasses.dataclass
class Run:
    cell: str
    seconds: float
    setup_s: float
    dims: Dims
    serve: dict
    traffic: dict
    records: list            # serve.Record
    steps: list              # serve.Step
    admits: list             # serve.Admit
    peaks: dict | None       # peaks.json entry of this device, None off-chip
    trace: object = None     # trace.Trace of the traced run
    trace_host: tuple | None = None   # (on, off) of the trace, host clock
    stall: tuple | None = None        # host interval the profiler's start took


def quantile(values, q: float) -> float | None:
    """The q-quantile with linear interpolation between order statistics
    (numpy's default); None for no values."""
    v = sorted(values)
    if not v:
        return None
    x = q * (len(v) - 1)
    lo = math.floor(x)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


def arrived(run: Run) -> list:
    return [r for r in run.records if 0.0 <= r.arrival < run.seconds]


def ttfts(run: Run) -> list[float]:
    """Time to first token from the scheduled arrival, for every request
    due in the window; one with no first token by the window's end
    counts at its wait so far."""
    out = []
    for r in arrived(run):
        t = r.token_times[0] if r.token_times else None
        out.append((t if t is not None and t <= run.seconds
                    else run.seconds) - r.arrival)
    return out


def stalled(run: Run, a: float, b: float) -> float:
    """Seconds of ``[a, b]`` in which the profiler was starting (0 in an
    untraced run)."""
    if run.stall is None:
        return 0.0
    return max(0.0, min(b, run.stall[1]) - max(a, run.stall[0]))


def queue_waits(run: Run) -> list[float]:
    """Scheduled arrival to the start of its admission (or to the
    window's end, for one still waiting)."""
    out = []
    for r in arrived(run):
        t = r.admit_start
        out.append((t if t is not None and t <= run.seconds
                    else run.seconds) - r.arrival)
    return out


def token_gaps(run: Run) -> list[float]:
    """Gaps between consecutive output tokens of one request, for tokens
    emitted in the window."""
    out = []
    for r in run.records:
        ts = [t for t in r.token_times if t <= run.seconds]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(run: Run) -> int:
    return sum(sum(1 for t in r.token_times if t <= run.seconds)
               for r in run.records)


def steps_in_window(run: Run) -> list:
    return [s for s in run.steps if s.end <= run.seconds]


def admits_in_window(run: Run) -> list:
    return [a for a in run.admits if a.end <= run.seconds]


def steps_traced(run: Run) -> list:
    """Steps whose host span lies inside the traced part of the window."""
    if run.trace_host is None:
        return []
    on, off = run.trace_host
    return [s for s in run.steps if s.start >= on and s.end <= off]


def decode_runs(run: Run, pattern: str) -> list:
    """The traced window's runs of programs whose name holds ``pattern``
    (first device plane), cut at the window's edges."""
    tr = run.trace
    if tr is None or tr.window is None or not tr.modules:
        return []
    return [e for e in tr.clip(next(iter(tr.modules.values())))
            if pattern in e.name]


def dump(run: Run, trace_host) -> dict:
    """The window's host records, to read metrics again from a kept
    trace."""
    return {
        "cell": run.cell, "seconds": run.seconds, "setup_s": run.setup_s,
        "trace_host": trace_host, "stall": run.stall,
        "steps": [[s.start, s.end, s.positions, s.sampled]
                  for s in run.steps],
        "admits": [[a.start, a.end, a.length] for a in run.admits],
        "records": [[r.arrival, r.admit_start, r.token_times, r.req.greedy,
                     r.req.n_new, len(r.req.prompt)] for r in run.records],
    }


def from_dump(d: dict, dims: Dims, serve: dict, traffic: dict,
              peaks: dict | None) -> Run:
    """The ``Run`` that ``dump`` wrote (prompts as zeros: only their
    lengths are kept)."""
    import numpy as np

    from serve import Admit, Record, Step
    from traffic import Req

    records = [Record(req=Req(i, np.zeros(lp, np.int32), n, g, 0, arr),
                      arrival=arr, admit_start=adm, token_times=tt)
               for i, (arr, adm, tt, g, n, lp) in enumerate(d["records"])]
    return Run(cell=d["cell"], seconds=d["seconds"], setup_s=d["setup_s"],
               dims=dims, serve=serve, traffic=traffic, records=records,
               steps=[Step(*s) for s in d["steps"]],
               admits=[Admit(*a) for a in d["admits"]], peaks=peaks,
               trace_host=tuple(d["trace_host"]) if d["trace_host"]
               else None,
               stall=tuple(d["stall"]) if d.get("stall") else None)


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    path = os.path.join(METRICS_DIR, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
