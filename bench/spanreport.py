#!/usr/bin/env python3
"""Reads the program's ``serve.*`` spans out of a trace kept by a traced
run, and prints one JSON line of what they show (``spans.py``):

  python3 bench/run.py --workload <cell> --seed <n> --seconds 50 --trace 1 \\
      --keep-trace t.xplane.pb --keep-run r.json
  python3 bench/spanreport.py t.xplane.pb --run r.json

Keys: the window and device-busy seconds; ``idle_by_span``; the longest
idle stretches by span; ``step_gap_ms_p50`` and, from the device's
program runs alone, ``device_step_gap_ms_p50`` (the two agree when the
host spans and the device planes share one clock); ``step_gap_parts``, that
idle time cut at the host's span edges; ``launches_per_step``
and the host's launches per decode step by span and program;
``admit_idle_ms_p50``; the longest span of each name.  With ``--run``
(the host records of the same run), ``tokens_per_s`` over the window and
inside and outside its traced part.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

import devtrace
import spans


def tokens_per_s(run: dict) -> dict:
    """Output tokens per second of the window, and of its traced part
    and the rest (host clock)."""
    seconds = run["seconds"]
    times = [t for r in run["records"] for t in r[2] if t <= seconds]
    out = {"window": len(times) / seconds}
    if run.get("trace_host"):
        on, off = run["trace_host"]
        inside = sum(on <= t <= off for t in times)
        out["traced"] = inside / (off - on)
        out["untraced"] = (len(times) - inside) / (seconds - (off - on))
    return out


def report(path: str, run: dict | None = None) -> dict:
    trace = devtrace.load(path)
    program = spans.load(path)
    out = {"window_s": trace.window_s, "busy_s": devtrace.busy_s(trace),
           "idle_by_span": spans.idle_by_span(trace, program),
           "longest_idle": spans.longest_idle(trace, program),
           "step_gap_ms_p50": spans.step_gap_ms_p50(trace, program),
           "device_step_gap_ms_p50": spans.device_step_gap_ms_p50(trace),
           "step_gap_parts": spans.step_gap_parts(trace, program),
           "launches_per_step": spans.launches_per_step(trace, program),
           "admit_idle_ms_p50": spans.admit_idle_ms_p50(trace, program)}
    by_span = spans.launches_by_span(trace, program)
    n_steps = by_span and len(spans.in_window(trace, program.spans,
                                              "serve.step.launch"))
    if n_steps:
        out["host_launches_per_step"] = {
            where: {p: n / n_steps for p, n in per.items()}
            for where, per in by_span.items()}
    longest: dict[str, float] = {}
    for s in program.spans:
        longest[s.name] = max(longest.get(s.name, 0.0), s.end - s.start)
    out["longest_span_s"] = longest
    if run is not None:
        out["tokens_per_s"] = tokens_per_s(run)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help=".xplane.pb, or the same gzipped")
    ap.add_argument("--run", help="the run's host records (--keep-run)")
    args = ap.parse_args(argv)
    run = None
    if args.run:
        with open(args.run) as f:
            run = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = args.trace
        if path.endswith(".gz"):
            path = os.path.join(tmp, "trace.xplane.pb")
            with gzip.open(args.trace) as src, open(path, "wb") as dst:
                shutil.copyfileobj(src, dst)
        print(json.dumps(report(path, run)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
