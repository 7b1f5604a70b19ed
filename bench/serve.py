"""Drives the system under test: ``RunaheadServer.submit``/``step`` over
its ``ContinuousScheduler``, on the benchmark's own clock.

Host spans are taken by wrapping ``admit`` and ``step`` on the scheduler
instance.  Both end in a device-to-host read (the first token, the step's
tokens), so the host clock times finished device work.  Every emitted
token gets the time at which the call that produced it returned.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from traffic import Req, Stream


@dataclasses.dataclass
class Record:
    """One request as the benchmark saw it (times in seconds after the
    window opened)."""
    req: Req
    arrival: float
    admit_start: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list | None = None            # set when it finished


@dataclasses.dataclass
class Step:
    start: float
    end: float
    positions: list          # position of the token each live slot fed
    sampled: bool            # some live request samples (the solves run)


@dataclasses.dataclass
class Admit:
    start: float
    end: float
    length: int              # prompt tokens prefilled


class Recorder:
    """Wraps the scheduler's ``admit`` and ``step``; keeps every span."""

    def __init__(self, scheduler):
        self.scheduler = scheduler
        self.records: dict[int, Record] = {}
        self.steps: list[Step] = []
        self.admits: list[Admit] = []
        self.t0 = time.perf_counter()
        self.annotate = False
        orig_admit, orig_step = scheduler.admit, scheduler.step

        def admit(rid, prompt, n_new, seed, sampler, **kw):
            t0 = self.now()
            if self.annotate:
                with jax.profiler.TraceAnnotation("bench.admit"):
                    ok = orig_admit(rid, prompt, n_new, seed, sampler, **kw)
            else:
                ok = orig_admit(rid, prompt, n_new, seed, sampler, **kw)
            t1 = self.now()
            if ok:
                rec = self.records[rid]
                rec.admit_start = t0
                rec.token_times.append(t1)
                self.admits.append(Admit(t0, t1, len(rec.req.prompt)))
            return ok

        def step():
            live = [s for s in scheduler.slots if s is not None]
            positions = [len(self.records[s.rid].req.prompt)
                         + len(s.tokens) - 1 for s in live]
            sampled = any(not self.records[s.rid].req.greedy for s in live)
            t0 = self.now()
            if self.annotate:
                with jax.profiler.TraceAnnotation("bench.step"):
                    emitted = orig_step()
            else:
                emitted = orig_step()
            t1 = self.now()
            for rid, toks in emitted.items():
                self.records[rid].token_times.extend([t1] * len(toks))
            if positions:
                self.steps.append(Step(t0, t1, positions, sampled))
            return emitted

        scheduler.admit = admit
        scheduler.step = step

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def open_window(self) -> None:
        """Restart the clock and forget everything recorded so far."""
        self.records.clear()
        self.steps.clear()
        self.admits.clear()
        self.t0 = time.perf_counter()


def sampler_for(req: Req, traffic: dict, serve: dict):
    from repro.serving.sampler import SamplerConfig

    s = traffic["sampler"]
    return SamplerConfig(temperature=float(s["temperature"]),
                         top_k=int(s["top_k"]), top_p=float(s["top_p"]),
                         greedy=req.greedy, spec_k=serve["spec_k"],
                         rounds=serve["rounds"], backend=serve["backend"])


def submit(server, rec: Recorder, req: Req, traffic: dict, serve: dict,
           arrival: float) -> None:
    from repro.serving.server import Request

    rec.records[req.rid] = Record(req=req, arrival=arrival)
    server.submit(Request(rid=req.rid, prompt=req.prompt.tolist(),
                          n_new=req.n_new, seed=req.seed,
                          sampler=sampler_for(req, traffic, serve)))


def collect(rec: Recorder, completions) -> None:
    for c in completions:
        rec.records[c.rid].tokens = list(c.tokens)


def warm_up(server, rec: Recorder, traffic: dict, serve: dict,
            lengths: list[int], vocab: int) -> int:
    """Serve one short request per prompt length of the mix for each
    decoding kind it has, so that every program the window runs is
    compiled (or loaded) here: each prefill length, the first-token
    sampler of greedy and of sampled requests, and the decode step with
    and without the sampler's solves (a batch of one kind alone selects
    that kind's step).  Returns the number of requests served."""
    import numpy as np

    share = traffic["greedy_share"]
    kinds = ([True] if share > 0 else []) + ([False] if share < 1 else [])
    n = 0
    for greedy in kinds:
        for length in lengths:
            n += 1
            req = Req(rid=-n, prompt=np.full(length, 7 % vocab, np.int32),
                      n_new=3, greedy=greedy, seed=n, arrival=0.0)
            submit(server, rec, req, traffic, serve, 0.0)
        collect(rec, server.drain())
    return n


class TraceWindow:
    """The profiler on over part of the window, marked by a host span.
    Starting it stalls the host between two steps; ``stall`` is that
    interval, which no host span holds and ``mfu`` leaves out."""

    def __init__(self, rec: Recorder, trace_dir: str):
        self.rec, self.dir = rec, trace_dir
        self.span = None
        self.on = self.off = None
        self.stall = None

    def start(self) -> None:
        t0 = self.rec.now()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no Python function events
        opts.host_tracer_level = 1        # the bench.* spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.rec.annotate = True
        self.on = self.rec.now()
        self.stall = (t0, self.on)

    def stop(self) -> None:
        self.off = self.rec.now()
        self.rec.annotate = False
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def run_window(server, rec: Recorder, stream: Stream, traffic: dict,
               serve: dict, seconds: float, *, trace: TraceWindow | None
               = None, trace_at: float = 0.0) -> dict:
    """Serve the mix for ``seconds`` on the benchmark's clock; with
    ``trace``, profile from ``trace_at`` to the window's end (the trace
    is written out after it).  Returns the number of requests submitted
    and when the loop ended."""
    n_slots = serve["n_slots"]
    closed = traffic["loop"] == "closed"
    submitted = 0
    rec.open_window()
    while True:
        now = rec.now()
        if now >= seconds:
            break
        if trace is not None and trace.on is None and now >= trace_at:
            trace.start()
        if closed:
            while submitted - len(rec.admits) < n_slots:
                submit(server, rec, stream.take(), traffic, serve, now)
                submitted += 1
        else:
            while stream.peek().arrival <= now:
                req = stream.take()
                submit(server, rec, req, traffic, serve, req.arrival)
                submitted += 1
        if server.scheduler.n_active or submitted > len(rec.admits):
            collect(rec, server.step())
        else:
            nxt = seconds if closed else min(stream.peek().arrival, seconds)
            time.sleep(max(0.0, min(nxt - rec.now(), 0.05)))
    end = rec.now()
    if trace is not None and trace.on is not None:
        trace.stop()
    return {"submitted": submitted, "end": end}
