"""Request/response front-end over the continuous-batching scheduler.

``RunaheadServer`` is the serving loop the ``launch/serve.py --continuous``
driver (and the serving benchmark) runs: submit ``Request``s at any time,
call ``step()`` per decode tick, collect ``Completion``s as each request
finishes — no request ever waits for another request's tail tokens, which
is the whole point over one-shot ``generate``.

The loop is deliberately synchronous and single-threaded: one ``step()``
is one batched decode launch, and admission happens between steps.  The
async transports a production deployment needs (HTTP, streaming) bolt onto
``submit``/``step``/``drain`` without touching the device code.

Admitting from the queue and collecting completions are ``jax.profiler``
host spans (``serve.queue``, ``serve.drain``) beside the scheduler's own.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Sequence

import jax
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig
from repro.serving.sampler import SamplerConfig
from repro.serving.scheduler import ContinuousScheduler


@dataclasses.dataclass
class Request:
    """One generation request.

    ``arrival`` is the decode-step index at which ``run`` makes the
    request visible to the server (0 = available immediately): the
    staggered-arrival knob of scripted workloads.  ``submit`` ignores it.
    """

    rid: Any
    prompt: Sequence[int]
    n_new: int
    seed: int = 0
    sampler: SamplerConfig = dataclasses.field(default_factory=SamplerConfig)
    arrival: int = 0
    eos_id: int | None = None       # stop early on this token (n_new is
    # then a budget cap, not an exact length)


@dataclasses.dataclass
class Completion:
    """A finished request.  ``arrival_time`` (at ``submit``) and
    ``finish_time`` (when its completion was collected) are
    ``time.perf_counter()`` readings: only their difference means
    anything."""

    rid: Any
    tokens: list[int]
    arrival_step: int
    admit_step: int
    finish_step: int
    arrival_time: float
    finish_time: float

    @property
    def latency_s(self) -> float:
        return self.finish_time - self.arrival_time

    @property
    def queue_steps(self) -> int:
        """Decode steps spent waiting for a slot."""
        return self.admit_step - self.arrival_step


class RunaheadServer:
    """Continuous-batching serving engine over the runahead sampler."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 4,
        context: int = 64,
        spec_k: int = 5,
        rounds: int = 8,
        backend: str = "jnp",
        mesh: jax.sharding.Mesh | None = None,
        draft_len: int = 1,
        drafter=None,
        page_size: int | None = None,
        cache_pages: int | None = None,
        page_impl: str = "gather",
        step_horizon: int = 1,
        draft_len_auto: bool = False,
        max_draft_len: int | None = None,
    ):
        self.scheduler = ContinuousScheduler(
            cfg, params, n_slots=n_slots, context=context,
            spec_k=spec_k, rounds=rounds, backend=backend, mesh=mesh,
            draft_len=draft_len, drafter=drafter,
            page_size=page_size, cache_pages=cache_pages,
            page_impl=page_impl, step_horizon=step_horizon,
            draft_len_auto=draft_len_auto, max_draft_len=max_draft_len,
        )
        self._pending: deque[Request] = deque()
        self._meta: dict[Any, tuple[int, int, float]] = {}   # rid -> meta
        self._step_idx = 0

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.rid in self._meta:
            raise ValueError(
                f"request id {req.rid!r} already pending or in flight"
            )
        # reject unservable requests HERE, before they enter the queue —
        # a late failure inside _admit_pending would lose the request
        self.scheduler.validate_request(req.n_new, req.sampler,
                                        prompt_len=len(req.prompt))
        self._pending.append(req)
        self._meta[req.rid] = (self._step_idx, -1, time.perf_counter())

    def step(self) -> list[Completion]:
        """Admit what fits, advance one scheduler boundary, return new
        completions.

        With ``step_horizon`` K > 1 one call covers K fused decode
        iterations (one dispatch): admission, eviction, and completion
        drain all happen HERE, at the horizon boundary — requests
        finishing mid-horizon surface at the end of the call, and queued
        requests wait at most K iterations for a slot.
        """
        self._admit_pending()
        self.scheduler.step()
        self._step_idx += 1
        return self._drain_finished()

    def drain(self) -> list[Completion]:
        """Step until every submitted request has completed."""
        done: list[Completion] = []
        # n_new == 1 requests can finish inside admission without a step
        self._admit_pending()
        done.extend(self._drain_finished())
        while self._pending or self.scheduler.n_active:
            done.extend(self.step())
        return done

    def run(self, requests: Sequence[Request]) -> list[Completion]:
        """Serve a scripted workload with staggered ``arrival`` steps."""
        todo = sorted(requests, key=lambda r: r.arrival)
        done: list[Completion] = []
        i = 0
        while i < len(todo) or self._pending or self.scheduler.n_active:
            while i < len(todo) and todo[i].arrival <= self._step_idx:
                self.submit(todo[i])
                i += 1
            if not (self._pending or self.scheduler.n_active):
                # idle gap before the next arrival: jump to it
                self._step_idx = todo[i].arrival
                continue
            done.extend(self.step())
        done.extend(self._drain_finished())
        return done

    # -- internals ----------------------------------------------------------

    def _admit_pending(self) -> None:
        with TraceAnnotation("serve.queue"):
            while self._pending and self.scheduler.has_free_slot():
                req = self._pending[0]
                if not self.scheduler.admit(
                    req.rid, req.prompt, req.n_new, req.seed, req.sampler,
                    eos_id=req.eos_id,
                ):
                    break                    # pool filled under us
                self._pending.popleft()
                arr, _, t0 = self._meta[req.rid]
                self._meta[req.rid] = (arr, self._step_idx, t0)

    def _drain_finished(self) -> list[Completion]:
        with TraceAnnotation("serve.drain"):
            out = []
            now = time.perf_counter()
            for fin in self.scheduler.pop_finished():
                arr, adm, t0 = self._meta.pop(fin.rid)
                out.append(Completion(
                    rid=fin.rid, tokens=fin.tokens, arrival_step=arr,
                    admit_step=adm, finish_step=self._step_idx,
                    arrival_time=t0, finish_time=now,
                ))
            return out


def generate_oneshot_reference(
    cfg: ModelConfig, params, req: Request, *, context: int
) -> list[int]:
    """The request served alone through the one-shot engine — the
    per-request ground truth continuous batching must reproduce."""
    import jax.numpy as jnp

    from repro.serving.engine import generate

    prompt = jnp.asarray(req.prompt, jnp.int32).reshape(1, -1)
    toks = generate(
        cfg, params, prompt, req.n_new, jax.random.PRNGKey(req.seed),
        context=context, sampler=req.sampler,
    )
    out = [int(t) for t in toks[0]]
    if req.eos_id is not None and req.eos_id in out:
        out = out[: out.index(req.eos_id) + 1]
    return out
