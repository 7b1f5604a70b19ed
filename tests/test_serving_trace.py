"""Host spans of the serving layers, read back from a profiler trace.

``ContinuousScheduler.admit``/``step`` and ``RunaheadServer``'s queue and
drain record ``jax.profiler`` spans (``serve.*``) that the benchmark's
trace reduction reads by name.  Served under ``jax.profiler.trace`` on
the CPU, every span must be in the trace, each phase inside its call,
each admission tagged with its request, and the tokens unchanged.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.models.testing import reduced_config
from repro.models.transformer import init_params
from repro.serving.sampler import SamplerConfig
from repro.serving.server import Request, RunaheadServer

CONTEXT = 32
PARENT = {
    "serve.admit.prefill": "serve.admit",
    "serve.admit.sample": "serve.admit",
    "serve.admit.book": "serve.admit",
    "serve.admit": "serve.queue",
    "serve.step.prepare": "serve.step",
    "serve.step.launch": "serve.step",
    "serve.step.readback": "serve.step",
    "serve.step.commit": "serve.step",
}
SPANS = set(PARENT) | {"serve.queue", "serve.step", "serve.drain"}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _requests() -> list[Request]:
    """Three requests on two slots: one waits for a slot to free."""
    sc = lambda **kw: SamplerConfig(backend="jnp", **kw)
    return [
        Request("a", [1, 2, 3, 4], 5, seed=11, sampler=sc(top_k=12)),
        Request("b", [9, 8, 7, 6, 5], 3, seed=22, sampler=sc(top_p=0.9)),
        Request("c", [4, 4, 4], 4, seed=33, sampler=sc(greedy=True)),
    ]


def _serve(cfg, params, **kw) -> dict:
    srv = RunaheadServer(cfg, params, n_slots=2, context=CONTEXT, **kw)
    return {c.rid: c.tokens for c in srv.run(_requests())}


def _spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start ns, end ns, stats) of every ``serve.*`` host event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name.startswith("serve."))
    return out


@pytest.mark.parametrize("kw", [
    {},                                  # serial step, dense ring
    {"step_horizon": 4},                 # fused horizon
    {"page_size": 4},                    # paged pool
], ids=["serial", "fused", "paged"])
def test_spans_cover_the_serving_phases(tiny, tmp_path, kw):
    cfg, params = tiny
    untraced = _serve(cfg, params, **kw)
    with jax.profiler.trace(str(tmp_path)):
        traced = _serve(cfg, params, **kw)
    assert traced == untraced

    spans = _spans(str(tmp_path))
    assert {name for name, *_ in spans} == SPANS

    for name, lo, hi, _ in spans:
        if name in PARENT:
            assert any(p == PARENT[name] and plo <= lo and hi <= phi
                       for p, plo, phi, _ in spans), name

    admits = {st["rid"]: st["length"]
              for name, _, _, st in spans if name == "serve.admit"}
    assert admits == {r.rid: len(r.prompt) for r in _requests()}
    lives = [st["live"] for name, _, _, st in spans if name == "serve.step"]
    assert lives and all(1 <= n <= 2 for n in lives)
