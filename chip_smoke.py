#!/usr/bin/env python3
"""Smoke test: serve full-width qwen3-4b on one TPU v5e chip.

Drives the serving main path once, through the entry points a user
calls (``RunaheadServer`` built as ``launch/serve.py --continuous``
builds it): qwen3-4b at its published widths, random bf16 weights from
``--seed``, 4 slots of 2,048 context, the ``pallas`` solver backend
compiled, the tuner in its analytic tier.

One chip (no option), in one process:

  1. dense ring cache: 8 requests (prompts of 128 and 512 tokens, 32
     new tokens each, samplers mixing top-k, top-p and target entropy),
     served cold (compiles included) and again warm;
  2. paged cache (``page_size=16``, ``page_impl="pallas"``): the same
     requests;
  3. kernel parity: on one batch of real decode logits, every compiled
     solver kernel against its jnp reference — counts and the fused
     top-k bracket exact, mass and entropy allclose.

``--four-chips`` runs only the mesh path: the same requests under
``--mesh 2x2`` on four chips, compared with the same requests on
device 0, by the first decode step's logits (bf16 tolerance below).

The last line of stdout is the JSON contract line, printed only when
every phase passed on a TPU at full width.  Without a TPU the script
exits non-zero before doing any work, unless ``--reduced`` asks for a
CPU rehearsal at a tiny width, which also never prints the line:

  JAX_PLATFORMS=cpu python chip_smoke.py --reduced
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --reduced --four-chips
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-4b"
N_SLOTS = 4
CONTEXT = 2048
NEW_TOKENS = 32
PROMPT_LENS = (128, 512)
N_REQUESTS = 8
PAGE_SIZE = 16
SPEC_K, ROUNDS = 5, 8
N_CANDIDATES = (1 << SPEC_K) - 1       # one speculative round's grid
# First-decode-step logits on the mesh vs device 0: bf16 carries 8
# mantissa bits, so a few roundings apart is a few 2**-8 of the largest
# logit; 2**-5 of it allows for the reductions regrouping across chips.
LOGIT_TOL = 2.0 ** -5
# Kernel parity tolerances: those of the repo's kernel parity tests.
MASS_TOL = dict(rtol=1e-5, atol=1e-6)
ENTROPY_TOL = dict(rtol=1e-4, atol=1e-5)


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds jax spent in XLA compiles (a persistent-cache hit counts
    its load instead), and how many it made.  Tracing is left out: its
    events nest when one jit traces inside another."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == self.EVENT:
            self.seconds += secs
            self.compiles += 1


def make_requests(cfg, seed):
    import numpy as np

    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request

    samplers = [
        SamplerConfig(top_k=40, backend="pallas"),
        SamplerConfig(top_p=0.9, backend="pallas"),
        SamplerConfig(target_entropy=3.0, top_k=40, backend="pallas"),
        SamplerConfig(top_k=20, top_p=0.95, temperature=0.8,
                      backend="pallas"),
    ]
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab,
                                    size=PROMPT_LENS[i % 2]).tolist(),
                n_new=NEW_TOKENS, seed=seed + i,
                sampler=samplers[i % len(samplers)])
        for i in range(N_REQUESTS)
    ]


def build_server(cfg, params, *, mesh=None, page_size=None):
    from repro.serving.server import RunaheadServer

    return RunaheadServer(
        cfg, params, n_slots=N_SLOTS, context=CONTEXT, spec_k=SPEC_K,
        rounds=ROUNDS, backend="pallas", mesh=mesh, page_size=page_size,
        page_impl="pallas" if page_size else "gather")


def check_completions(cfg, done, tag):
    check(len(done) == N_REQUESTS,
          f"{tag}: {len(done)} of {N_REQUESTS} requests completed")
    for c in done:
        check(len(c.tokens) == NEW_TOKENS,
              f"{tag}: request {c.rid} got {len(c.tokens)} tokens")
        check(all(0 <= t < cfg.vocab for t in c.tokens),
              f"{tag}: request {c.rid} emitted a token outside the vocab")
    return {c.rid: c.tokens for c in done}


def serve_phase(name, cfg, params, requests, clock, **server_kw):
    """Serve the requests cold, then warm on a fresh server (the compiled
    steps are shared module-wide); both runs must emit the same tokens."""
    n_tok = N_REQUESTS * NEW_TOKENS
    c0 = clock.seconds
    t0 = time.perf_counter()
    server = build_server(cfg, params, **server_kw)
    cold = check_completions(cfg, server.run(requests), name)
    cold_s = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    del server
    n0 = clock.compiles
    t0 = time.perf_counter()
    server = build_server(cfg, params, **server_kw)
    warm = check_completions(cfg, server.run(requests), name + " warm")
    warm_s = time.perf_counter() - t0
    check(warm == cold, f"{name}: the warm run's tokens differ from the "
                        "cold run's")
    say(f"{name}: {N_REQUESTS} requests, {n_tok} tokens; cold "
        f"{cold_s:.3f} s ({n_tok / cold_s:.2f} tok/s incl. compile, "
        f"{compile_s:.3f} s compiling); warm {warm_s:.3f} s "
        f"({n_tok / warm_s:.2f} tok/s, {clock.compiles - n0} compiles)")
    s = server.scheduler
    say(f"{name}: {s.n_decode_steps} decode steps, {s.n_dispatches} "
        f"dispatches, {s.n_admissions} admissions")
    del server
    return cold


def first_decode_logits(cfg, scheduler, token=None):
    """Logits of one decode step over the scheduler's admitted slots
    (``token`` overrides the slots' current tokens)."""
    import jax

    from repro.models.decode import decode_step

    if token is None:
        token = scheduler.token
    step = jax.jit(functools.partial(decode_step, cfg))
    logits, _ = step(scheduler.params, token, scheduler.pos, scheduler.cache)
    return logits


def admit_first(scheduler, requests):
    for r in requests[:N_SLOTS]:
        check(scheduler.admit(r.rid, r.prompt, r.n_new, r.seed, r.sampler),
              f"admission of request {r.rid} refused")


def kernel_parity(cfg, params, requests):
    """Every compiled solver kernel vs its jnp reference, on real decode
    logits, through the public wrappers (the tuned geometry)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    server = build_server(cfg, params)
    admit_first(server.scheduler, requests)
    z = first_decode_logits(cfg, server.scheduler).astype(jnp.float32)
    del server
    B = z.shape[0]
    frac = jnp.linspace(0.02, 0.98, N_CANDIDATES)
    lo, hi = z.min(-1, keepdims=True), z.max(-1, keepdims=True)
    taus = lo + (hi - lo) * frac[None, :]
    got = np.asarray(ops.multi_count(z, taus))
    want = np.asarray(ref.multi_count_ref(z, taus))
    check(np.array_equal(got, want), "multi_count differs from jnp")

    probs = jax.nn.softmax(z, axis=-1)
    ptaus = probs.max(-1, keepdims=True) * frac[None, :]
    np.testing.assert_allclose(np.asarray(ops.multi_mass(probs, ptaus)),
                               np.asarray(ref.multi_mass_ref(probs, ptaus)),
                               **MASS_TOL, err_msg="multi_mass")

    ts = jnp.broadcast_to(jnp.linspace(0.2, 2.0, N_CANDIDATES),
                          (B, N_CANDIDATES))
    np.testing.assert_allclose(np.asarray(ops.multi_entropy(z, ts)),
                               np.asarray(ref.multi_entropy_ref(z, ts)),
                               **ENTROPY_TOL, err_msg="multi_entropy")
    zs = z - z.max(-1, keepdims=True)
    zt = zs[:, None, :] / ts[:, :, None]
    s, w = ops.multi_entropy_moments(zs, ts)
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(jnp.exp(zt).sum(-1)),
                               **ENTROPY_TOL, err_msg="moments s")
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray((zt * jnp.exp(zt)).sum(-1)),
                               **ENTROPY_TOL, err_msg="moments w")

    lo_k, hi_k = ops.runahead_topk_threshold(z, k_target=40, rounds=ROUNDS,
                                             spec_k=SPEC_K)
    lo_r, hi_r = ref.runahead_topk_threshold_ref(z, k_target=40,
                                                 rounds=ROUNDS,
                                                 spec_k=SPEC_K)
    check(np.array_equal(np.asarray(lo_k), np.asarray(lo_r))
          and np.array_equal(np.asarray(hi_k), np.asarray(hi_r)),
          "runahead_topk_threshold differs from jnp")
    say(f"kernel parity: multi_count, multi_mass, multi_entropy, "
        f"multi_entropy_moments, runahead_topk_threshold match jnp on "
        f"({B}, {z.shape[1]}) decode logits, M={N_CANDIDATES}")
    from repro.core import tuning

    for key, decision in tuning.explain_kernels():
        say(f"kernel geometry: {key} -> {decision.label()} "
            f"[{decision.source}]")


def four_chip_phase(cfg, params, requests):
    """The 2x2 mesh against device 0, in one process."""
    import jax
    import numpy as np

    from repro.launch.mesh import parse_mesh

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh = parse_mesh("2x2")
    everywhere = set(mesh.devices.flat)
    # device 0's share of the replicated params, aliased, not copied:
    # one chip cannot hold two copies of the model
    local = jax.tree_util.tree_map(lambda a: a.addressable_shards[0].data,
                                   params)

    single = build_server(cfg, local)
    sharded = build_server(cfg, params, mesh=mesh)
    sch = sharded.scheduler
    for leaf in jax.tree_util.tree_leaves(sch.params):
        check(leaf.sharding.device_set == everywhere,
              "params are not on all four devices")
    for name, leaf in (("token", sch.token), ("pos", sch.pos),
                       ("keys", sch.keys),
                       *(("cache", c) for c in
                         jax.tree_util.tree_leaves(sch.cache))):
        check(leaf.sharding.device_set == everywhere,
              f"slot state {name} is not on all four devices")
    say(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}: params "
        f"and slot state on {len(everywhere)} devices")

    admit_first(single.scheduler, requests)
    admit_first(sch, requests)
    ref_logits = first_decode_logits(cfg, single.scheduler)
    # the same input tokens on both sides: the admission samples may
    # legitimately differ at bf16 ties
    mesh_logits = first_decode_logits(
        cfg, sch, token=jax.device_put(single.scheduler.token,
                                       sch.token.sharding))
    a = np.asarray(ref_logits, np.float32)
    b = np.asarray(mesh_logits, np.float32)
    err = float(np.abs(a - b).max())
    scale = float(np.abs(a).max())
    say(f"first decode step logits, mesh vs device 0: max |diff| {err!r} "
        f"vs max |logit| {scale!r} (tolerance {LOGIT_TOL!r} x max)")
    check(np.isfinite(b).all(), "mesh logits are not finite")
    check(err <= LOGIT_TOL * scale, "mesh logits differ from device 0's "
                                    "beyond the bf16 tolerance")
    del single, sharded, sch

    n_tok = N_REQUESTS * NEW_TOKENS
    for tag, p, m in (("device 0", local, None), ("mesh 2x2", params, mesh)):
        t0 = time.perf_counter()
        server = build_server(cfg, p, mesh=m)
        check_completions(cfg, server.run(requests), tag)
        dt = time.perf_counter() - t0
        say(f"{tag}: served {N_REQUESTS} requests, {n_tok} tokens in "
            f"{dt:.3f} s incl. compile ({n_tok / dt:.2f} tok/s)")
        del server


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh path and its device-0 "
                         "comparison (needs four chips)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at a tiny width; never prints "
                         "the contract line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch import env as launch_env
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    resolved = launch_env.configure()

    import jax
    import jax.numpy as jnp
    from jax import monitoring

    clock = CompileClock(monitoring)
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    say(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devices)}")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(configured {resolved['compile_cache_dir']})")

    from repro.configs.registry import get_config
    from repro.kernels import ops
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params

    try:
        interp = ops.interpret_mode()
        say(f"pallas interpret mode: {interp}")
        check(not (on_tpu and interp), "pallas would interpret on a TPU")
        cfg = reduced_config(ARCH) if args.reduced else get_config(ARCH)
        say(f"model: {ARCH}{' (reduced)' if args.reduced else ''}, "
            f"{cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
            f"{cfg.vocab}, {cfg.param_count() / 1e9:.3f} B params")
        t0 = time.perf_counter()
        key = jax.random.PRNGKey(args.seed)
        def init(k):
            return init_params(cfg, k, jnp.bfloat16)

        if args.four_chips:
            from repro.launch.mesh import parse_mesh
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(parse_mesh("2x2"), PartitionSpec())
            params = jax.jit(init, out_shardings=replicated)(key)
        else:
            params = jax.jit(init)(key)
        jax.block_until_ready(params)
        say(f"params: random bf16 from seed {args.seed} in "
            f"{time.perf_counter() - t0:.3f} s")
        requests = make_requests(cfg, args.seed)

        if args.four_chips:
            four_chip_phase(cfg, params, requests)
        else:
            serve_phase("dense", cfg, params, requests, clock)
            serve_phase("paged", cfg, params, requests, clock,
                        page_size=PAGE_SIZE)
            kernel_parity(cfg, params, requests)
    except (PhaseFailed, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    say(f"compile total: {clock.seconds:.3f} s over {clock.compiles} "
        "backend compiles")
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    if not on_tpu or args.reduced:
        print("chip_smoke: rehearsal passed; the contract line is printed "
              "only on a TPU at full width", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
