"""Sharded-vs-unsharded BIT-exactness of the mesh-native solver engine and
the continuous-batching serving stack (DESIGN.md §5).

Runs in SUBPROCESSES with 8 forced host devices (the forced-device flag
must never leak into this pytest process).  Two layers:

  * engine: all four solver kinds, both backends, scalar AND per-row
    traced parameters — final (lo, hi) brackets under a (2 data, 4 model)
    mesh policy must equal the single-device solve bit-for-bit (the sign
    walk consumes signs only, so brackets are grid points whose exactness
    survives the float psum reassociation of the mass/entropy partials);
  * serving: `RunaheadServer` with `mesh=` — staggered arrivals, slot
    reuse, heterogeneous per-slot samplers covering every engine kind the
    sampler exposes — must emit per-request token streams identical to
    the single-device server, per backend.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro.core import solver, tuning
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 256), jnp.float32) * 3.0
    probs = jax.nn.softmax(x, axis=-1)

    cases = [
        ("count_above", x, dict(k=17)),                    # static fast path
        ("count_above", x, dict(k=jnp.arange(8) + 3)),     # per-row traced
        ("mass_at_or_above", probs, dict(p=0.9)),
        ("mass_at_or_above", probs,
         dict(p=jnp.linspace(0.5, 0.95, 8))),
        ("entropy_at_temperature", x, dict(target=2.0)),
        ("count_below", x, dict(q=0.3)),
    ]
    for backend in ("jnp", "pallas"):
        for kind, op, params in cases:
            # tuning.disabled() pins the legacy fixed policy: plain path
            # unmeshed, vocab-sharded shard_map under the policy — the
            # pair this differential exists to compare
            with tuning.disabled():
                ref = solver.solve_kind(kind, op, backend=backend,
                                        rounds=6, spec_k=4, **params)
                with solver.mesh_policy(mesh):
                    sh = solver.solve_kind(kind, op, backend=backend,
                                           rounds=6, spec_k=4, **params)
            assert bool(jnp.array_equal(ref[0], sh[0])
                        & jnp.array_equal(ref[1], sh[1])), \\
                (backend, kind, ref, sh)
            # tuned: whatever decomposition/placement the tuner picks
            # under the mesh must land on the same brackets
            with solver.mesh_policy(mesh):
                tu = solver.solve_kind(kind, op, backend=backend,
                                       rounds=6, spec_k=4, **params)
            assert bool(jnp.array_equal(ref[0], tu[0])
                        & jnp.array_equal(ref[1], tu[1])), \\
                (backend, kind, tuning.explain()[-1], ref, tu)
            print(f"{backend}/{kind} bit-exact (fixed + tuned)")
        # pure data parallelism (model axis size 1): the fused
        # whole-solve top-k hook stays on the per-device full rows
        mesh_dp = make_mesh((8, 1), ("data", "model"))
        with tuning.disabled():
            ref = solver.solve_kind("count_above", x, backend=backend,
                                    rounds=6, spec_k=4, k=17)
            with solver.mesh_policy(mesh_dp):
                sh = solver.solve_kind("count_above", x, backend=backend,
                                       rounds=6, spec_k=4, k=17)
        assert bool(jnp.array_equal(ref[0], sh[0])
                    & jnp.array_equal(ref[1], sh[1]))
        print(f"{backend}/data-parallel fused top-k bit-exact")
    print("OK")
""")

SERVING_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request, RunaheadServer

    backend = "@BACKEND@"
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = make_mesh((2, 4), ("data", "model"))

    def workload():
        sc = lambda **kw: SamplerConfig(backend=backend, **kw)
        return [
            Request("a", [1, 2, 3, 4], 5, seed=11, sampler=sc(top_k=12)),
            Request("b", [9, 8, 7, 6, 5], 3, seed=22, sampler=sc(top_p=0.9)),
            Request("c", [4, 4, 4], 4, seed=33,
                    sampler=sc(target_entropy=2.0), arrival=1),
            Request("d", [10, 20, 30, 40], 6, seed=44,
                    sampler=sc(temperature=0.7), arrival=2),
            Request("e", [2, 4, 6, 8], 4, seed=55,
                    sampler=sc(top_k=8, top_p=0.95), arrival=4),
        ]

    plain = RunaheadServer(cfg, params, n_slots=4, context=32,
                           backend=backend)
    ref = {c.rid: c.tokens for c in plain.run(workload())}
    meshed = RunaheadServer(cfg, params, n_slots=4, context=32,
                            backend=backend, mesh=mesh)
    got = {c.rid: c.tokens for c in meshed.run(workload())}
    assert ref == got, (backend, ref, got)

    # slot state really is sharded over the data axis (and stays so
    # through donation across steps)
    kv = meshed.scheduler.cache[0]["kv"].k
    spec = kv.sharding.spec
    assert len(spec) >= 2 and spec[1] == "data", spec
    print(backend, "sharded serving streams identical:", ref)
    print("OK")
""")


SPEC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import (
        Request, RunaheadServer, generate_oneshot_reference)

    backend = "@BACKEND@"
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = make_mesh((2, 4), ("data", "model"))

    # repetitive greedy workload: drafts actually get accepted, so the
    # verify/rollback/position-jump path runs under GSPMD for real
    sc = SamplerConfig(backend=backend, greedy=True, top_k=12)
    pats = [[3, 5, 7], [2, 4, 6], [9, 9, 1]]
    reqs = [Request(f"r{i}", (pats[i % 3] * 3)[:8], 7 + (i % 3), seed=i,
                    sampler=sc, arrival=i // 3) for i in range(5)]
    refs = {r.rid: generate_oneshot_reference(cfg, params, r, context=32)
            for r in reqs}

    for m in (None, mesh):
        srv = RunaheadServer(cfg, params, n_slots=2, context=32,
                             backend=backend, mesh=m, draft_len=3)
        got = {c.rid: c.tokens for c in srv.run(list(reqs))}
        label = "meshed" if m is not None else "single"
        assert got == refs, (backend, label, got, refs)
        assert srv.scheduler.n_accepted > 0, label
        print(backend, label, "speculative streams bit-exact, acceptance",
              round(srv.scheduler.acceptance_rate, 3))
    print("OK")
""")


PAGED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request, RunaheadServer

    backend = "@BACKEND@"
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = make_mesh((2, 4), ("data", "model"))

    pre = list(range(1, 10))       # shared prefix: COW forks under GSPMD
    def workload():
        sc = lambda **kw: SamplerConfig(backend=backend, **kw)
        return [
            Request("a", pre + [50], 5, seed=11, sampler=sc(top_k=12)),
            Request("b", pre + [51], 3, seed=22, sampler=sc(top_p=0.9)),
            Request("c", [4, 4, 4], 4, seed=33,
                    sampler=sc(temperature=0.7), arrival=1),
            Request("d", pre + [52], 6, seed=44, sampler=sc(), arrival=2),
            Request("e", [2, 4, 6, 8], 4, seed=55,
                    sampler=sc(top_k=8, top_p=0.95), arrival=4),
        ]

    dense = RunaheadServer(cfg, params, n_slots=4, context=32,
                           backend=backend)
    ref = {c.rid: c.tokens for c in dense.run(workload())}
    # paged, single device and meshed: streams must be bit-identical to
    # the dense single-device server either way
    for m in (None, mesh):
        srv = RunaheadServer(cfg, params, n_slots=4, context=32,
                             backend=backend, mesh=m, page_size=4)
        got = {c.rid: c.tokens for c in srv.run(workload())}
        label = "meshed" if m is not None else "single"
        assert got == ref, (backend, label, got, ref)
        assert srv.scheduler.n_prefix_hits >= 1, label
    # the pool really shards its page dim over the data axis (and stays
    # so through donation across steps); n_pages = 4*8+1 = 33 does not
    # divide 2, so force a divisible pool to check placement
    srv = RunaheadServer(cfg, params, n_slots=4, context=32,
                         backend=backend, mesh=mesh, page_size=4,
                         cache_pages=34)
    got = {c.rid: c.tokens for c in srv.run(workload())}
    assert got == ref, (backend, "sized", got, ref)
    spec = srv.scheduler.pool[0]["kv"].k.sharding.spec
    assert len(spec) >= 2 and spec[1] == "data", spec

    # speculative paged under the mesh: greedy repetitive workload so
    # accepted drafts jump positions across page boundaries for real
    sc = SamplerConfig(backend=backend, greedy=True, top_k=12)
    pats = [[3, 5, 7], [2, 4, 6], [9, 9, 1]]
    reqs = [Request(f"r{i}", (pats[i % 3] * 3)[:8], 7 + (i % 3), seed=i,
                    sampler=sc, arrival=i // 3) for i in range(5)]
    sd = RunaheadServer(cfg, params, n_slots=2, context=32,
                        backend=backend, draft_len=3)
    sref = {c.rid: c.tokens for c in sd.run(list(reqs))}
    sp = RunaheadServer(cfg, params, n_slots=2, context=32,
                        backend=backend, mesh=mesh, draft_len=3,
                        page_size=3)
    sgot = {c.rid: c.tokens for c in sp.run(list(reqs))}
    assert sgot == sref, (backend, sgot, sref)
    assert sp.scheduler.n_accepted > 0
    print(backend, "paged sharded serving streams identical:", ref)
    print("OK")
""")


FUSED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.draft import RepeatLastDrafter
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request, RunaheadServer

    backend = "@BACKEND@"
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh = make_mesh((2, 4), ("data", "model"))

    def workload():
        sc = lambda **kw: SamplerConfig(backend=backend, **kw)
        return [
            Request("a", [1, 2, 3, 4], 5, seed=11, sampler=sc(top_k=12)),
            Request("b", [9, 8, 7, 6, 5], 3, seed=22, sampler=sc(top_p=0.9)),
            Request("c", [4, 4, 4], 4, seed=33,
                    sampler=sc(target_entropy=2.0), arrival=1),
            Request("d", [10, 20, 30, 40], 6, seed=44,
                    sampler=sc(temperature=0.7), arrival=2),
            Request("e", [2, 4, 6, 8], 4, seed=55,
                    sampler=sc(top_k=8, top_p=0.95), arrival=4),
        ]

    # per-step single-device server is the reference; the fused horizon
    # must reproduce it on 1 device AND under the (2, 4) mesh, dense and
    # paged — the scan body shards exactly like the per-step body
    plain = RunaheadServer(cfg, params, n_slots=4, context=32,
                           backend=backend)
    ref = {c.rid: c.tokens for c in plain.run(workload())}
    for m in (None, mesh):
        for page in (None, 4):
            srv = RunaheadServer(cfg, params, n_slots=4, context=32,
                                 backend=backend, mesh=m, page_size=page,
                                 step_horizon=4)
            got = {c.rid: c.tokens for c in srv.run(workload())}
            label = ("meshed" if m is not None else "single",
                     "paged" if page else "dense")
            assert got == ref, (backend, label, got, ref)
            assert srv.scheduler.n_horizons >= 1, label
            print(backend, label, "fused streams identical")

    # fused speculative under the mesh: repeat-last drafting on-device,
    # greedy repetitive workload == the serial reference
    sc = SamplerConfig(backend=backend, greedy=True, top_k=12)
    pats = [[3, 5, 7], [2, 4, 6], [9, 9, 1]]
    reqs = [Request(f"r{i}", (pats[i % 3] * 3)[:8], 7 + (i % 3), seed=i,
                    sampler=sc, arrival=i // 3) for i in range(5)]
    sref = {c.rid: c.tokens
            for c in RunaheadServer(cfg, params, n_slots=2, context=32,
                                    backend=backend).run(list(reqs))}
    srv = RunaheadServer(cfg, params, n_slots=2, context=32,
                         backend=backend, mesh=mesh, draft_len=3,
                         drafter=RepeatLastDrafter(), step_horizon=3)
    sgot = {c.rid: c.tokens for c in srv.run(list(reqs))}
    assert sgot == sref, (backend, sgot, sref)
    assert srv.scheduler.n_accepted > 0
    print(backend, "fused speculative meshed streams identical")
    print("OK")
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=500)


@pytest.mark.slow
def test_all_kinds_bit_exact_under_mesh():
    r = _run(KINDS_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_serving_streams_identical(backend):
    r = _run(SERVING_SCRIPT.replace("@BACKEND@", backend))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_paged_streams_identical(backend):
    """Paged continuous batching on 8 devices: dense single-device
    streams reproduced bit-for-bit by the paged server (1 device AND the
    (2, 4) mesh, serial and speculative), with prefix COW forks taken
    and the page pool genuinely sharded over the data axis."""
    r = _run(PAGED_SCRIPT.replace("@BACKEND@", backend))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["jnp"])
def test_sharded_fused_horizon_streams_identical(backend):
    """Fused K=4 horizons on 8 devices: per-step single-device streams
    reproduced bit-for-bit (dense/paged × single/meshed), plus fused
    on-device speculative drafting under the mesh."""
    r = _run(FUSED_SCRIPT.replace("@BACKEND@", backend))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_speculative_streams_identical(backend):
    """Greedy draft-and-verify on 8 devices: per-request streams must
    equal the serial one-shot reference, meshed AND unmeshed, with drafts
    genuinely accepted (variable-length position jumps under GSPMD)."""
    r = _run(SPEC_SCRIPT.replace("@BACKEND@", backend))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
