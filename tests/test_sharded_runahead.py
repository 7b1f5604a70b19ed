"""Chip-level runahead bisection (shard_map over a mesh axis) + sharding
rule machinery.  Runs in a SUBPROCESS with 8 forced host devices so the
512-device dry-run flag never leaks into this pytest process."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, math
    import jax.numpy as jnp
    from repro.core import find_root_runahead_sharded, find_root_serial, make_paper_f

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    f = make_paper_f(50)
    a, b = jnp.float32(1.0), jnp.float32(2.0)
    for k in (2, 3, 4):
        r_sh = find_root_runahead_sharded(f, a, b, 12, k, mesh, axis="model")
        r_se = find_root_serial(f, a, b, 12, mode="signbit")
        assert float(r_sh) == float(r_se), (k, float(r_sh), float(r_se))
        print(f"k={k} sharded == serial: {float(r_sh):.6f}")
    print("OK")
""")

PARAM_SPEC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.shardings import make_param_shardings, zero1_spec
    from repro.launch.specs import params_specs
    from repro.configs.registry import get_config

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("qwen2-moe-a2.7b")
    params = params_specs(cfg)
    sh = make_param_shardings(mesh, params)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    specs = {"/".join(str(getattr(k, "key", k)) for k in path): s.spec
             for path, s in flat}
    # embed sharded over model on vocab dim
    assert specs["embed"] == P("model", None), specs["embed"]
    # MoE expert stacks: (L, E, d, f) with experts over model
    moe_gate = [v for k, v in specs.items()
                if "moe" in k and k.endswith("w_gate") and "shared" not in k]
    assert moe_gate and all(s == P(None, "model", None, None)
                            for s in moe_gate), moe_gate
    # attention wq: last dim over model
    wqs = [v for k, v in specs.items() if k.endswith("wq")]
    assert wqs and all(s == P(None, None, "model") for s in wqs), wqs
    print("OK")
""")


UNEVEN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro.core import find_root_serial, make_paper_f
    from repro.core import sharded

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("model",))
    f = make_paper_f(50)
    a, b = jnp.float32(1.0), jnp.float32(2.0)

    # Uneven splits over the full 8-way axis: 2**k - 1 points never divide
    # 8, so every round pads the grid — k=2 leaves FIVE of eight devices
    # evaluating nothing but padding.  Poison the pad fill with values
    # whose signs would derail the walk if they were ever consulted
    # (f(NaN) -> NaN -> bit 0; f(+-inf) -> NaN/garbage): trajectory
    # equality with serial bisection proves the padded-point signs are
    # computed and DISCARDED.  Non-divisible iteration budgets also cover
    # the partial last-round walk.
    for poison in (float("nan"), float("inf"), float("-inf")):
        sharded._pad_fill = (
            lambda interior, n_fill, p=poison:
                jnp.full((n_fill,), p, interior.dtype)
        )
        sharded._cached_sharded_solve.cache_clear()
        for k, iters in ((2, 12), (3, 11), (4, 13)):
            r_sh = sharded.find_root_runahead_sharded(f, a, b, iters, k,
                                                      mesh, axis="model")
            r_se = find_root_serial(f, a, b, iters, mode="signbit")
            assert float(r_sh) == float(r_se), (
                poison, k, iters, float(r_sh), float(r_se))
            print(f"poison={poison} k={k} iters={iters}: discarded")
    print("OK")
""")

RETRACE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    from repro.core import make_paper_f
    from repro.core import sharded

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    f = make_paper_f(50)
    a, b = jnp.float32(1.0), jnp.float32(2.0)

    # The engine's mesh path must cache its compiled step (the old
    # implementation rebuilt jax.jit(shard_map(...)) around a fresh
    # closure every call): repeated identical calls are pure cache hits,
    # a different static config is exactly one more miss.
    sharded.find_root_runahead_sharded(f, a, b, 12, 3, mesh)
    before = sharded._cached_sharded_solve.cache_info()
    for _ in range(5):
        sharded.find_root_runahead_sharded(f, a, b, 12, 3, mesh)
    after = sharded._cached_sharded_solve.cache_info()
    assert after.misses == before.misses, (before, after)
    assert after.hits == before.hits + 5, (before, after)
    sharded.find_root_runahead_sharded(f, a, b, 12, 4, mesh)
    assert sharded._cached_sharded_solve.cache_info().misses \\
        == before.misses + 1
    print("OK")
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=500)


@pytest.mark.slow
def test_sharded_runahead_matches_serial():
    r = _run(SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_uneven_split_pad_signs_discarded():
    r = _run(UNEVEN_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_compiled_step_cached_across_calls():
    r = _run(RETRACE_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_param_sharding_rules():
    r = _run(PARAM_SPEC_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
