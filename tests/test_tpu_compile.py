"""Compiles of the serving main path for a TPU v5e chip, with no chip.

The TPU compiler is installed here and compiles for a described,
unattached chip: it refuses what interpret mode hides — block shapes
Mosaic cannot tile, too much VMEM, programs that do not fit HBM.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and pytest-xdist
workers must all collect the same tests.  Keep every such compile in
this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import tuning
from repro.kernels import blocks
from repro.kernels import multi_count as mc
from repro.kernels import multi_entropy as me
from repro.kernels import multi_mass as mm
from repro.kernels import paged_attend as pa
from repro.kernels import runahead_threshold as rt

V = 151_936                  # qwen3 vocab
M = 31                       # one speculative round at spec_k 5
HBM_BYTES = 15.75 * 2 ** 30  # what XLA lets one v5e program use

SOLVER_KERNELS = {
    "multi_count": mc.multi_count,
    "multi_mass": mm.multi_mass,
    "multi_entropy": me.multi_entropy,
    "multi_entropy_moments": me.multi_entropy_moments,
}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compile cache off
    (entries written here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _tuned(kernel, shape, dtype, fixed):
    """The geometry the analytic tier picks for a compiled TPU launch."""
    key = tuning.KernelKey(kernel=kernel, shape=shape, dtype=dtype,
                           device_kind="tpu", interpret=False)
    return tuning.decide_kernel(key, fixed=fixed).params


@pytest.mark.parametrize("geometry", ["fixed", "tuned"])
@pytest.mark.parametrize("B", [1, 4, 8])
@pytest.mark.parametrize("kernel", sorted(SOLVER_KERNELS))
def test_solver_kernel_compiles_for_v5e(chip, kernel, B, geometry):
    fixed = {"block_v": blocks.DEFAULT_BLOCK_V}
    params = (fixed if geometry == "fixed"
              else _tuned(kernel, (B, V, M), "float32", fixed))
    fn = functools.partial(SOLVER_KERNELS[kernel], **params,
                           interpret=False)
    _, hlo = _compile(fn, _shape(chip, (B, V), jnp.float32),
                      _shape(chip, (B, M), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("geometry", ["fixed", "tuned"])
@pytest.mark.parametrize("B", [1, 4, 8])
def test_fused_topk_compiles_for_v5e(chip, B, geometry):
    fixed = {"block_v": blocks.LANE}
    params = (fixed if geometry == "fixed"
              else _tuned("runahead_topk", (B, V), "float32", fixed))
    fn = functools.partial(rt.runahead_topk_threshold, k_target=40,
                           rounds=8, spec_k=5, **params, interpret=False)
    _, hlo = _compile(fn, _shape(chip, (B, V), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("geometry", ["fixed", "tuned"])
@pytest.mark.parametrize("L", [1, 4])
def test_paged_attend_compiles_for_v5e(chip, L, geometry):
    """qwen3-4b heads (n_kv 8, n_rep 4, head_dim 128), 16-row pages,
    2,048 context, 4 slots, serial (L=1) and a 4-token verify grid."""
    B, nkv, R, D, P, context = 4, 8, 4, 128, 16, 2048
    n_chain = context // P
    n_pages = B * n_chain + 1
    fixed = {"pages_per_step": 1}
    params = (fixed if geometry == "fixed"
              else _tuned("paged_attend", (B, nkv, n_chain, P, L, R, D),
                          "bfloat16", fixed))
    fn = functools.partial(pa.paged_attend, context=context, **params,
                           interpret=False)
    pool = _shape(chip, (n_pages, P, nkv, D), jnp.bfloat16)
    _, hlo = _compile(fn, pool, pool,
                      _shape(chip, (B, n_chain), jnp.int32),
                      _shape(chip, (B,), jnp.int32),
                      _shape(chip, (B, L, nkv * R, D), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_qwen3_4b_decode_step_fits_one_v5e(chip):
    """The full-width decode step at 4 slots x 2,048 context, cache
    donated: arguments plus temp must fit one chip's HBM."""
    from repro.configs.registry import get_config
    from repro.models.decode import decode_step, init_cache
    from repro.models.transformer import init_params

    cfg = get_config("qwen3-4b")
    B, context = 4, 2048

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, B, context, jnp.bfloat16)))
    step = jax.jit(functools.partial(decode_step, cfg), donate_argnums=(3,))
    compiled = step.lower(params, _shape(chip, (B,), jnp.int32),
                          _shape(chip, (B,), jnp.int32), cache).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_BYTES
