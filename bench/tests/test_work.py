"""The work counts against the program's own parameter shapes, and the
arithmetic bound that keeps every share built on them at or under 100%."""
import jax
import jax.numpy as jnp
import pytest

import spec
import work
from model import Dims, make_weights

CONFIGS = ["qwen3-4b", "internlm2-1.8b"]


def dims_of(name):
    return Dims.of(spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")
                   ["model"])


def program_shapes(name):
    from repro.configs.registry import get_config
    from repro.models.transformer import init_params

    cfg = get_config(name)
    return cfg, jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                               jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    dm = dims_of(name)
    _, shapes = program_shapes(name)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert work.param_count(dm) == n


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_matches_program(name):
    dm = dims_of(name)
    _, want = program_shapes(name)
    got = jax.eval_shape(lambda: make_weights(dm, jax.random.key(0)))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    assert jax.tree_util.tree_leaves(got) == jax.tree_util.tree_leaves(want)


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_bytes_within_what_the_step_holds(name):
    """What a step needs never exceeds the weights plus the live part of
    the cache it reads, so bytes/time/peak <= 100% when the time covers
    the step."""
    from repro.models.decode import init_cache

    dm = dims_of(name)
    conf = spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")["serve"]
    B, C = conf["n_slots"], conf["context"]
    cfg, params = program_shapes(name)
    cache = jax.eval_shape(lambda: init_cache(cfg, B, C))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves((params, cache)))
    full = [C - 1] * B
    assert work.decode_step_bytes(dm, full) <= held
    assert work.decode_step_bytes(dm, [0]) >= work.weight_bytes(dm) - (
        0 if dm.tied else dm.vocab_rows * dm.d * work.BF16)


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_bounded_by_dense_count(name):
    """Needed FLOPs lie between the matmul count and that count plus full
    (not causal) attention, so mfu over a window cannot pass what the
    chip could do."""
    dm = dims_of(name)
    mats = 2 * dm.layers * work.layer_matmul_params(dm)
    for length in (1, 64, 1536):
        f = work.prefill_flops(dm, length)
        assert mats * length <= f
        assert f <= (mats * length + 2 * dm.d * dm.vocab
                     + dm.layers * 4 * dm.heads * dm.head_dim
                     * length * length)
    assert work.decode_token_flops(dm, 0) < work.decode_token_flops(dm, 99)
    assert work.solve_bytes(8, dm.vocab) == 8 * dm.vocab * 4
