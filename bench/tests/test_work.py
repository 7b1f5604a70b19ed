"""The work counts against the program's own parameter shapes, and the
arithmetic bound that keeps every share built on them at or under 100%."""
import jax
import jax.numpy as jnp
import pytest

import model
import spec
import work
from model import Dims, make_weights

CONFIGS = ["qwen3-4b", "internlm2-1.8b"]

# Full-width counts as the dense stack's work counts gave them when
# ``work.py`` held them itself, for these positions, prompt lengths and
# decode steps: answering through the reference module moves none.
POSITIONS = [0, 1, 127, 511, 1023, 2047]
LENGTHS = [1, 64, 512, 1536, 2048]
STEPS = [[0], [5, 9, 300], [1023] * 32, [2047] * 8, list(range(0, 2048, 64))]
COUNTS = {
    "qwen3-4b": {
        "param_count": 4022468096,
        "weight_bytes": 8044936192,
        "kv_bytes_per_position": 147456,
        "decode_token_flops": [8045133824, 8045723648, 8120041472,
                               8346533888, 8648523776, 9252503552],
        "prefill_flops": [8045133824, 467069173760, 3798753738752,
                          11858561859584, 16120394153984],
        "decode_step_bytes": [8045083648, 8091679744, 12876774400,
                              10460855296, 12730498048],
    },
    "internlm2-1.8b": {
        "param_count": 1889110016,
        "weight_bytes": 3778220032,
        "kv_bytes_per_position": 98304,
        "decode_token_flops": [3399155712, 3399352320, 3424124928,
                               3499622400, 3600285696, 3801612288],
        "prefill_flops": [3399155712, 194061533184, 1572387422208,
                          4871022968832, 6597650153472],
        "decode_step_bytes": [3399262208, 3430334464, 6620516352,
                              5009805312, 6522998784],
    },
}


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
def test_dense_counts_unmoved(name):
    dm = dims_of(name)
    got = {
        "param_count": work.param_count(dm),
        "weight_bytes": work.weight_bytes(dm),
        "kv_bytes_per_position": work.kv_bytes_per_position(dm),
        "decode_token_flops": [work.decode_token_flops(dm, p)
                               for p in POSITIONS],
        "prefill_flops": [work.prefill_flops(dm, n) for n in LENGTHS],
        "decode_step_bytes": [work.decode_step_bytes(dm, s) for s in STEPS],
    }
    assert got == COUNTS[name]


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
        0 if dm.tied else dm.vocab_rows * dm.d * model.BF16)


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_bounded_by_dense_count(name):
    """Needed FLOPs lie between the matmul count and that count plus full
    (not causal) attention, so mfu over a window cannot pass what the
    chip could do."""
    dm = dims_of(name)
    mats = 2 * dm.layers * model.layer_matmul_params(dm)
    for length in (1, 64, 1536):
        f = work.prefill_flops(dm, length)
        assert mats * length <= f
        assert f <= (mats * length + 2 * dm.d * dm.vocab
                     + dm.layers * 4 * dm.heads * dm.head_dim
                     * length * length)
    assert work.decode_token_flops(dm, 0) < work.decode_token_flops(dm, 99)
    assert work.solve_bytes(8, dm.vocab) == 8 * dm.vocab * 4
