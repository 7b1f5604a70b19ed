"""Inactive lanes of the dense serving step stay bit-frozen, and the step
freezes them without touching the whole cache.

The dense serial step (``serving/scheduler.py::_step_body``) freezes an
inactive lane in two places: ``decode_step(..., write_mask=active)``
drops the one K/V row the lane would write, and ``freeze_cache_lanes``
selects the pre-step recurrent state (SSM, xLSTM) back in.  These tests
pin both halves in one ``_scheduler_step`` call, and pin that the
compiled step holds no select or copy the size of a K/V leaf.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.decode import decode_step, init_cache, prefill
from repro.models.testing import reduced_config
from repro.models.transformer import init_params
from repro.serving.sampler import SamplerConfig, SlotSamplers
from repro.serving.scheduler import _scheduler_step

B, CONTEXT, PROMPT = 4, 16, 6
ACTIVE = np.array([True, False, True, False])
# per-slot depths: different ring slots, one lane past a wrap of the ring
POS = np.array([PROMPT, PROMPT + 3, CONTEXT + 1, 2 * CONTEXT + 2], np.int32)


def _tiny_dense():
    return dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
    )


STACKS = {
    "dense": (_tiny_dense, jnp.bfloat16),
    "dense_int8": (_tiny_dense, jnp.int8),
    # SWA ring + global K/V beside SSM state
    "hymba": (lambda: reduced_config("hymba-1.5b"), jnp.bfloat16),
    # recurrent state only: no K/V at all
    "xlstm": (lambda: reduced_config("xlstm-1.3b"), jnp.bfloat16),
}


def _step_args(cfg, cache):
    greedy = SamplerConfig(greedy=True)
    return dict(
        token=jnp.arange(B, dtype=jnp.int32) + 1,
        pos=jnp.asarray(POS),
        keys=jax.vmap(jax.random.PRNGKey)(jnp.arange(B)),
        active=jnp.asarray(ACTIVE),
        cache=cache,
        slots=SlotSamplers.stack([greedy] * B),
        draft=jnp.zeros((B, 0), jnp.int32),
    )


def _statics(cfg):
    return dict(cfg=cfg, spec_k=5, rounds=8, backend="jnp",
                enable=(False, False, False), top_k_static=None,
                greedy_only=True)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_inactive_lanes_frozen_active_lanes_stepped(stack):
    make_cfg, kv_dtype = STACKS[stack]
    cfg = make_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                 cfg.vocab)
    _, cache = prefill(cfg, params, prompts, CONTEXT, kv_dtype=kv_dtype)
    before = jax.tree_util.tree_map(np.asarray, cache)
    args = _step_args(cfg, cache)

    _, unmasked = jax.jit(functools.partial(decode_step, cfg))(
        params, args["token"], args["pos"], cache)
    unmasked = jax.tree_util.tree_map(np.asarray, unmasked)

    # the step donates its state: hand it copies
    args = jax.tree_util.tree_map(jnp.array, args)
    *_, after, _, _ = _scheduler_step(params, **args, **_statics(cfg))

    leaves = zip(jax.tree_util.tree_leaves_with_path(after),
                 jax.tree_util.tree_leaves(before),
                 jax.tree_util.tree_leaves(unmasked))
    n = 0
    for (path, got), old, ref in leaves:
        got = np.asarray(got)
        name = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(got[:, ~ACTIVE], old[:, ~ACTIVE],
                                      err_msg=name)
        np.testing.assert_array_equal(got[:, ACTIVE], ref[:, ACTIVE],
                                      err_msg=name)
        # the active lanes did step: the check above is not vacuous
        assert not np.array_equal(got[:, ACTIVE], old[:, ACTIVE]), name
        n += 1
    assert n == len(jax.tree_util.tree_leaves(
        init_cache(cfg, B, CONTEXT, kv_dtype)))


def _instructions(hlo: str):
    """(opcode, result dims) of every instruction of an HLO module."""
    pat = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    for line in hlo.splitlines():
        m = pat.match(line)
        if m:
            yield m.group(2), tuple(int(d) for d in m.group(1).split(",")
                                    if d)


def test_dense_step_has_no_cache_sized_select_or_copy():
    """Freezing inactive lanes costs O(B) rows, not a pass over the
    cache: the optimized HLO of the dense step holds no select, and no
    copy, whose result has a K/V leaf's shape (in any element type: the
    CPU compiler widens bf16 selects to f32)."""
    cfg = _tiny_dense()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = init_cache(cfg, B, CONTEXT)
    hlo = _scheduler_step.lower(
        params, **_step_args(cfg, cache), **_statics(cfg),
    ).compile().as_text()
    kv_shapes = {leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}
    found = [(op, dims) for op, dims in _instructions(hlo)
             if dims in kv_shapes]
    assert found, "no instruction carries a K/V leaf: the pattern is stale"
    assert not [f for f in found if f[0] in ("select", "copy")], found
