"""A second reference module, for the tests: the dense stack of
``model.py`` with a bias on the q, k and v projections, as the program's
``qwen1.5-4b`` layer has it (``cfg.qkv_bias``: ``bq``, ``bk``, ``bv``,
added before RoPE).  ``data/qwen1.5-4b.json`` names it with
``"reference": "tests.qkv_bias"``; no harness file names it.  It takes
every piece it shares with the dense stack from ``model.py``."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

import model
from model import (decode_token_flops, kv_bytes_per_position,  # noqa: F401
                   prefill_flops, set_fields)


@dataclasses.dataclass(frozen=True)
class Dims(model.Dims):
    """The dense sizes; a class of this module's, so that ``work.py`` and
    ``check.py`` find this module by the ``Dims`` they hold."""


def program_fields(dm: Dims) -> dict:
    return dict(model.program_fields(dm), qkv_bias=True)


def _bias_params(dm: Dims) -> int:
    return dm.layers * (dm.heads + 2 * dm.kv_heads) * dm.head_dim


def weight_shapes(dm: Dims) -> dict:
    tree = model.weight_shapes(dm)
    L, hd = dm.layers, dm.head_dim
    tree["runs"][0]["attn"].update(bq=(L, dm.heads * hd),
                                   bk=(L, dm.kv_heads * hd),
                                   bv=(L, dm.kv_heads * hd))
    return tree


def make_weights(dm: Dims, key, dtype=jnp.bfloat16):
    return model.init_weights(weight_shapes(dm), key, dtype)


def project(dm: Dims, quant, h, a):
    q, k, v = model.project(dm, quant, h, a)

    def biased(x, b):
        return x + jnp.asarray(b, jnp.float32).reshape(x.shape[1:])

    return biased(q, a["bq"]), biased(k, a["bk"]), biased(v, a["bv"])


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def forward(w, tokens, *, dm: Dims, quant: str | None = None):
    return model.logits(w, tokens, dm, quant, project)


def param_count(dm: Dims) -> int:
    return model.param_count(dm) + _bias_params(dm)


def weight_bytes(dm: Dims) -> int:
    return param_count(dm) * model.BF16


def decode_step_bytes(dm: Dims, positions: list[int]) -> int:
    """The dense step's bytes and one read of each bias."""
    return (model.decode_step_bytes(dm, positions)
            + _bias_params(dm) * model.BF16)
