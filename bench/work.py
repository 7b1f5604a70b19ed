"""Operations and bytes the served model needs, from its sizes alone.

These are the least any implementation must do: attention is counted over
live positions only, prefill computes logits for its last position only,
and a decode step reads each weight once, the live keys and values once,
and writes one key and value row per live slot.  A share of a peak built
on them cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

from model import Dims

BF16 = 2
F32 = 4


def layer_matmul_params(dm: Dims) -> int:
    d, hd = dm.d, dm.head_dim
    attn = d * dm.heads * hd * 2 + d * dm.kv_heads * hd * 2
    return attn + 3 * d * dm.ff


def param_count(dm: Dims) -> int:
    """Every weight: matrices, norm scales, embedding and unembedding."""
    norms = 2 * dm.d + (2 * dm.head_dim if dm.qk_norm else 0)
    table = dm.vocab_rows * dm.d
    return (dm.layers * (layer_matmul_params(dm) + norms) + dm.d
            + table * (1 if dm.tied else 2))


def weight_bytes(dm: Dims) -> int:
    return param_count(dm) * BF16


def kv_bytes_per_position(dm: Dims) -> int:
    """K and V of one position over all layers, bf16."""
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * BF16


def _attn_flops(dm: Dims, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` positions."""
    return dm.layers * 4 * dm.heads * dm.head_dim * keys


def decode_token_flops(dm: Dims, pos: int) -> int:
    """One token at position ``pos`` (attending to pos + 1 keys)."""
    return (2 * dm.layers * layer_matmul_params(dm) + 2 * dm.d * dm.vocab
            + _attn_flops(dm, pos + 1))


def prefill_flops(dm: Dims, length: int) -> int:
    """A prompt of ``length`` tokens, causal, logits of the last only."""
    causal_keys = length * (length + 1) // 2
    return (2 * dm.layers * layer_matmul_params(dm) * length
            + 2 * dm.d * dm.vocab + _attn_flops(dm, causal_keys))


def decode_step_bytes(dm: Dims, positions: list[int]) -> int:
    """One decode step over live slots feeding the tokens at
    ``positions``: every weight once (the gathered embedding rows instead
    of the whole table where the unembedding is a table of its own), the
    K/V of the positions before each token, and one K/V row written per
    slot."""
    weights = weight_bytes(dm)
    if not dm.tied:
        weights -= dm.vocab_rows * dm.d * BF16
        weights += len(positions) * dm.d * BF16
    kv = kv_bytes_per_position(dm)
    return weights + sum(p * kv for p in positions) + len(positions) * kv


def solve_bytes(batch: int, vocab: int) -> int:
    """One read of the (batch, vocab) float32 logits: the least one
    threshold solve reads."""
    return batch * vocab * F32
