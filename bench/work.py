"""Operations and bytes the served model needs, from its sizes alone.

These are the least any implementation must do: attention is counted over
live positions only, prefill computes logits for its last position only,
and a decode step reads each weight once, the live keys and values once,
and writes one key and value row per live slot.  A share of a peak built
on them cannot pass 100% unless the time leaves out part of the work.

Each count is the reference module's that made ``dm`` (``spec.py``), so a
metric reader asks here whatever the architecture.
"""
from __future__ import annotations

from spec import reference_of

F32 = 4


def param_count(dm) -> int:
    """Every weight: matrices, norm scales, embedding and unembedding."""
    return reference_of(dm).param_count(dm)


def weight_bytes(dm) -> int:
    return reference_of(dm).weight_bytes(dm)


def kv_bytes_per_position(dm) -> int:
    """The cache one position takes over all layers."""
    return reference_of(dm).kv_bytes_per_position(dm)


def decode_token_flops(dm, pos: int) -> int:
    """One token at position ``pos`` (attending to pos + 1 keys)."""
    return reference_of(dm).decode_token_flops(dm, pos)


def prefill_flops(dm, length: int) -> int:
    """A prompt of ``length`` tokens, causal, logits of the last only."""
    return reference_of(dm).prefill_flops(dm, length)


def decode_step_bytes(dm, positions: list[int]) -> int:
    """One decode step over live slots feeding the tokens at
    ``positions``."""
    return reference_of(dm).decode_step_bytes(dm, positions)


def solve_bytes(batch: int, vocab: int) -> int:
    """One read of the (batch, vocab) float32 logits: the least one
    threshold solve reads."""
    return batch * vocab * F32
