"""Pallas TPU kernel: multi-threshold count over a tiled vocab.

The paper's "function evaluation" for the LM threshold solves is
``count(logits > tau)`` — one pass over the vocab.  Runahead bisection asks
for that count at 2**k - 1 candidate thresholds per round; this kernel
answers ALL candidates in a single tiled sweep, so the speculative width
(the paper's thread count) rides along the VPU lane dimension for free.

Layout (TPU target):
  * grid = (B, V // BLOCK_V): one batch row per grid row, vocab tiled.
  * operands are viewed as (B, 1, ·) with the batch dim squeezed out of
    every block: Mosaic requires a block's last two dims to be (8, 128)
    multiples or the whole array dims, and a (1, BLOCK_V) block over a
    (B, V) array is neither once B > 1.
  * logits block (1, BLOCK_V) streamed HBM -> VMEM per grid step.
  * taus block (1, M_pad) resident per row (M_pad = lane-padded candidates —
    the paper's false-sharing 2-D padding becomes lane alignment here).
  * out block (1, M_pad) revisited across the vocab axis: zeroed at the
    first tile, accumulated afterwards (standard Pallas reduction pattern).

Vocab padding: the wrapper pads logits with -inf, which can never exceed a
finite threshold, so padded lanes contribute zero to every count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks

BLOCK_V = blocks.DEFAULT_BLOCK_V   # legacy default vocab tile per grid step
LANE = blocks.LANE                 # TPU lane width; candidate dim padded


def _kernel(logits_ref, taus_ref, out_ref):
    v_step = pl.program_id(1)

    @pl.when(v_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = logits_ref[...]                       # (1, block_v)
    taus = taus_ref[...]                          # (1, M_pad)
    # (1, M_pad, BLOCK_V) compare — fused by Mosaic into VPU ops; the
    # reduction folds the vocab tile into the per-candidate partial count.
    hits = block[:, None, :] > taus[:, :, None]
    out_ref[...] += jnp.sum(hits, axis=-1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def multi_count(logits: jax.Array, taus: jax.Array, *,
                block_v: int | None = None, interpret: bool = False):
    """counts[b, m] = #{v : logits[b, v] > taus[b, m]}.

    logits: (B, V) float32;  taus: (B, M) float32  ->  (B, M) float32.
    ``block_v`` is the vocab tile per grid step (lane-clamped; None =
    the legacy :data:`BLOCK_V`).  Counts are order-invariant integer
    sums, so the result is BIT-identical for every block size.
    """
    B, V = logits.shape
    _, M = taus.shape
    block = blocks.clamp_block_v(block_v, V)
    m_pad = blocks.lane_pad(M)
    v_pad, n_steps = blocks.grid_v(V, block)
    logits_p = jnp.pad(logits, ((0, 0), (0, v_pad - V)),
                       constant_values=-jnp.inf)
    # Padded candidates get +inf thresholds -> count 0, discarded below.
    taus_p = jnp.pad(taus, ((0, 0), (0, m_pad - M)), constant_values=jnp.inf)

    out = pl.pallas_call(
        _kernel,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((None, 1, block), lambda b, v: (b, 0, v)),
            pl.BlockSpec((None, 1, m_pad), lambda b, v: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, m_pad), lambda b, v: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, m_pad), jnp.float32),
        interpret=interpret,
    )(logits_p[:, None, :], taus_p[:, None, :])
    return out[:, 0, :M]
