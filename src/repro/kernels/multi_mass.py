"""Pallas TPU kernel: multi-threshold probability MASS over a tiled vocab.

The nucleus (top-p) solve's "function evaluation" is
``mass(probs >= tau) = sum of probs at or above tau`` — one pass over the
vocab.  Runahead bisection asks for that mass at 2**k - 1 candidate
thresholds per round; this kernel answers ALL candidates for ALL batch rows
in a single tiled sweep, the mass-analogue of ``multi_count`` (same layout:
grid = (B, V // BLOCK_V), operands viewed as (B, 1, ·) with the batch
dim squeezed out of each block, logits tile streamed HBM -> VMEM,
lane-padded candidate row resident, output block revisited/accumulated
over vocab tiles).

Padding: probs are padded with -1.0 (a probability can never be negative,
so padded lanes are below every candidate threshold and contribute zero
mass — including to the engine's bracket-sign probe at tau = 0).  Padded
candidates get +inf thresholds -> zero mass, discarded by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks

BLOCK_V = blocks.DEFAULT_BLOCK_V   # legacy default vocab tile per grid step
LANE = blocks.LANE                 # TPU lane width; candidate dim padded


def _kernel(probs_ref, taus_ref, out_ref):
    v_step = pl.program_id(1)

    @pl.when(v_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = probs_ref[...]                        # (1, block_v)
    taus = taus_ref[...]                          # (1, M_pad)
    keep = block[:, None, :] >= taus[:, :, None]  # (1, M_pad, block_v)
    out_ref[...] += jnp.sum(
        jnp.where(keep, block[:, None, :], 0.0), axis=-1
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def multi_mass(probs: jax.Array, taus: jax.Array, *,
               block_v: int | None = None, interpret: bool = False):
    """mass[b, m] = sum of probs[b, v] where probs[b, v] >= taus[b, m].

    probs: (B, V) float32;  taus: (B, M) float32  ->  (B, M) float32.
    ``block_v`` is the vocab tile per grid step (lane-clamped; None =
    the legacy :data:`BLOCK_V`).  Partial sums accumulate per tile, so
    different blocks regroup the float reduction — allclose across
    blocks, bit-identical only at a fixed block.
    """
    B, V = probs.shape
    _, M = taus.shape
    block = blocks.clamp_block_v(block_v, V)
    m_pad = blocks.lane_pad(M)
    v_pad, n_steps = blocks.grid_v(V, block)
    probs_p = jnp.pad(probs, ((0, 0), (0, v_pad - V)), constant_values=-1.0)
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
    )(probs_p[:, None, :], taus_p[:, None, :])
    return out[:, 0, :M]
