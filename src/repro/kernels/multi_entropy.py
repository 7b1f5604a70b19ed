"""Pallas TPU kernel: softmax entropy at MANY temperatures, tiled vocab.

The entropy-calibrated-temperature solve's "function evaluation" is
``H(softmax(z / T))`` — one pass over the vocab per candidate T.  Runahead
bisection asks for H at 2**k - 1 candidate temperatures per round; this
kernel answers ALL candidates for ALL batch rows in one tiled sweep.

Entropy needs two coupled reductions per candidate (a normaliser and an
expectation), so the kernel accumulates the pair

  s[m] = sum_v exp(z_v / T_m)            (normaliser)
  w[m] = sum_v (z_v / T_m) exp(z_v / T_m)

across vocab tiles into a revisited (2, M_pad) output block (batch dim
squeezed out of every block, as in ``multi_count``); the wrapper
finalises ``H = log(s) - w / s``.  The row max is subtracted up front (in
the wrapper), which makes every exp argument <= 0 — no overflow, no online
max-rescaling needed, and H is shift-invariant so the result is exact.

Padding: vocab lanes are padded with a -1e30 sentinel: exp underflows to
exactly 0 and the w-contribution is 0 * finite = 0, for ANY candidate
temperature in the bracket.  Padded candidate lanes get T = 1 (harmless;
discarded by the wrapper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks

BLOCK_V = blocks.DEFAULT_BLOCK_V   # legacy default vocab tile per grid step
LANE = blocks.LANE                 # TPU lane width; candidate dim padded

_PAD_SENTINEL = -1e30


def _kernel(z_ref, ts_ref, out_ref):
    v_step = pl.program_id(1)

    @pl.when(v_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...]                                # (1, block_v), max-shifted
    ts = ts_ref[...]                              # (1, M_pad)
    zt = z[:, None, :] / ts[:, :, None]           # (1, M_pad, block_v)
    e = jnp.exp(zt)
    s = jnp.sum(e, axis=-1)                       # (1, M_pad)
    w = jnp.sum(zt * e, axis=-1)                  # (1, M_pad)
    out_ref[0:1, :] += s.astype(out_ref.dtype)
    out_ref[1:2, :] += w.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def multi_entropy_moments(
    z_shifted: jax.Array, ts: jax.Array, *,
    block_v: int | None = None, interpret: bool = False
):
    """The kernel's raw accumulator pair for PRE-SHIFTED logits.

    z_shifted: (B, V) f32 with every element <= 0 (caller subtracts a row
    max — the LOCAL max in the single-device wrapper below, the pmax'd
    GLOBAL max in the vocab-sharded solver backend, which psums the
    returned partials across shards before finalising H).
    Returns (s, w), each (B, M): s[m] = sum_v exp(z_v / T_m),
    w[m] = sum_v (z_v / T_m) exp(z_v / T_m).
    ``block_v`` is the vocab tile per grid step (lane-clamped; None =
    the legacy :data:`BLOCK_V`); like ``multi_mass`` the float partials
    regroup with the block, so cross-block parity is allclose.
    """
    B, V = z_shifted.shape
    _, M = ts.shape
    block = blocks.clamp_block_v(block_v, V)
    m_pad = blocks.lane_pad(M)
    v_pad, n_steps = blocks.grid_v(V, block)
    z_p = jnp.pad(z_shifted.astype(jnp.float32), ((0, 0), (0, v_pad - V)),
                  constant_values=_PAD_SENTINEL)
    ts_p = jnp.pad(ts, ((0, 0), (0, m_pad - M)), constant_values=1.0)

    acc = pl.pallas_call(
        _kernel,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((None, 1, block), lambda b, v: (b, 0, v)),
            pl.BlockSpec((None, 1, m_pad), lambda b, v: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 2, m_pad), lambda b, v: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 2, m_pad), jnp.float32),
        interpret=interpret,
    )(z_p[:, None, :], ts_p[:, None, :])
    return acc[:, 0, :M], acc[:, 1, :M]


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def multi_entropy(
    logits: jax.Array, ts: jax.Array, *,
    block_v: int | None = None, interpret: bool = False
):
    """H[b, m] = entropy of softmax(logits[b] / ts[b, m]).

    logits: (B, V) float32;  ts: (B, M) float32 (positive)  ->  (B, M) f32.
    """
    z = logits.astype(jnp.float32)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    s, w = multi_entropy_moments(z, ts, block_v=block_v, interpret=interpret)
    return jnp.log(s) - w / s
