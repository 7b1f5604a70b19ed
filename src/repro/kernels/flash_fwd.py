"""Pallas TPU kernel: flash-attention forward (online softmax, VMEM tiles).

The §Perf iterations showed the pure-JAX chunked attention still pays
fusion-boundary HBM traffic for every score chunk (~134 MB per (q,kv) tile
on deepseek train).  On the TPU target this kernel keeps the running
(m, l, acc) state and the score tile entirely in VMEM: HBM traffic becomes
Q + K + V + O only —

    bytes(attention) = 4 * S * D * heads * dtype    (+ K/V refetch per
                                                      q-tile when S > VMEM)

Grid: (B, H, n_q).  Each program loads its q tile and streams the K/V
rows for its (batch, head) from VMEM-resident blocks, iterating kv tiles
with a fori_loop and the usual online-softmax rescaling.  Causal masking
derives from the q-tile index; `window > 0` adds the SWA band.

Backward: flash needs a dedicated bwd kernel (dQ/dK/dV with recomputed
probabilities).  Here backward falls back to the pure-JAX chunked path via
jax.custom_vjp — numerically identical, and the remat'd training step
already recomputes forward, so the kernel still eliminates the forward's
score traffic.  A Mosaic bwd kernel is the documented next step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, q_chunk, kv_chunk, seq, window,
            scale):
    qi = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32)            # (qc, D)
    n_kv = seq // kv_chunk
    q_start = qi * q_chunk
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_chunk, 1), 0)

    def body(ki, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(
            k_ref[0, :, 0, :], ki * kv_chunk, kv_chunk, 0
        ).astype(jnp.float32)                            # (kc, D)
        v = jax.lax.dynamic_slice_in_dim(
            v_ref[0, :, 0, :], ki * kv_chunk, kv_chunk, 0
        ).astype(jnp.float32)
        s = q @ k.T * scale                              # (qc, kc) in VMEM
        kpos = ki * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_chunk), 1
        )
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v
        return m_new, l, acc

    m0 = jnp.full((q_chunk, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q_chunk, 1), jnp.float32)
    a0 = jnp.zeros((q_chunk, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def _flash_fwd_pallas(q, k, v, *, q_chunk, kv_chunk, window, interpret):
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    grid = (B, H, S // q_chunk)
    kern = functools.partial(
        _kernel, q_chunk=q_chunk, kv_chunk=kv_chunk, seq=S, window=window,
        scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_chunk, 1, D), lambda b, h, qi: (b, qi, h, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, h, qi: (b, 0, h, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, h, qi: (b, 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_chunk, 1, D),
                               lambda b, h, qi: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_fwd(q, k, v, q_chunk, kv_chunk, window, interpret):
    """Causal flash attention; q/k/v: (B, S, H, D) with equal head counts
    (callers repeat/pad GQA heads first).  S must divide by the chunks.
    ``interpret`` comes from ``kernels.ops.interpret_mode()``."""
    return _flash_fwd_pallas(q, k, v, q_chunk=q_chunk, kv_chunk=kv_chunk,
                             window=window, interpret=interpret)


def _fwd(q, k, v, q_chunk, kv_chunk, window, interpret):
    out = flash_fwd(q, k, v, q_chunk, kv_chunk, window, interpret)
    return out, (q, k, v)


def _bwd(q_chunk, kv_chunk, window, interpret, res, g):
    from repro.models.attention import flash_attend

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attend(
            q_, k_, v_, causal=True, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk,
        ),
        q, k, v,
    )
    return vjp(g)


flash_fwd.defvjp(_fwd, _bwd)
