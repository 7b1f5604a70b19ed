"""Pallas TPU kernel: FUSED multi-round runahead top-k threshold solve.

Beyond-paper optimisation (DESIGN.md §2.1): runahead bisection reduces
*rounds* (n -> n/k); this kernel additionally makes every round after the
first **HBM-free** by keeping the batch row's logits resident in VMEM and
running the whole round loop inside the kernel.  The un-fused path streams
the vocab from HBM once per round (rounds × V × 4 bytes); the fused path
streams it exactly once.

  HBM traffic:  unfused  = rounds · V · 4 B   per row
                fused    =           V · 4 B   per row      (rounds× less)

VMEM budget: one row of a 152 k vocab in f32 is 608 KiB — comfortably
VMEM-resident.  The row is laid out as a dense (V_pad / 128, 128) tile so
it fills whole (8, 128) vregs; a (1, V) row would pad every vreg to eight
sublanes and cost eight times the VMEM.

Grid = (B,): one batch row per program.  Outputs the final (lo, hi) bracket
of the k-th largest logit, lane 0 / lane 1 of a lane-padded output row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks

LANE = blocks.LANE


def _midpoint_grid(lo, hi, spec_k: int):
    """2**spec_k + 1 bisection-tree grid points (each a (1, 1) value)."""
    n = 1 << spec_k
    pts = [None] * (n + 1)
    pts[0], pts[n] = lo, hi
    for level in range(1, spec_k + 1):
        d = 1 << (spec_k - level)
        for m in range(d, n, 2 * d):
            pts[m] = (pts[m - d] + pts[m + d]) / 2
    return pts


def _total(x):
    """Sum of a 2-D tile as a (1, 1) value."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _make_kernel(k_target: int, rounds: int, spec_k: int, v_real: int):
    n = 1 << spec_k

    def kernel(logits_ref, out_ref):
        row = logits_ref[...]                          # (R, LANE) VMEM
        # Only the first v_real elements are real logits; the pad is -inf,
        # which no finite threshold counts, but it must not set the bracket.
        flat = (jax.lax.broadcasted_iota(jnp.int32, row.shape, 0) * LANE
                + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1))
        valid = flat < v_real
        lo0 = jnp.min(jnp.min(jnp.where(valid, row, jnp.inf), axis=0,
                              keepdims=True), axis=1, keepdims=True) - 1.0
        hi0 = jnp.max(jnp.max(jnp.where(valid, row, -jnp.inf), axis=0,
                              keepdims=True), axis=1, keepdims=True) + 1.0
        kf = jnp.float32(k_target)
        # one lane per grid point 0..n
        lanes = jax.lax.broadcasted_iota(
            jnp.int32, (1, blocks.lane_pad(n + 1)), 1)

        def sign_at(tau):
            # sign bit of f(tau) = k - count(> tau): 1.0 when negative
            count = _total(jnp.where(row > tau, 1.0, 0.0))
            return jnp.where(kf - count < 0, 1.0, 0.0)

        def pick(vec, idx):
            # vec[idx] for a traced (1, 1) index, by a one-hot lane max
            # (Mosaic has no dynamic_slice on values)
            return jnp.max(jnp.where(lanes == idx, vec, -jnp.inf), axis=1,
                           keepdims=True)

        def round_body(_, carry):
            lo, hi, sl = carry
            pts = _midpoint_grid(lo, hi, spec_k)
            # All 2**k - 1 speculative evaluations against the VMEM-resident
            # row — the paper's helper threads, zero extra HBM traffic.
            # Lane i of pts_vec / sign_vec holds grid point i and its sign.
            pts_vec = jnp.zeros(lanes.shape, jnp.float32)
            for m in range(n + 1):
                pts_vec = jnp.where(lanes == m, pts[m], pts_vec)
            sign_vec = jnp.where(lanes == 0, sl, 0.0)
            for m in range(1, n):
                sign_vec = jnp.where(lanes == m, sign_at(pts[m]), sign_vec)
            # Serial-exact index walk, statically unrolled spec_k steps with
            # traced index selects (the path is data-dependent).
            li = jnp.zeros((1, 1), jnp.int32)
            hi_i = jnp.full((1, 1), n, jnp.int32)
            s_cur = sl
            for _step in range(spec_k):
                mid = jax.lax.shift_right_logical(li + hi_i, 1)
                s_m = pick(sign_vec, mid)
                go_left = s_cur != s_m
                hi_i = jnp.where(go_left, mid, hi_i)
                li = jnp.where(go_left, li, mid)
                s_cur = jnp.where(go_left, s_cur, s_m)
            return pick(pts_vec, li), pick(pts_vec, hi_i), s_cur

        lo_f, hi_f, _ = jax.lax.fori_loop(
            0, rounds, round_body, (lo0, hi0, sign_at(lo0))
        )
        out_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
        out_ref[...] = jnp.where(out_lanes == 0, lo_f,
                                 jnp.where(out_lanes == 1, hi_f, 0.0))

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k_target", "rounds", "spec_k", "block_v", "interpret"),
)
def runahead_topk_threshold(
    logits: jax.Array,
    *,
    k_target: int,
    rounds: int = 8,
    spec_k: int = 5,
    block_v: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused solve: logits (B, V) -> (lo, hi) each (B,), bracketing the
    k-th largest value per row.  rounds × spec_k serial-equivalent steps.

    The row stays whole-row VMEM-resident (that is this kernel's point);
    ``block_v`` only sets the resident row's padding granularity — the
    lane-masked count is invariant to it, so results are BIT-identical
    for every legal value (None = :data:`LANE`, the minimum padding).
    """
    B, V = logits.shape
    v_pad = blocks.pad_to(V, blocks.clamp_block_v(block_v or LANE, V))
    logits_p = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, v_pad - V)),
                       constant_values=-jnp.inf)
    rows = v_pad // LANE

    out = pl.pallas_call(
        _make_kernel(k_target, rounds, spec_k, V),
        grid=(B,),
        in_specs=[pl.BlockSpec((None, rows, LANE), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, LANE), jnp.float32),
        interpret=interpret,
    )(logits_p.reshape(B, rows, LANE))
    return out[:, 0, 0], out[:, 0, 1]
