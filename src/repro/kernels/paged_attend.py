"""Pallas kernel: paged decode/verify attention over a page-table KV cache.

The serving stack's paged cache (DESIGN.md §13) stores K/V in a flat page
pool ``(n_pages, page_size, n_kv, head_dim)``; a slot's logical ring
buffer is the concatenation of the pages its table row names.  The jnp
reference path materialises that gather to (B, context, ...) every step —
on real hardware that is a full cache copy per token.  This kernel never
materialises it: each program streams its slot's page chain page by page
(the page id read from the slot's table row), keeping the online-softmax
state (m, l, acc) and one (L·R, page_size) score tile in VMEM, the same
shape of win as ``flash_fwd`` over the dense layout —

    bytes(paged attend) = Q + chain pages touched + O

Grid: (B, chain pages).  The page table and positions are
scalar-prefetched into SMEM, and each page BlockSpec's index map reads
the table, so the pipeline DMAs exactly the slot's chain, one whole
(page_size, n_kv, head_dim) page per step, the next page's DMA in flight
while this one is folded in — never the pool.  (The block cannot be one
head's slice: Mosaic needs a block's last two dims tile-aligned or whole,
and the pool's are (n_kv, head_dim).)  Every kv head is folded in the
same program, each with its own online-softmax state in VMEM scratch
across the page axis.  GQA rides inside: the q block carries each
head's ``n_rep`` query heads for all L verify positions, so a draft run
crossing a page boundary is just two steps of the page axis.
Masking reproduces dense ``decode_attend``'s per-depth ring validity mask
(position p_s attendable iff 0 <= p_s <= pos + l), which also kills the
tail of a final partial page (linear index >= context) and every
null-page row.

Off-TPU the kernel runs in interpret mode like every other kernel here
(kernels/ops.py gates).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tab_ref, pos_ref, q_ref, *refs, context, page_size, n_draft,
            n_rep, scale, pages_per_step):
    """One grid step folds ``pages_per_step`` pages of slot b's chain into
    the online-softmax state of every kv head.

    ``tab_ref``/``pos_ref`` are the scalar-prefetched page table and
    positions (SMEM); the page blocks arrive already DMA'd by their
    BlockSpecs, whose index maps read the same table.
    """
    d = pages_per_step
    k_refs, v_refs = refs[:d], refs[d:2 * d]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * d:]
    C, P, L, R = context, page_size, n_draft, n_rep
    n_kv = q_ref.shape[0]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # Rows are l-major: row r holds draft depth l = r // R.  Each depth's
    # ring slot and wrap count come from scalar SMEM arithmetic and are
    # spread over its R rows by selects.
    row = jax.lax.broadcasted_iota(jnp.int32, (L * R, 1), 0)
    pos = pos_ref[b]
    pq = jnp.zeros((L * R, 1), jnp.int32)
    slot_q = jnp.zeros((L * R, 1), jnp.int32)
    wraps = jnp.zeros((L * R, 1), jnp.int32)
    for l in range(L):
        rows_l = (row >= l * R) & (row < (l + 1) * R)
        pq = jnp.where(rows_l, pos + l, pq)
        slot_q = jnp.where(rows_l, (pos + l) % C, slot_q)
        wraps = jnp.where(rows_l, (pos + l) // C, wraps)

    for h in range(n_kv):
        q = q_ref[h].astype(jnp.float32)                     # (L*R, D)
        m, l_sum, acc = m_ref[h], l_ref[h], acc_ref[h]
        for i in range(d):
            # Page j*d + i of the chain.  Past the chain's end the index
            # map clamps the table read but ``lin`` keeps the true index,
            # so every lane of such a page has lin >= C and masks out (p
            # underflows to 0, corr = 1 — state untouched, which is why
            # results stay BIT-identical for every pages_per_step).
            k_pg = k_refs[i][:, h, :].astype(jnp.float32)    # (P, D)
            v_pg = v_refs[i][:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_pg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            lin = (j * d + i) * P + jax.lax.broadcasted_iota(
                jnp.int32, (1, P), 1)
            # dense decode_attend's ring validity at depth pos+l, plus the
            # partial-last-page cut (lin >= C holds no ring slot at all)
            p_s = jnp.where(lin <= slot_q, wraps * C + lin,
                            (wraps - 1) * C + lin)           # (L*R, P)
            valid = (p_s >= 0) & (p_s <= pq) & (lin < C)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_sum = l_sum * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p, v_pg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = m_new
        m_ref[h], l_ref[h], acc_ref[h] = m, l_sum, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("context", "pages_per_step", "interpret"))
def paged_attend(
    pool_k: jax.Array,       # (n_pages, P, n_kv, hd)
    pool_v: jax.Array,
    table: jax.Array,        # (B, max_chain) int32 page ids
    pos: jax.Array,          # (B,) int32 position of q[:, 0]
    q: jax.Array,            # (B, L, n_heads, hd) — rope already applied
    *,
    context: int,
    pages_per_step: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged decode/verify attention; returns (B, L, n_heads, hd).

    The drafted K/V rows must already be written into the pool (the
    caller scatters them first, exactly as the dense verify path writes
    its ring rows before attending).

    ``pages_per_step`` is the number of pages each grid step folds in,
    each its own BlockSpec so their DMAs are in flight together.  Results
    are bit-identical for every value; see the kernel comment for the
    trailing-page argument.
    """
    n_pages, P, nkv, D = pool_k.shape
    B, L, nq, _ = q.shape
    R = nq // nkv
    n_chain = table.shape[1]
    d = min(max(1, int(pages_per_step)), n_chain)
    # kv-major head grouping, the same layout _verify_sdpa reduces in
    qg = (q.reshape(B, L, nkv, R, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, nkv, L * R, D))
    kern = functools.partial(
        _kernel, context=context, page_size=P, n_draft=L, n_rep=R,
        scale=1.0 / math.sqrt(D), pages_per_step=d,
    )

    def page_spec(i):
        # one whole page (all kv heads: the block's last two dims must be
        # the pool's own (n_kv, head_dim)), located by table lookup
        return pl.BlockSpec(
            (None, P, nkv, D),
            lambda b, j, tab, _pos: (
                tab[b, jnp.minimum(j * d + i, n_chain - 1)], 0, 0, 0))

    head_spec = pl.BlockSpec((None, nkv, L * R, D),
                             lambda b, j, _tab, _pos: (b, 0, 0, 0))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, -(-n_chain // d)),
            in_specs=[head_spec] + [page_spec(i) for i in range(d)] * 2,
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((nkv, L * R, 1), jnp.float32),
                pltpu.VMEM((nkv, L * R, 1), jnp.float32),
                pltpu.VMEM((nkv, L * R, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, L * R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table.astype(jnp.int32), jnp.asarray(pos, jnp.int32).reshape(B),
      qg, *([pool_k] * d), *([pool_v] * d))
    return (out.reshape(B, nkv, L, R, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, L, nq, D))
