"""Grouped-query attention with the assigned archs' variants.

Covers: MHA/GQA/MQA (n_kv_heads), qk-norm (qwen3, chameleon), QKV bias
(qwen1.5), RoPE / learned positions (whisper), full-causal and
sliding-window masks (hymba), non-causal encoder and cross attention
(whisper), and a ring-buffer KV cache for decode.

Sharding: heads/kv-heads carry the "heads"/"kv_heads" logical axes (tensor
parallel over `model`); batch carries "batch".  Softmax in f32.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rmsnorm

Params = dict
NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, nq * hd, dtype),
        "wk": dense_init(kk, d, nkv * hd, dtype),
        "wv": dense_init(kv, d, nkv * hd, dtype),
        "wo": dense_init(ko, nq * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dtype)}
    del cross  # same parameter shapes; callers pass encoder output as kv_src
    return p


def _project_q(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
    return q


def _project_kv(p: Params, cfg: ModelConfig, x: jax.Array):
    B, S, _ = x.shape
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return k, v


def _sdpa(q, k, v, mask, n_rep: int):
    """q: (B,Sq,nq,hd) k/v: (B,Sk,nkv,hd) mask: broadcastable (B,1,Sq,Sk).

    GQA is computed by repeating K/V up to the query head count and using a
    single 4-D einsum: a (nkv, n_rep) 5-D grouping cannot be sharded by a
    single mesh axis and forces GSPMD into involuntary full remat (observed
    on qwen3 train_4k: 71 GiB temp).  The repeat is free at trace level for
    n_rep=1 and otherwise materialises transiently under remat; each device
    keeps only the kv heads its query-head shard needs when nq divides the
    model axis.
    """
    B, Sq, nq, hd = q.shape
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd))
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


import os

FLASH_MIN_SEQ = 4096     # full-materialisation path below this (tests/smoke)
Q_CHUNK = 512
KV_CHUNK = 1024
# §Perf baseline/optimised toggle: REPRO_DISABLE_FLASH=1 restores the
# full-materialisation attention for A/B dry-runs.
FLASH_ENABLED = os.environ.get("REPRO_DISABLE_FLASH") != "1"


def flash_attend(q, k, v, *, causal: bool = True, window=0,
                 q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK,
                 n_rep: int = 1):
    """Chunked online-softmax attention (flash-style, pure JAX).

    Replaces the (B, h, S, S) score materialisation with a scan over query
    chunks; each chunk runs an inner online-softmax scan over KV chunks and
    is wrapped in jax.checkpoint, so backward recomputes the chunk instead
    of storing probabilities — memory O(S·chunk) instead of O(S²).

    Sliding-window variant: when `window` is a positive python int, each
    query chunk slices only its [start - window, end) KV band (static
    length window + q_chunk), making SWA prefill O(S·window) compute AND
    memory (hymba's 29 SWA layers at 32k).

    GQA: with n_rep > 1, q has n_kv*n_rep heads while k/v keep n_kv — the
    grouped einsums never materialise repeated K/V (§Perf: a repeat that
    cannot shard over the model axis replicates GBs of K/V per layer).

    q: (B, S, Hq, hd); k, v: (B, S, Hq // n_rep, hd).  Positions are
    implicit (0..S-1): callers with nonstandard position vectors use the
    reference path.
    """
    B, S, Hq, D = q.shape
    H = Hq // n_rep          # kv heads
    R = n_rep
    scale = 1.0 / math.sqrt(D)
    pad_q = (-S) % q_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    n_q = q.shape[1] // q_chunk

    banded = bool(causal) and isinstance(window, int) and 0 < window < S
    if banded:
        band = window + q_chunk                  # static KV slice length
        pad_left = window
        k_p = jnp.pad(k, ((0, 0), (pad_left, 0), (0, 0), (0, 0)))
        v_p = jnp.pad(v, ((0, 0), (pad_left, 0), (0, 0), (0, 0)))
    else:
        pad_kv = (-S) % kv_chunk
        k_p = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v_p = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        n_kv = k_p.shape[1] // kv_chunk

    w_arr = jnp.asarray(window)

    def one_q_chunk(qi, q_c):
        """q_c: (B, q_chunk, Hq, D); qi: chunk index (traced)."""
        q_start = qi * q_chunk
        qpos = q_start + jnp.arange(q_chunk)                 # (q_chunk,)
        qf = q_c.astype(jnp.float32).reshape(B, q_chunk, H, R, D)

        def inner(carry, kv_idx_or_slice):
            m, l, o = carry
            if banded:
                k_c, v_c, kpos = kv_idx_or_slice
            else:
                ki = kv_idx_or_slice
                k_c = jax.lax.dynamic_slice_in_dim(k_p, ki * kv_chunk,
                                                   kv_chunk, 1)
                v_c = jax.lax.dynamic_slice_in_dim(v_p, ki * kv_chunk,
                                                   kv_chunk, 1)
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qf,
                           k_c.astype(jnp.float32)) * scale
            mask = jnp.ones((q_chunk, kpos.shape[0]), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
                mask &= (kpos[None, :] > qpos[:, None] - w_arr) | (w_arr <= 0)
            mask &= (kpos[None, :] >= 0) & (qpos[:, None] < S)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhrqk,bkhd->bhrqd", p.astype(v_c.dtype), v_c
            ).astype(jnp.float32)
            return (m_new, l, o), None

        m0 = jnp.full((B, H, R, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, R, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, H, R, q_chunk, D), jnp.float32)

        if banded:
            # static-length KV band [q_start, q_start + band) in the
            # left-padded array == [q_start - window, q_end) unpadded.
            k_c = jax.lax.dynamic_slice_in_dim(k_p, q_start, band, 1)
            v_c = jax.lax.dynamic_slice_in_dim(v_p, q_start, band, 1)
            kpos = q_start - window + jnp.arange(band)
            (m, l, o), _ = inner((m0, l0, o0), (k_c, v_c, kpos))
        else:
            (m, l, o), _ = jax.lax.scan(
                inner, (m0, l0, o0), jnp.arange(n_kv)
            )
        out = o / jnp.maximum(l[..., None], 1e-30)
        # (B,H,R,qc,D) -> (B,qc,H*R,D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, q_chunk, Hq, D)
        return out.astype(q.dtype)

    one_q_chunk = jax.checkpoint(one_q_chunk, prevent_cse=False)

    def outer(_, qi):
        q_c = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, 1)
        return None, one_q_chunk(qi, q_c)

    _, chunks = jax.lax.scan(outer, None, jnp.arange(n_q))
    out = chunks.swapaxes(0, 1).reshape(B, n_q * q_chunk, Hq, D)
    return out[:, :S]


def causal_mask(sq: int, sk: int, window: int = 0, offset: int = 0):
    """(1, 1, sq, sk) bool; offset = absolute position of query 0."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


def attend(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: int | jax.Array = 0,
    causal: bool = True,
    kv_src: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, S, _ = x.shape
    q = _project_q(p, cfg, x)
    kv_in = x if kv_src is None else kv_src
    k, v = _project_kv(p, cfg, kv_in)
    if not cfg.learned_pos and kv_src is None:
        q = apply_rope_heads(q, positions, cfg.rope_theta)
        k = apply_rope_heads(k, positions if kv_positions is None
                             else kv_positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    use_flash = (FLASH_ENABLED and causal and kv_src is None
                 and S >= FLASH_MIN_SEQ and isinstance(window, int))
    if use_flash:
        # chunked online-softmax path: no (S, S) score materialisation
        # (§Perf hillclimb: prefill_32k / train_4k memory term).
        from repro.distributed.sharding import logical_axis_size

        tp = max(logical_axis_size("heads"), 1)
        if tp > 1:
            # Megatron-style head padding: repeat K/V to the query head
            # count and zero-pad heads to a multiple of the TP axis so the
            # attention einsums shard (deepseek's 56 heads over 16 chips
            # otherwise replicate the whole attention per device — §Perf).
            hp = -(-cfg.n_heads // tp) * tp
            kr = jnp.repeat(k, n_rep, axis=2) if n_rep > 1 else k
            vr = jnp.repeat(v, n_rep, axis=2) if n_rep > 1 else v
            if hp != cfg.n_heads:
                padw = ((0, 0), (0, 0), (0, hp - cfg.n_heads), (0, 0))
                qp = jnp.pad(q, padw)
                kr = jnp.pad(kr, padw)
                vr = jnp.pad(vr, padw)
            else:
                qp = q
            qp = shard(qp, "batch", None, "heads", None)
            kr = shard(kr, "batch", None, "heads", None)
            vr = shard(vr, "batch", None, "heads", None)
            out = flash_attend(qp, kr, vr, causal=True, window=window)
            out = out[:, :, :cfg.n_heads]
        else:
            # no TP (tests / single device): grouped GQA flash, K/V
            # unrepeated
            out = flash_attend(q, k, v, causal=True, window=window,
                               n_rep=n_rep)
    else:
        mask = None
        if causal and kv_src is None:
            qp = positions[:, :, None]
            kp = positions[:, None, :]
            mask = kp <= qp
            # `window` may be a traced per-layer scalar (0 = global).
            w = jnp.asarray(window)
            mask &= (kp > qp - w) | (w <= 0)
            mask = mask[:, None]
        out = _sdpa(q, k, v, mask, n_rep)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = out @ p["wo"].astype(x.dtype)
    if return_kv:
        return out, (k, v)   # k already roped — matches decode cache layout
    return out


def apply_rope_heads(x, positions, theta):
    from repro.models.layers import apply_rope

    return apply_rope(x, positions, theta)


def _decode_sdpa(q, k, v, mask, n_rep: int):
    """Decode-time GQA over a seq-sharded ring cache — NO head repeat.

    Repeating K/V here would 7x the (huge) cache and force a reshard off
    the "cache_seq" layout (observed: 20 GiB temp on deepseek decode_32k).
    Instead queries group as (nkv, n_rep) and both einsums contract over
    the sharded cache axis; the only collectives are the tiny softmax
    max/sum and output partial-sum reductions.
    """
    B, Sq, nq, hd = q.shape                    # Sq == 1
    nkv = k.shape[2]
    qg = q[:, 0].reshape(B, nkv, n_rep, hd)
    scores = jnp.einsum("bhrd,bkhd->bhrk", qg, k).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd))
    scores = shard(scores, "batch", None, None, "cache_seq")
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)   # (1,1,1,C) broadcast
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrk,bkhd->bhrd", probs, v)
    return out.reshape(B, Sq, nq, hd)


# ---------------------------------------------------------------------------
# decode path (ring-buffer KV cache)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Ring-buffer cache: capacity = full seq (dense) or window (SWA).

    int8 mode (beyond-paper §Perf: halves the decode memory term): k/v are
    stored as int8 with one f16 scale per (batch, slot, kv_head); dequant
    happens on read, fused into the attention dot's epilogue on TPU so the
    HBM traffic is the int8 payload.
    """
    k: jax.Array                    # (B, C, n_kv, hd)  bf16 | int8
    v: jax.Array
    k_scale: jax.Array | None = None   # (B, C, n_kv) f16, int8 mode only
    v_scale: jax.Array | None = None

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype
                  ) -> KVCache:
    shape = (batch, capacity, cfg.n_kv_heads, cfg.head_dim)
    if dtype == jnp.int8:
        sshape = shape[:-1]
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(sshape, jnp.float16),
            v_scale=jnp.zeros(sshape, jnp.float16),
        )
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, n_kv, hd) -> int8 values + per-(B,S,n_kv) f16 scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def decode_attend(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,            # (B, 1, D) current token
    pos: jax.Array,          # () int32 shared position, or (B,) per-slot
    cache: KVCache,
    *,
    window: int | jax.Array = 0,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """One decode step: append K/V at pos (mod capacity), attend over cache.

    ``pos`` may be a scalar (lock-step batch: one-shot ``generate``) or a
    (B,) vector (continuous batching: each slot at its own depth).  The
    scalar path keeps the contiguous ``dynamic_update_slice`` write; the
    vector path scatters one ring slot per row and builds a per-row
    validity mask — same values row-for-row when the positions coincide.

    ``write_mask`` (B,) bool, per-slot positions only: a row whose mask is
    False aims its write past the ring and the scatter drops it, so that
    row's K/V (and int8 scales) come back bit-equal to ``cache``.  This is
    where the serving step freezes an inactive lane's K/V: one dropped row
    instead of a select over the whole cache
    (``models.decode.freeze_cache_lanes``).  The row still attends — over
    its cache without the new row — and its output is the caller's to
    discard.
    """
    B = x.shape[0]
    q = _project_q(p, cfg, x)                                # (B,1,nq,hd)
    k_new, v_new = _project_kv(p, cfg, x)                    # (B,1,nkv,hd)
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    pvec = pos[:, None] if per_slot else jnp.full((B, 1), pos, jnp.int32)
    if not cfg.learned_pos:
        q = apply_rope_heads(q, pvec, cfg.rope_theta)
        k_new = apply_rope_heads(k_new, pvec, cfg.rope_theta)

    C = cache.capacity
    slot = (pos % C).astype(jnp.int32)

    if per_slot:
        rows = jnp.arange(B)
        at = slot if write_mask is None else jnp.where(write_mask, slot, C)

        def write(buf, new):                     # (B,C,...) <- (B,1,...)
            return buf.at[rows, at].set(new[:, 0], mode="drop")
    else:
        if write_mask is not None:
            raise ValueError("write_mask needs per-slot (B,) positions")

        def write(buf, new):
            start = (0, slot) + (0,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, new, start)

    new_cache: KVCache
    if cache.quantized:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        k_i8 = shard(write(cache.k, kq), "batch", "cache_seq", "kv_heads",
                     None)
        v_i8 = shard(write(cache.v, vq), "batch", "cache_seq", "kv_heads",
                     None)
        k_sc = write(cache.k_scale, ks)
        v_sc = write(cache.v_scale, vs)
        new_cache = KVCache(k=k_i8, v=v_i8, k_scale=k_sc, v_scale=v_sc)
        k = _dequantize_kv(k_i8, k_sc, x.dtype)
        v = _dequantize_kv(v_i8, v_sc, x.dtype)
    else:
        k = shard(write(cache.k, k_new), "batch", "cache_seq", "kv_heads",
                  None)
        v = shard(write(cache.v, v_new), "batch", "cache_seq", "kv_heads",
                  None)
        new_cache = KVCache(k=k, v=v)

    # validity: ring slot s holds absolute position p_s; it is attendable iff
    # p_s <= pos and p_s > pos - C (ring eviction) and (SWA) p_s > pos - w.
    slots = jnp.arange(C)
    w = jnp.asarray(window)
    if per_slot:
        slots = slots[None, :]                               # (1, C)
        pos_c, slot_c = pos[:, None], slot[:, None]          # (B, 1)
        wraps = (pos_c // C).astype(jnp.int32)
        p_s = jnp.where(slots <= slot_c, wraps * C + slots,
                        (wraps - 1) * C + slots)
        valid = (p_s >= 0) & (p_s <= pos_c)
        valid &= (p_s > pos_c - w) | (w <= 0)
        mask = valid[:, None, None, :]                       # (B,1,1,C)
    else:
        wraps = (pos // C).astype(jnp.int32)
        p_s = jnp.where(slots <= slot, wraps * C + slots,
                        (wraps - 1) * C + slots)
        valid = (p_s >= 0) & (p_s <= pos)
        valid &= (p_s > pos - w) | (w <= 0)
        mask = valid[None, None, None, :]                    # (1,1,1,C)

    out = _decode_sdpa(q, k, v, mask, cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), new_cache


def _verify_sdpa(q, k, v, mask, n_rep: int):
    """``_decode_sdpa`` generalised to L queries: the speculative verify
    grid (DESIGN.md §12).  q: (B, L, nq, hd); k/v: the (B, C, nkv, hd)
    ring cache with the draft K/V already written at their ring slots;
    mask: (B, 1, 1, L, C) per-query validity.

    Bit-exactness requirement: for query l the reduction over the cache
    axis must be element-for-element the reduction the serial
    ``_decode_sdpa`` performs at position pos+l — same C-length buffer,
    same values at same slots, masked entries exp()-ing to exactly 0 —
    so the accepted prefix of a verify grid reproduces serial logits.
    """
    B, L, nq, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, L, nkv, n_rep, hd)
    scores = jnp.einsum("blhrd,bkhd->bhrlk", qg, k).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd))
    scores = shard(scores, "batch", None, None, None, "cache_seq")
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrlk,bkhd->blhrd", probs, v)
    return out.reshape(B, L, nq, hd)


def decode_attend_multi(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,            # (B, L, D) current token + drafted run
    pos: jax.Array,          # (B,) int32 absolute position of x[:, 0]
    cache: KVCache,
    *,
    window: int | jax.Array = 0,
) -> tuple[jax.Array, KVCache, KVCache]:
    """Verify-grid attention: L tokens per row in ONE step (speculative
    decode, DESIGN.md §12).

    Writes all L K/V rows into the ring cache at slots (pos+l) % C —
    exactly the slots L serial steps would have written — then attends
    each query l over the SAME C-length buffer with the serial step's
    validity mask at depth pos+l.  Keeping the drafted K/V inside the
    buffer (instead of appending a block) preserves the serial reduction
    tree, which is what makes accepted rows bit-identical to serial
    decode.

    Returns (out (B, L, D'), cache-with-all-L-written, stash): ``stash``
    is a KVCache-shaped pytree of the PRE-write values at the L touched
    slots, which ``models.decode.rollback_cache_runs`` scatters back for
    rejected draft rows.
    """
    B, L, _ = x.shape
    C = cache.capacity
    if L > C:
        raise ValueError(
            f"draft run length {L} exceeds cache capacity {C}: ring slots "
            "would collide")
    q = _project_q(p, cfg, x)                                # (B,L,nq,hd)
    k_new, v_new = _project_kv(p, cfg, x)                    # (B,L,nkv,hd)
    pos = jnp.asarray(pos, jnp.int32)
    pgrid = pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]  # (B,L)
    if not cfg.learned_pos:
        q = apply_rope_heads(q, pgrid, cfg.rope_theta)
        k_new = apply_rope_heads(k_new, pgrid, cfg.rope_theta)

    slots_w = (pgrid % C).astype(jnp.int32)                  # (B, L)
    rows = jnp.arange(B)[:, None]

    def write(buf, new):                     # (B,C,...) <- (B,L,...)
        return buf.at[rows, slots_w].set(new)

    def keep(buf):                           # pre-write values at targets
        return buf[rows, slots_w]

    if cache.quantized:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        stash = KVCache(k=keep(cache.k), v=keep(cache.v),
                        k_scale=keep(cache.k_scale),
                        v_scale=keep(cache.v_scale))
        k_i8 = shard(write(cache.k, kq), "batch", "cache_seq", "kv_heads",
                     None)
        v_i8 = shard(write(cache.v, vq), "batch", "cache_seq", "kv_heads",
                     None)
        k_sc = write(cache.k_scale, ks)
        v_sc = write(cache.v_scale, vs)
        new_cache = KVCache(k=k_i8, v=v_i8, k_scale=k_sc, v_scale=v_sc)
        k = _dequantize_kv(k_i8, k_sc, x.dtype)
        v = _dequantize_kv(v_i8, v_sc, x.dtype)
    else:
        stash = KVCache(k=keep(cache.k), v=keep(cache.v))
        k = shard(write(cache.k, k_new), "batch", "cache_seq", "kv_heads",
                  None)
        v = shard(write(cache.v, v_new), "batch", "cache_seq", "kv_heads",
                  None)
        new_cache = KVCache(k=k, v=v)

    # per-query validity: the serial per-slot mask of decode_attend at
    # depth pos+l, one row per (b, l).  Ring slots written for DEEPER
    # draft positions are masked out here exactly as serial would mask
    # the stale data they overwrote.
    slots = jnp.arange(C)[None, None, :]                     # (1,1,C)
    w = jnp.asarray(window)
    pq = pgrid[:, :, None]                                   # (B,L,1)
    slot_q = pq % C
    wraps = (pq // C).astype(jnp.int32)
    p_s = jnp.where(slots <= slot_q, wraps * C + slots,
                    (wraps - 1) * C + slots)
    valid = (p_s >= 0) & (p_s <= pq)
    valid &= (p_s > pq - w) | (w <= 0)
    mask = valid[:, None, None]                              # (B,1,1,L,C)

    out = _verify_sdpa(q, k, v, mask, cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(B, L, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), new_cache, stash


# ---------------------------------------------------------------------------
# paged decode path (block/page-table KV cache, DESIGN.md §13)
# ---------------------------------------------------------------------------

def paged_view(buf: jax.Array, table: jax.Array, context: int) -> jax.Array:
    """Gather a slot's page chain into the dense ring layout.

    buf: (n_pages, P, ...) page pool; table: (B, max_chain) page ids ->
    (B, context, ...).  Chain page j holds ring slots [j*P, (j+1)*P), so
    concatenating the chain and slicing to ``context`` reproduces the
    dense per-slot ring buffer ELEMENT FOR ELEMENT — the paged attention
    below reduces over the exact array the dense ``decode_attend`` owns,
    which is what makes paged streams bit-identical to dense ones.  Tail
    entries past the last mapped page read the null page; they correspond
    to positions the validity mask excludes either way.
    """
    B = table.shape[0]
    gathered = buf[table]                        # (B, max_chain, P, ...)
    flat = gathered.reshape((B, -1) + buf.shape[2:])
    return flat[:, :context]


def _paged_slot_mask(pgrid: jax.Array, context: int) -> jax.Array:
    """Dense ``decode_attend``'s per-slot validity mask at each query
    depth.  pgrid: (B, L) absolute positions -> (B, L, C) bool."""
    C = context
    slots = jnp.arange(C)[None, None, :]                     # (1,1,C)
    pq = pgrid[:, :, None]                                   # (B,L,1)
    slot_q = pq % C
    wraps = (pq // C).astype(jnp.int32)
    p_s = jnp.where(slots <= slot_q, wraps * C + slots,
                    (wraps - 1) * C + slots)
    return (p_s >= 0) & (p_s <= pq)


def paged_decode_attend_multi(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,            # (B, L, D) current token (+ drafted run)
    pos: jax.Array,          # (B,) int32 absolute position of x[:, 0]
    cache: KVCache,          # page-pool layout: k/v (n_pages, P, nkv, hd)
    table: jax.Array,        # (B, max_chain) int32 page ids
    *,
    context: int,
    impl: str = "gather",
) -> tuple[jax.Array, KVCache, KVCache]:
    """Verify-grid attention over a page-table cache (L == 1 is the plain
    decode step).  The dual of ``decode_attend_multi`` with the ring
    buffer factored through the page table: K/V rows land at (page =
    table[b, slot // P], offset = slot % P) for ring slot (pos+l) % C —
    draft runs cross page boundaries exactly like they cross ring slots —
    and each query reduces over the chain gathered back into ring order
    (``paged_view``), masked by the serial validity mask at its depth.

    Returns (out (B, L, D'), pool-with-L-rows-written, stash of pre-write
    values at the touched (page, offset) targets for rollback).

    ``impl``: "gather" (jnp gather + the dense sdpa — bit-identical to
    dense by construction) or "pallas" (the fused page-streaming kernel,
    kernels/paged_attend.py; online-softmax reassociation makes it
    allclose-, not bit-, equal).  Dense all-attention stacks only; int8
    pools and sliding windows are not paged (see models.decode).
    """
    if cache.quantized:
        raise NotImplementedError("paged cache does not support int8 K/V")
    B, L, _ = x.shape
    C = context
    P = cache.k.shape[1]                     # page size
    if L > C:
        raise ValueError(
            f"draft run length {L} exceeds cache capacity {C}: ring slots "
            "would collide")
    q = _project_q(p, cfg, x)                                # (B,L,nq,hd)
    k_new, v_new = _project_kv(p, cfg, x)                    # (B,L,nkv,hd)
    pos = jnp.asarray(pos, jnp.int32)
    pgrid = pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]  # (B,L)
    if not cfg.learned_pos:
        q = apply_rope_heads(q, pgrid, cfg.rope_theta)
        k_new = apply_rope_heads(k_new, pgrid, cfg.rope_theta)

    slots_w = (pgrid % C).astype(jnp.int32)                  # (B, L)
    rows = jnp.arange(B)[:, None]
    pages_w = table[rows, slots_w // P]                      # (B, L)
    offs_w = slots_w % P

    def write(buf, new):                     # (n_pages,P,...) <- (B,L,...)
        return shard(buf.at[pages_w, offs_w].set(new),
                     "page", None, "kv_heads", None)

    def keep(buf):                           # pre-write values at targets
        return buf[pages_w, offs_w]

    stash = KVCache(k=keep(cache.k), v=keep(cache.v))
    new_cache = KVCache(k=write(cache.k, k_new), v=write(cache.v, v_new))

    mask = _paged_slot_mask(pgrid, C)[:, None, None]         # (B,1,1,L,C)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    if impl == "pallas":
        from repro.kernels.ops import paged_attend

        out = paged_attend(new_cache.k, new_cache.v, table, pos, q,
                           context=C)
    elif impl == "gather":
        k = paged_view(new_cache.k, table, C)                # (B,C,nkv,hd)
        v = paged_view(new_cache.v, table, C)
        if L == 1:
            # serial decode: reduce through the SAME einsum the dense
            # decode_attend uses, so the paged serial step is bit-equal
            # to dense by construction, not just by XLA coincidence
            out = _decode_sdpa(q, k, v, mask[:, :, :, 0], n_rep)
        else:
            out = _verify_sdpa(q, k, v, mask, n_rep)
    else:
        raise ValueError(f"unknown paged attention impl {impl!r}")
    out = out.reshape(B, L, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), new_cache, stash


def attend_with_prefix(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,            # (B, S_suf, D) suffix activations
    positions: jax.Array,    # (B, S_suf) absolute positions of the suffix
    k_pre: jax.Array,        # (B, start, nkv, hd) cached prefix K (roped)
    v_pre: jax.Array,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Suffix-prefill attention: queries for positions ``[start, S)``
    over [cached prefix K/V ; the suffix's own K/V] — the prefill-skip
    forward (DESIGN.md §13).  Key order and values match what a cold
    full prefill reduces over for the same rows, so suffix activations
    (and therefore the first-token logits) are bit-identical to cold
    prefill on substrates with order-stable masked reductions (the CPU
    CI substrate; the paged guard asserts it).
    """
    B, S_suf, _ = x.shape
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    if not cfg.learned_pos:
        q = apply_rope_heads(q, positions, cfg.rope_theta)
        k = apply_rope_heads(k, positions, cfg.rope_theta)
    kf = jnp.concatenate([k_pre.astype(k.dtype), k], axis=1)
    vf = jnp.concatenate([v_pre.astype(v.dtype), v], axis=1)
    S = kf.shape[1]
    qp = positions[:, :, None]                               # (B,S_suf,1)
    kp = jnp.arange(S, dtype=jnp.int32)[None, None, :]       # (1,1,S)
    mask = (kp <= qp)[:, None]                               # (B,1,S_suf,S)
    out = _sdpa(q, kf, vf, mask, cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(B, S_suf, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), (k, v)


def decode_cross_attend(
    p: Params, cfg: ModelConfig, x: jax.Array, enc_k: jax.Array,
    enc_v: jax.Array,
) -> jax.Array:
    """Cross-attention during decode: encoder K/V precomputed at prefill."""
    q = _project_q(p, cfg, x)
    out = _decode_sdpa(q, enc_k, enc_v, None, cfg.n_heads // cfg.n_kv_heads)
    B = x.shape[0]
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype)
