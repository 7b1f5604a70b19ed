"""Decode path: cache init, prefill, and single-token decode step.

Cache layout mirrors the layer plan (transformer.py): a list with one entry
per run, each entry a pytree of arrays stacked along the run's layer axis so
the decode step scans layers exactly like the forward pass.

Cache capacities (DESIGN.md §7 — what makes long_500k legal):
  dense/moe/whisper self-attn   full context capacity
  hymba_global                  full context capacity (3 layers only)
  hymba_swa                     min(window, context)  — ring buffer
  mamba / xLSTM                 O(1) recurrent state, no growth

Sharding: KV batch over ("pod","data"), kv-heads over "model" when
divisible; the big hymba_global / dense caches shard their sequence dim
over "model" otherwise (rules in distributed/sharding.py).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.attention import KVCache
from repro.models.config import ModelConfig
from repro.models.layers import apply_mlp, apply_norm, embed, unembed
from repro.models.transformer import (
    _apply_block,
    encode,
    layer_plan,
)

Params = dict
Cache = list


def _kv_capacity(kind: str, cfg: ModelConfig, context: int) -> int:
    if kind == "hymba_swa":
        return min(cfg.sliding_window, context)
    return context


def init_cache(
    cfg: ModelConfig,
    batch: int,
    context: int,
    dtype=jnp.bfloat16,
    *,
    encoder_len: int | None = None,
) -> Cache:
    """Zero cache sized for `context` tokens."""
    cache: Cache = []
    for kind, count in layer_plan(cfg):
        if kind in ("dense", "moe", "hymba_global", "hymba_swa",
                    "whisper_dec"):
            cap = _kv_capacity(kind, cfg, context)
            kv = jax.vmap(
                lambda _: attn_lib.init_kv_cache(cfg, batch, cap, dtype)
            )(jnp.arange(count))
            entry: Any = {"kv": kv}
            if kind in ("hymba_global", "hymba_swa"):
                d_in = cfg.n_heads * cfg.head_dim
                entry["ssm"] = jax.vmap(
                    lambda _: ssm_lib.init_ssm_state(cfg, batch, d_in, dtype)
                )(jnp.arange(count))
            if kind == "whisper_dec":
                el = encoder_len or cfg.encoder_len
                shape = (count, batch, el, cfg.n_kv_heads, cfg.head_dim)
                entry["enc_k"] = jnp.zeros(shape, dtype)
                entry["enc_v"] = jnp.zeros(shape, dtype)
            cache.append(entry)
        elif kind == "mlstm":
            cache.append(
                {"state": jax.vmap(
                    lambda _: xlstm_lib.init_mlstm_state(cfg, batch)
                )(jnp.arange(count))}
            )
        elif kind == "slstm":
            cache.append(
                {"state": jax.vmap(
                    lambda _: xlstm_lib.init_slstm_state(cfg, batch)
                )(jnp.arange(count))}
            )
        else:
            raise ValueError(kind)
    return cache


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _ring_fill(kv_full: jax.Array, cap: int) -> jax.Array:
    """Place the last min(S, cap) positions at ring slots pos % cap.

    kv_full: (B, S, n_kv, hd) -> (B, cap, n_kv, hd).
    """
    B, S, n_kv, hd = kv_full.shape
    if S <= cap:
        out = jnp.pad(kv_full, ((0, 0), (0, cap - S), (0, 0), (0, 0)))
    else:
        tail = kv_full[:, S - cap:]                    # (B, cap, n_kv, hd)
        slots = (jnp.arange(S - cap, S)) % cap
        out = jnp.zeros((B, cap, n_kv, hd), kv_full.dtype).at[:, slots].set(
            tail)
    # land directly in the decode-cache layout (seq over `model`)
    return shard(out, "batch", "cache_seq", None, None)


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (B, S)
    context: int,
    *,
    encoder_frames: jax.Array | None = None,
    compute_dtype=jnp.bfloat16,
    capacity_mode: str = "fifo",
    moe_groups: int = 1,
    kv_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Cache]:
    """Process the prompt; returns (last-position logits (B, V) f32, cache).

    Only the final position's logits are computed (the (B, S, V) tensor is
    never materialised — prefill feeds the decode loop, not the loss).
    """
    B, S = tokens.shape
    x = embed(params["embed"], tokens, compute_dtype)
    if cfg.learned_pos:
        x = x + params["pos_embed"].astype(compute_dtype)[None, :S]
    x = shard(x, "batch", "seq_sp", "embed")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    encoder_out = None
    if cfg.is_encdec:
        assert encoder_frames is not None
        encoder_out = encode(cfg, params, encoder_frames.astype(compute_dtype))

    cache: Cache = []
    for run_params, (kind, count) in zip(params["runs"], layer_plan(cfg)):
        x, entry = _prefill_run(
            kind, cfg, run_params, x, positions, context,
            encoder_out=encoder_out, capacity_mode=capacity_mode,
            moe_groups=moe_groups, kv_dtype=kv_dtype,
        )
        cache.append(entry)

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    last = x[:, -1]
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, last, cfg.vocab)
    return shard(logits, "batch", "vocab"), cache


def _make_kv_entry(k, v, cap, kv_dtype):
    """Ring-fill + optional int8 quantisation (beyond-paper §Perf)."""
    kf = _ring_fill(k, cap)
    vf = _ring_fill(v, cap)
    if kv_dtype == jnp.int8:
        kq, ks = attn_lib._quantize_kv(kf)
        vq, vs = attn_lib._quantize_kv(vf)
        return KVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
    return KVCache(k=kf, v=vf)


def _prefill_block(kind, cfg, p, x, positions, cap, *, encoder_out,
                   capacity_mode, moe_groups=1, kv_dtype=jnp.bfloat16):
    """One block forward that also emits its decode-cache entry."""
    eps = cfg.norm_eps
    if kind in ("dense", "moe"):
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, (k, v) = attn_lib.attend(p["attn"], cfg, h, positions,
                                    return_kv=True)
        x = x + a
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        if kind == "dense":
            x = x + apply_mlp(cfg.act, p["mlp"], h)
        else:
            out, _ = moe_lib.moe_apply(p["moe"], cfg, h,
                                       capacity_mode=capacity_mode,
                                       n_groups=moe_groups)
            x = x + out
        entry = {"kv": _make_kv_entry(k, v, cap, kv_dtype)}
        return x, entry
    if kind in ("hymba_global", "hymba_swa"):
        w = 0 if kind == "hymba_global" else cfg.sliding_window
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, (k, v) = attn_lib.attend(p["attn"], cfg, h, positions, window=w,
                                    return_kv=True)
        s, ssm_state = ssm_lib.ssm_apply(p["ssm"], cfg, h, return_state=True)
        a = apply_norm(cfg.norm, p["attn_norm"], a, eps)
        s = apply_norm(cfg.norm, p["ssm_norm"], s, eps)
        x = x + 0.5 * (a + s)
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        x = x + apply_mlp(cfg.act, p["mlp"], h)
        entry = {
            "kv": _make_kv_entry(k, v, cap, kv_dtype),
            "ssm": ssm_state,
        }
        return x, entry
    if kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln"], x, eps)
        out, state = xlstm_lib.mlstm_apply(p["mlstm"], cfg, h,
                                           return_state=True)
        return x + out, {"state": state}
    if kind == "slstm":
        h = apply_norm(cfg.norm, p["ln"], x, eps)
        out, state = xlstm_lib.slstm_apply(p["slstm"], cfg, h,
                                           return_state=True)
        return x + out, {"state": state}
    if kind == "whisper_dec":
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, (k, v) = attn_lib.attend(p["attn"], cfg, h, positions,
                                    return_kv=True)
        x = x + a
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        xa, (ek, ev) = attn_lib.attend(
            p["xattn"], cfg, h, positions, causal=False, kv_src=encoder_out,
            return_kv=True,
        )
        x = x + xa
        h = apply_norm(cfg.norm, p["ln3"], x, eps)
        x = x + apply_mlp(cfg.act, p["mlp"], h)
        entry = {
            "kv": _make_kv_entry(k, v, cap, kv_dtype),
            "enc_k": ek, "enc_v": ev,
        }
        return x, entry
    raise ValueError(kind)


def _prefill_run(kind, cfg, run_params, x, positions, context, *,
                 encoder_out, capacity_mode, moe_groups=1,
                 kv_dtype=jnp.bfloat16):
    cap = _kv_capacity(kind, cfg, context)

    def body(x, p_l):
        x, entry = _prefill_block(
            kind, cfg, p_l, x, positions, cap,
            encoder_out=encoder_out, capacity_mode=capacity_mode,
            moe_groups=moe_groups, kv_dtype=kv_dtype,
        )
        return x, entry

    x, entries = jax.lax.scan(body, x, run_params)
    return x, entries


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _step_block(kind, cfg, p, x, pos, entry, *, capacity_mode, write_mask):
    """One block for one token.  x: (B, 1, D)."""
    eps = cfg.norm_eps
    if kind in ("dense", "moe"):
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, kv = attn_lib.decode_attend(p["attn"], cfg, h, pos, entry["kv"],
                                       write_mask=write_mask)
        x = x + a
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        if kind == "dense":
            x = x + apply_mlp(cfg.act, p["mlp"], h)
        else:
            out, _ = moe_lib.moe_apply(p["moe"], cfg, h,
                                       capacity_mode=capacity_mode)
            x = x + out
        return x, {"kv": kv}
    if kind in ("hymba_global", "hymba_swa"):
        w = 0 if kind == "hymba_global" else cfg.sliding_window
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, kv = attn_lib.decode_attend(p["attn"], cfg, h, pos, entry["kv"],
                                       window=w, write_mask=write_mask)
        s, ssm_state = ssm_lib.ssm_step(p["ssm"], cfg, h, entry["ssm"])
        a = apply_norm(cfg.norm, p["attn_norm"], a, eps)
        s = apply_norm(cfg.norm, p["ssm_norm"], s, eps)
        x = x + 0.5 * (a + s)
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        x = x + apply_mlp(cfg.act, p["mlp"], h)
        return x, {"kv": kv, "ssm": ssm_state}
    if kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln"], x, eps)
        out, state = xlstm_lib.mlstm_step(p["mlstm"], cfg, h, entry["state"])
        return x + out, {"state": state}
    if kind == "slstm":
        h = apply_norm(cfg.norm, p["ln"], x, eps)
        out, state = xlstm_lib.slstm_step(p["slstm"], cfg, h, entry["state"])
        return x + out, {"state": state}
    if kind == "whisper_dec":
        h = apply_norm(cfg.norm, p["ln1"], x, eps)
        a, kv = attn_lib.decode_attend(p["attn"], cfg, h, pos, entry["kv"],
                                       write_mask=write_mask)
        x = x + a
        h = apply_norm(cfg.norm, p["ln2"], x, eps)
        x = x + attn_lib.decode_cross_attend(
            p["xattn"], cfg, h, entry["enc_k"], entry["enc_v"]
        )
        h = apply_norm(cfg.norm, p["ln3"], x, eps)
        x = x + apply_mlp(cfg.act, p["mlp"], h)
        return x, {"kv": kv, "enc_k": entry["enc_k"], "enc_v": entry["enc_v"]}
    raise ValueError(kind)


def decode_step(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,                  # (B,) int32 current token
    pos: jax.Array,                    # () int32 shared, or (B,) per-slot
    cache: Cache,
    *,
    compute_dtype=jnp.bfloat16,
    capacity_mode: str = "fifo",
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, Cache]:
    """One decode step: returns (logits (B, V) f32, updated cache).

    ``pos`` is either a scalar (every row at the same depth — one-shot
    ``generate``) or a (B,) vector (continuous batching: heterogeneous
    in-flight requests, one position per slot).  Either way this is ONE
    compiled function: the continuous scheduler re-uses the same jitted
    step across arbitrary slot occupancy.

    ``write_mask`` (B,) bool, with per-slot ``pos``: rows where it is
    False write no K/V row (``attention.decode_attend``), so their ring
    K/V comes back bit-equal to ``cache``.  Recurrent state is rewritten
    for every row regardless; ``freeze_cache_lanes`` restores it.
    """
    B = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    x = embed(params["embed"], token[:, None], compute_dtype)  # (B, 1, D)
    if cfg.learned_pos:
        pe = params["pos_embed"].astype(compute_dtype)
        x = x + (pe[pos][:, None] if pos.ndim == 1
                 else pe[None, pos][:, None])

    new_cache: Cache = []
    for run_params, entry, (kind, _) in zip(
        params["runs"], cache, layer_plan(cfg)
    ):
        # The cache rides in the carry and each layer is updated where it
        # lies.  Scanned as xs -> ys, the step would emit a second stacked
        # cache, which XLA then copies into the donated one.
        def body(carry, p_l):
            x, entry, i = carry
            entry_l = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                entry)
            x, new_l = _step_block(
                kind, cfg, p_l, x, pos, entry_l, capacity_mode=capacity_mode,
                write_mask=write_mask,
            )
            entry = jax.tree_util.tree_map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
                entry, new_l)
            return (x, entry, i + 1), None

        (x, new_entry, _), _ = jax.lax.scan(
            body, (x, entry, jnp.int32(0)), run_params)
        new_cache.append(new_entry)

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, x[:, 0], cfg.vocab)
    return shard(logits, "batch", "vocab"), new_cache


# ---------------------------------------------------------------------------
# speculative verify (draft-and-verify decode, DESIGN.md §12)
# ---------------------------------------------------------------------------

def verify_supported(cfg: ModelConfig) -> bool:
    """Whether ``decode_verify`` can serve this arch.

    Dense attention stacks only: recurrent layers (SSM / xLSTM) would need
    per-draft-position state checkpoints to roll back, and MoE capacity
    cuts couple the (B, L) grid rows through the router, breaking the
    accepted-prefix == serial contract.
    """
    return all(kind == "dense" for kind, _ in layer_plan(cfg))


def decode_verify(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (B, L): current token + drafted run
    pos: jax.Array,                    # (B,) int32 position of tokens[:, 0]
    cache: Cache,
    *,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Cache, list]:
    """Score a (B, L) token grid in ONE forward against the slotted cache.

    Row b feeds [t_0, d_1, .., d_{L-1}] at positions pos_b .. pos_b+L-1:
    the current token then the drafted run.  ``logits[:, l]`` predicts the
    token at position pos+l+1 given that prefix — the sequence-level
    runahead grid: L serial decode steps answered by one batched forward,
    the accept/reject of each drafted token playing the paper's sign
    check.

    All L K/V rows are written into the ring cache (the state L serial
    steps would have left); the returned ``stash`` holds the pre-write
    values at the touched slots so ``rollback_cache_runs`` can restore the
    rows the acceptance logic rejects.  Returns (logits (B, L, V) f32,
    cache, stash).  Dense stacks only — see ``verify_supported``.
    """
    B, L = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    x = embed(params["embed"], tokens, compute_dtype)        # (B, L, D)
    if cfg.learned_pos:
        pe = params["pos_embed"].astype(compute_dtype)
        x = x + pe[pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]]

    new_cache: Cache = []
    stashes: list = []
    for run_params, entry, (kind, _) in zip(
        params["runs"], cache, layer_plan(cfg)
    ):
        if kind != "dense":
            raise ValueError(
                f"decode_verify supports dense layer stacks only, got "
                f"{kind!r} (see verify_supported)")

        def body(x, inp):
            p_l, entry_l = inp
            eps = cfg.norm_eps
            h = apply_norm(cfg.norm, p_l["ln1"], x, eps)
            a, kv, st = attn_lib.decode_attend_multi(
                p_l["attn"], cfg, h, pos, entry_l["kv"])
            x = x + a
            h = apply_norm(cfg.norm, p_l["ln2"], x, eps)
            x = x + apply_mlp(cfg.act, p_l["mlp"], h)
            return x, ({"kv": kv}, st)

        x, (new_entry, st) = jax.lax.scan(body, x, (run_params, entry))
        new_cache.append(new_entry)
        stashes.append({"kv": st})

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, x, cfg.vocab)                    # (B, L, V)
    return shard(logits, "batch", None, "vocab"), new_cache, stashes


def rollback_cache_runs(cache: Cache, stash: list, pos, n_keep) -> Cache:
    """Restore cache rows ``decode_verify`` wrote for rejected positions.

    The dual of ``write_cache_slot``'s admission scatter, at draft-run
    granularity: cache leaves are (layers, B, C, ...) with the full L-row
    speculative write applied; ``stash`` mirrors them with the (layers, B,
    L, ...) pre-write values at the touched ring slots; ``n_keep`` (B,)
    commits the leading rows — 1 + accepted drafts for live slots, 0 for
    inactive rows (restoring them bit-exactly to their pre-step state).
    """
    pos = jnp.asarray(pos, jnp.int32)
    n_keep = jnp.asarray(n_keep, jnp.int32)

    def restore(leaf, old):
        L = old.shape[2]
        C = leaf.shape[2]
        pg = pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
        slots = (pg % C).astype(jnp.int32)                   # (B, L)
        rows = jnp.arange(leaf.shape[1])[:, None]
        keep = jnp.arange(L)[None, :] < n_keep[:, None]      # (B, L)
        cur = leaf[:, rows, slots]                           # (lyr,B,L,...)
        sel = keep.reshape((1,) + keep.shape + (1,) * (cur.ndim - 3))
        return leaf.at[:, rows, slots].set(jnp.where(sel, cur, old))

    return jax.tree_util.tree_map(restore, cache, stash)


# ---------------------------------------------------------------------------
# paged KV cache (block/page-table layout, DESIGN.md §13)
# ---------------------------------------------------------------------------

def paged_supported(cfg: ModelConfig) -> bool:
    """Whether the paged cache can serve this arch — dense attention
    stacks only, same gate as ``verify_supported`` (recurrent state has no
    page structure and SWA rings have their own capacity)."""
    return all(kind == "dense" for kind, _ in layer_plan(cfg))


def init_paged_pool(
    cfg: ModelConfig, n_pages: int, page_size: int, dtype=jnp.bfloat16
) -> Cache:
    """Zero page pool: the paged dual of ``init_cache``.

    Leaves are (layers, n_pages, page_size, n_kv, head_dim) — the batch
    and context dims of the dense layout are replaced by one flat pool of
    pages shared by every slot; the (n_slots, max_chain) page table (host
    side: serving/paged.py) says which pages spell which slot's ring.
    Page id 0 is the reserved null page.  The page dim carries the "page"
    logical axis (data-parallel shards of the pool).
    """
    if not paged_supported(cfg):
        raise ValueError(
            "paged KV cache supports dense layer stacks only (see "
            "paged_supported)")
    if dtype == jnp.int8:
        raise ValueError("paged cache does not support int8 K/V")
    pool: Cache = []
    for _, count in layer_plan(cfg):
        shape = (count, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        pool.append({"kv": KVCache(
            k=shard(jnp.zeros(shape, dtype), None, "page", None, "kv_heads",
                    None),
            v=shard(jnp.zeros(shape, dtype), None, "page", None, "kv_heads",
                    None),
        )})
    return pool


def mask_table_rows(table: jax.Array, active: jax.Array) -> jax.Array:
    """Point inactive slots' page-table rows at the null page (id 0).

    Per-step serving gets this for free: eviction zeroes the dead slot's
    table row on the host, so the slot's dead per-step writes land in the
    reserved null page instead of a recycled (possibly shared) page.  A
    fused multi-step horizon (serving/scheduler.py) cannot update the host
    table mid-scan, so each scan iteration re-derives the same invariant
    from the live ``active`` mask — without it, a slot finishing at
    iteration j < K keeps writing K/V through its stale chain, and a
    wrapped ring position can corrupt a COW page another slot still reads.
    """
    return jnp.where(active[:, None], table, 0)


# Cache entries a masked ``decode_step`` already leaves bit-frozen in
# inactive lanes: ring K/V (the dropped row write) and the encoder K/V,
# which decode only reads.
_FROZEN_BY_STEP = frozenset({"kv", "enc_k", "enc_v"})


def freeze_cache_lanes(new_cache, old_cache, active: jax.Array):
    """Bit-freeze inactive batch lanes: keep ``old_cache`` where ``~active``.

    The dense dual of ``mask_table_rows``, for a ``new_cache`` made by
    ``decode_step(..., write_mask=active)``.  A dense ring cache has no
    null page to absorb a dead lane's write, so that step drops the one
    K/V row an inactive lane would write: its ``"kv"`` entries come back
    frozen and pass through here untouched, as do the read-only
    ``"enc_k"``/``"enc_v"``.  What is left to select is recurrent state
    (SSM ``"ssm"``, xLSTM ``"state"``): its step rewrites the whole lane,
    so the pre-step state is selected back in for every inactive lane —
    a select over O(state), not over the K/V cache.  This is what lets a
    fused horizon (serving/scheduler.py) leave a slot that finished at
    iteration j < K bit-identical to the state per-step serving would
    have evicted.  Cache leaves are layer-stacked with the batch on axis
    1; each entry is judged by its own keys.
    """
    def sel(new, old):
        mask = active.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(mask, new, old)

    return [
        {name: leaf if name in _FROZEN_BY_STEP
         else jax.tree_util.tree_map(sel, leaf, old[name])
         for name, leaf in new.items()}
        for new, old in zip(new_cache, old_cache)
    ]


def paged_prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (1, S) one request's prompt
    context: int,
    pool: Cache,
    chain: jax.Array,                  # (chain_len,) int32 page ids
    *,
    page_size: int,
    skip: int = 0,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Cache]:
    """Prefill ONE request into its page chain; the paged admission path.

    ``skip`` pages (``skip * page_size`` leading positions) are already
    resident — a COW prefix fork found them in the hash (serving/paged.py)
    — so only the suffix runs a forward: suffix queries attend over
    [cached prefix K/V ; suffix K/V] (``attend_with_prefix``), which
    reduces over exactly the key sequence a cold prefill reduces over for
    the same rows.  ``skip == 0`` IS the cold path: the ordinary B=1
    ``prefill`` followed by a scatter of its ring rows into the chain's
    pages.  Either way returns (last-position logits (1, V) f32, pool),
    bit-identical to each other and to the dense slotted admission on the
    CPU CI substrate (order-stable masked reductions; paged_guard asserts
    it).

    ``skip`` is static (admission re-jits per (prompt_len, skip) exactly
    as the dense path re-jits per prompt_len); ``chain`` is traced, so
    WHICH pages hold the request never recompiles anything.
    """
    B, S = tokens.shape
    if B != 1:
        raise ValueError(f"paged_prefill admits one request, got B={B}")
    if not paged_supported(cfg):
        raise ValueError(
            "paged KV cache supports dense layer stacks only (see "
            "paged_supported)")
    P = page_size
    chain = jnp.asarray(chain, jnp.int32)
    chain_len = chain.shape[0]
    start = skip * P
    if not 0 <= start < S:
        raise ValueError(
            f"prefix skip {skip} pages covers {start} positions; prompt has "
            f"{S} (the suffix must recompute at least the last position)")

    if skip == 0:
        logits, sub = prefill(
            cfg, params, tokens, context, compute_dtype=compute_dtype,
        )
        rows = chain_len * P

        def scatter(pool_leaf, ring_leaf):
            big = ring_leaf[:, 0]                    # (layers, C, nkv, hd)
            C = big.shape[1]
            if rows <= C:
                big = big[:, :rows]
            else:
                big = jnp.pad(
                    big, ((0, 0), (0, rows - C)) + ((0, 0),) * (big.ndim - 2))
            big = big.reshape(
                (big.shape[0], chain_len, P) + big.shape[2:])
            return pool_leaf.at[:, chain].set(big.astype(pool_leaf.dtype))

        new_pool = [
            {"kv": KVCache(k=scatter(pe["kv"].k, se["kv"].k),
                           v=scatter(pe["kv"].v, se["kv"].v))}
            for pe, se in zip(pool, sub)
        ]
        return logits, new_pool

    # -- suffix path: skip pages of prefix K/V are already in the pool ------
    S_suf = S - start
    x = embed(params["embed"], tokens[:, start:], compute_dtype)
    if cfg.learned_pos:
        x = x + params["pos_embed"].astype(compute_dtype)[None, start:S]
    positions = jnp.broadcast_to(
        jnp.arange(start, S, dtype=jnp.int32), (1, S_suf))
    suf_slots = jnp.arange(start, S, dtype=jnp.int32)        # no wrap: S<=C
    pages_w = chain[suf_slots // P]                          # (S_suf,)
    offs_w = suf_slots % P
    pre = chain[:skip]

    new_pool: Cache = []
    for run_params, entry, (kind, _) in zip(
        params["runs"], pool, layer_plan(cfg)
    ):
        def body(x, inp):
            p_l, kv_l = inp
            eps = cfg.norm_eps
            k_pre = kv_l.k[pre].reshape(
                (1, start) + kv_l.k.shape[2:])       # (1, start, nkv, hd)
            v_pre = kv_l.v[pre].reshape((1, start) + kv_l.v.shape[2:])
            h = apply_norm(cfg.norm, p_l["ln1"], x, eps)
            a, (k_suf, v_suf) = attn_lib.attend_with_prefix(
                p_l["attn"], cfg, h, positions, k_pre, v_pre)
            x = x + a
            h = apply_norm(cfg.norm, p_l["ln2"], x, eps)
            x = x + apply_mlp(cfg.act, p_l["mlp"], h)
            kv_new = KVCache(
                k=kv_l.k.at[pages_w, offs_w].set(
                    k_suf[0].astype(kv_l.k.dtype)),
                v=kv_l.v.at[pages_w, offs_w].set(
                    v_suf[0].astype(kv_l.v.dtype)),
            )
            return x, kv_new

        x, kv_new = jax.lax.scan(body, x, (run_params, entry["kv"]))
        new_pool.append({"kv": kv_new})

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(table, x[:, -1], cfg.vocab)
    return shard(logits, "batch", "vocab"), new_pool


def decode_step_paged(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,                  # (B,) int32 current token
    pos: jax.Array,                    # (B,) int32 per-slot position
    pool: Cache,
    table: jax.Array,                  # (B, max_chain) int32 page ids
    *,
    context: int,
    compute_dtype=jnp.bfloat16,
    impl: str = "gather",
) -> tuple[jax.Array, Cache]:
    """One decode step over the page-table cache; the paged dual of
    ``decode_step`` (per-slot positions, dense stacks only).  Returns
    (logits (B, V) f32, pool)."""
    logits, pool, _ = decode_verify_paged(
        cfg, params, token[:, None], pos, pool, table,
        context=context, compute_dtype=compute_dtype, impl=impl,
    )
    return logits[:, 0], pool


def decode_verify_paged(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (B, L): current token + drafted run
    pos: jax.Array,                    # (B,) int32 position of tokens[:, 0]
    pool: Cache,
    table: jax.Array,                  # (B, max_chain) int32 page ids
    *,
    context: int,
    compute_dtype=jnp.bfloat16,
    impl: str = "gather",
) -> tuple[jax.Array, Cache, list]:
    """``decode_verify`` over the page-table cache: score a (B, L) grid in
    one forward, writing the L K/V rows through each slot's page chain —
    a draft run crossing a page boundary lands in two pages exactly as it
    crosses ring slots.  Returns (logits (B, L, V) f32, pool, stash);
    ``rollback_paged_runs`` restores the rejected rows.
    """
    B, L = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    x = embed(params["embed"], tokens, compute_dtype)        # (B, L, D)
    if cfg.learned_pos:
        pe = params["pos_embed"].astype(compute_dtype)
        x = x + pe[pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]]

    new_pool: Cache = []
    stashes: list = []
    for run_params, entry, (kind, _) in zip(
        params["runs"], pool, layer_plan(cfg)
    ):
        if kind != "dense":
            raise ValueError(
                f"paged decode supports dense layer stacks only, got "
                f"{kind!r} (see paged_supported)")

        def body(x, inp):
            p_l, kv_l = inp
            eps = cfg.norm_eps
            h = apply_norm(cfg.norm, p_l["ln1"], x, eps)
            a, kv, st = attn_lib.paged_decode_attend_multi(
                p_l["attn"], cfg, h, pos, kv_l, table,
                context=context, impl=impl)
            x = x + a
            h = apply_norm(cfg.norm, p_l["ln2"], x, eps)
            x = x + apply_mlp(cfg.act, p_l["mlp"], h)
            return x, (kv, st)

        x, (kv_new, st) = jax.lax.scan(body, x, (run_params, entry["kv"]))
        new_pool.append({"kv": kv_new})
        stashes.append({"kv": st})

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    tab = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed(tab, x, cfg.vocab)                      # (B, L, V)
    return shard(logits, "batch", None, "vocab"), new_pool, stashes


def rollback_paged_runs(
    pool: Cache, stash: list, table: jax.Array, pos, n_keep, *, context: int,
) -> Cache:
    """``rollback_cache_runs`` through the page table: pool leaves are
    (layers, n_pages, P, ...) with the full L-row speculative write
    applied; ``stash`` holds the (layers, B, L, ...) pre-write values at
    the touched (page, offset) targets; ``n_keep`` (B,) commits the
    leading rows and restores the rest bit-exactly.
    """
    pos = jnp.asarray(pos, jnp.int32)
    n_keep = jnp.asarray(n_keep, jnp.int32)
    C = context

    def restore(leaf, old):
        L = old.shape[2]
        P = leaf.shape[2]
        pg = pos[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
        slots = (pg % C).astype(jnp.int32)                   # (B, L)
        rows = jnp.arange(old.shape[1])[:, None]
        pages = table[rows, slots // P]                      # (B, L)
        offs = slots % P
        keep = jnp.arange(L)[None, :] < n_keep[:, None]      # (B, L)
        cur = leaf[:, pages, offs]                           # (lyr,B,L,...)
        sel = keep.reshape((1,) + keep.shape + (1,) * (cur.ndim - 3))
        return leaf.at[:, pages, offs].set(jnp.where(sel, cur, old))

    return jax.tree_util.tree_map(restore, pool, stash)


# ---------------------------------------------------------------------------
# slotted cache (continuous batching)
# ---------------------------------------------------------------------------

def write_cache_slot(cache: Cache, sub: Cache, slot) -> Cache:
    """Overwrite batch row `slot` of `cache` with the B=1 cache `sub`.

    Every cache leaf is laid out (layers, batch, ...), so one tree_map
    scatters the whole pytree — KV rings, SSM states, xLSTM states and
    encoder K/V alike.  This is the admission path of the continuous
    scheduler: the evicted request's slot is recycled in place, no
    reallocation and no copy of the other slots.
    """

    def wr(big, small):
        return big.at[:, slot].set(small[:, 0])

    return jax.tree_util.tree_map(wr, cache, sub)


def prefill_into_slot(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (1, S) one request's prompt
    context: int,
    cache: Cache,
    slot,
    *,
    encoder_frames: jax.Array | None = None,
    compute_dtype=jnp.bfloat16,
    capacity_mode: str = "fifo",
    kv_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Cache]:
    """Prefill ONE request and land its state in batch row `slot`.

    Returns (last-position logits (1, V) f32, updated slotted cache).  The
    prefill math is the ordinary batched `prefill` at B=1, so a request's
    state is bit-identical whether it was admitted into a slot or served
    one-shot; `context` must match the slotted cache's capacity.
    """
    logits, sub = prefill(
        cfg, params, tokens, context, encoder_frames=encoder_frames,
        compute_dtype=compute_dtype, capacity_mode=capacity_mode,
        kv_dtype=kv_dtype,
    )
    return logits, write_cache_slot(cache, sub, slot)
