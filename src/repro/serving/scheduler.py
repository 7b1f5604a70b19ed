"""Fixed-slot continuous-batching scheduler (DESIGN.md §9).

The paper's runahead premise — idle parallel lanes should absorb serial
latency — applied at the REQUEST level: the solver engine's batch axis is
only busy while every row has a live request, so the scheduler keeps a
fixed pool of `n_slots` decode lanes and admits/evicts requests per decode
step instead of waiting for a whole batch to drain (the one-shot
``serving.engine.generate`` shape).

Device state is slot-major and fixed-shape:

  * one slotted KV cache (``models.decode.init_cache`` at batch=n_slots),
    recycled in place by per-slot prefill (``prefill_into_slot``);
  * (B,) current-token / position vectors — ``decode_step`` natively
    supports per-slot positions, so heterogeneous in-flight requests share
    ONE compiled step function across arbitrary slot occupancy;
  * (B, 2) per-slot PRNG keys — each request's key chain is identical to
    a B=1 one-shot ``generate`` with its seed, which makes continuous
    serving token-identical per request (tests/test_serving_engine.py);
  * per-slot sampler parameters (``SlotSamplers``) riding the solver
    engine's batch axis.

Host state is a plain slot table (request id, tokens emitted, remaining
budget) plus a FIFO of waiting requests.  Admission runs the ordinary B=1
prefill and scatters the resulting cache into the free slot; eviction is
just marking the slot free — the next admission overwrites it.

``admit`` and ``step`` mark their phases with ``jax.profiler`` host spans
(``serve.admit`` and ``serve.admit.prefill|sample|book``; ``serve.step``
and ``serve.step.prepare|launch|readback|commit``).  A profiler trace
puts them on the device's clock, so each stretch of device idle time
lies inside the host phase that left the device waiting.  With no trace
running a span costs about a microsecond.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import solver
from repro.distributed.sharding import (
    SERVE_RULES,
    resolve_axes,
    resolved_axis_size,
)
from repro.models.config import ModelConfig
from repro.models.decode import (
    decode_step,
    decode_step_paged,
    decode_verify,
    decode_verify_paged,
    freeze_cache_lanes,
    init_cache,
    init_paged_pool,
    mask_table_rows,
    paged_prefill,
    paged_supported,
    prefill_into_slot,
    rollback_cache_runs,
    rollback_paged_runs,
    verify_supported,
)
from repro.serving.draft import DraftSource, NGramDrafter
from repro.serving.paged import (
    PageAllocator,
    pages_for,
    plan_chain,
    prefix_key,
)
from repro.serving.sampler import (
    SamplerConfig,
    SlotSamplers,
    sample_slots,
    verify_slots,
)


def slot_policy(mesh: jax.sharding.Mesh, n_slots: int):
    """(MeshPolicy, slot_axes) for a serving mesh, from SERVE_RULES.

    slot_axes shard the fixed slot pool over the data axes (None —
    replicated state — when n_slots doesn't divide them); the policy
    vocab-shards every sampler solve over `solver_vocab` (the engine
    itself falls back per-solve when the vocab doesn't divide).
    """
    slot_axes = resolve_axes(mesh, SERVE_RULES, "slot")
    if slot_axes is not None and n_slots % resolved_axis_size(
            mesh, slot_axes):
        slot_axes = None
    vocab_axis = resolve_axes(mesh, SERVE_RULES, "solver_vocab")
    policy = solver.MeshPolicy(mesh, vocab_axis=vocab_axis)
    return policy, slot_axes


def _shard_slot_state(mesh, slot_axes, token, pos, keys, cache):
    """Place slot-major device state: (B, ...) vectors on the slot axes,
    cache leaves (layers, B, ...) likewise on dim 1."""
    vec = NamedSharding(mesh, P(slot_axes))
    token = jax.device_put(token, vec)
    pos = jax.device_put(pos, vec)
    keys = jax.device_put(keys, NamedSharding(mesh, P(slot_axes, None)))
    cache = jax.tree_util.tree_map(
        lambda leaf: jax.device_put(
            leaf,
            NamedSharding(
                mesh, P(None, slot_axes, *(None,) * (leaf.ndim - 2))
                if leaf.ndim >= 2 else P()
            ),
        ),
        cache,
    )
    return token, pos, keys, cache


@dataclasses.dataclass
class _SlotInfo:
    """Host-side bookkeeping for one occupied slot."""

    rid: Any
    remaining: int                  # tokens still owed
    tokens: list[int]               # emitted so far (includes prefill token)
    sampler: SamplerConfig
    context: list[int] = dataclasses.field(default_factory=list)
    # prompt + emitted history, the draft source's lookup corpus
    eos_id: int | None = None       # stop token (host-side truncation)


@dataclasses.dataclass
class FinishedRequest:
    rid: Any
    tokens: list[int]


def _enable_bits(configs: list[SamplerConfig]) -> tuple[bool, bool, bool]:
    """(entropy, top_k, top_p) static gates for the compiled step: a solve
    compiles in only while SOME in-flight request uses it.

    Greedy rows never need one: argmax is invariant under every transform
    in the pipeline (temperature is a positive scale, top-k/top-p masks
    always keep the max element), so an all-greedy batch compiles a
    solver-free step — the whole sampler is one argmax."""
    need = [c for c in configs if not c.greedy]
    return (
        any(c.target_entropy is not None for c in need),
        any(c.top_k > 0 for c in need),
        any(c.top_p > 0.0 for c in need),
    )


def _static_top_k(configs: list[SamplerConfig]) -> int | None:
    """The shared top_k when every solve-needing config agrees on one
    positive value — lets sample_slots take the static-k fast paths
    (fused pallas kernel, probe skip).  Greedy rows don't vote (their
    argmax ignores the mask either way)."""
    ks = {c.top_k for c in configs if not c.greedy}
    if len(ks) == 1:
        k = ks.pop()
        if k > 0:
            return k
    return None


@functools.partial(
    jax.jit, static_argnames=("cfg", "context", "cache_dtype"),
    donate_argnames=("cache",),
)
def _admit_slot(params, tokens, cache, slot, key, *, cfg, context,
                cache_dtype):
    """Jitted admission: B=1 prefill scattered into `slot`, plus the
    request's first key split.  Compiles once per (cfg, prompt length) and
    is shared across scheduler instances; the first-token sample stays
    outside (it is shaped by the request's own SamplerConfig).  The old
    cache is donated — the scatter happens in place."""
    logits, cache = prefill_into_slot(
        cfg, params, tokens, context, cache, slot, kv_dtype=cache_dtype,
    )
    key, sub = jax.random.split(key)
    return logits, cache, key, sub


@functools.partial(
    jax.jit, static_argnames=("cfg", "context", "page_size", "skip"),
    donate_argnames=("pool",),
)
def _admit_paged(params, tokens, pool, chain, key, *, cfg, context,
                 page_size, skip):
    """Jitted paged admission: ``paged_prefill`` into the request's page
    chain plus the first key split.  Compiles once per (cfg, prompt
    length, chain length, skip) — WHICH pages hold the request is traced
    data; HOW MANY pages the prefix hash let us skip is static because it
    changes the forward's shape (the suffix length).  The pool is donated
    so the scatter happens in place."""
    logits, pool = paged_prefill(
        cfg, params, tokens, context, pool, chain,
        page_size=page_size, skip=skip,
    )
    key, sub = jax.random.split(key)
    return logits, pool, key, sub


@functools.partial(
    jax.jit,
    static_argnames=("spec_k", "rounds", "backend", "enable",
                     "top_k_static", "greedy_only"),
)
def _admit_sample(logits, keys, slots, *, spec_k, rounds, backend, enable,
                  top_k_static, greedy_only=False):
    """Jitted first-token sample at admission, through the SAME per-slot
    sampler as the decode step at B=1 — all float knobs are traced, so the
    jit cache is bounded by the (few) static gate combinations, never by
    how many distinct temperatures users pick."""
    return sample_slots(logits, keys, slots, spec_k=spec_k, rounds=rounds,
                        backend=backend, enable=enable,
                        top_k_static=top_k_static, greedy_only=greedy_only)


def _step_body(params, token, pos, keys, active, cache, slots, draft,
               *, cfg, spec_k, rounds, backend, enable, top_k_static,
               policy, draft_len, greedy_only):
    """The traced body of ONE continuous-batching decode step (dense).

    Shared verbatim by the per-step jit (``_scheduler_step``) and by every
    iteration of the fused-horizon scan (``_scheduler_horizon``): a single
    definition is what makes step_horizon a pure scheduling change —
    K-fused serving runs bit-identical math to per-step serving because
    there is literally one body to compile.
    """
    if draft_len == 1:
        logits, stepped = decode_step(cfg, params, token, pos, cache,
                                      write_mask=active)
        # inactive lanes keep their pre-step cache state — the serial
        # analogue of the verify branch's n_keep=0 rollback, and what
        # keeps a slot that finishes mid-horizon bit-frozen: the masked
        # step froze their K/V, this restores their recurrent state
        new_cache = freeze_cache_lanes(stepped, cache, active)
        ks = jax.vmap(jax.random.split)(keys)               # (B, 2, 2)
        new_keys = jnp.where(active[:, None], ks[:, 0], keys)
        with solver.mesh_policy(policy):
            nxt = sample_slots(logits, ks[:, 1], slots, spec_k=spec_k,
                               rounds=rounds, backend=backend,
                               enable=enable, top_k_static=top_k_static,
                               greedy_only=greedy_only)
        new_token = jnp.where(active, nxt, token)
        new_pos = jnp.where(active, pos + 1, pos)
        return (new_token, new_pos, new_keys, new_cache, nxt[:, None],
                jnp.zeros_like(pos))

    feed = jnp.concatenate([token[:, None], draft], axis=1)  # (B, L)
    grid, wide_cache, stash = decode_verify(cfg, params, feed, pos, cache)
    ks = jax.vmap(jax.random.split)(keys)                    # (B, 2, 2)
    new_keys = jnp.where(active[:, None], ks[:, 0], keys)
    with solver.mesh_policy(policy):
        out, n_acc = verify_slots(grid, draft, ks[:, 1], slots,
                                  spec_k=spec_k, rounds=rounds,
                                  backend=backend, enable=enable,
                                  top_k_static=top_k_static,
                                  greedy_only=greedy_only)
    n_acc = jnp.where(active, n_acc, 0)
    # live slots commit 1 + accepted rows; inactive slots (n_keep 0) get
    # every touched row restored — their state is bit-frozen, as in the
    # serial branch
    new_cache = rollback_cache_runs(wide_cache, stash, pos,
                                    jnp.where(active, 1 + n_acc, 0))
    bonus = jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0]
    new_token = jnp.where(active, bonus, token)
    new_pos = jnp.where(active, pos + 1 + n_acc, pos)
    return new_token, new_pos, new_keys, new_cache, out, n_acc


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec_k", "rounds", "backend", "enable",
                     "top_k_static", "policy", "draft_len", "greedy_only"),
    donate_argnames=("token", "pos", "keys", "cache"),
)
def _scheduler_step(params, token, pos, keys, active, cache, slots, draft,
                    *, cfg, spec_k, rounds, backend, enable, top_k_static,
                    policy=None, draft_len=1, greedy_only=False):
    """THE compiled continuous-batching decode step (module-level so the
    jit cache is shared by every scheduler instance in the process).

    One ``decode_step`` over all slots at their own positions, one
    per-slot key split, one ``sample_slots`` through the engine's batch
    axis; inactive slots are masked to keep their state frozen.  The big
    inputs are donated so XLA updates the KV cache in place instead of
    copying it every token (donation is a no-op on CPU test runs).

    ``policy`` (a hashable MeshPolicy, static BECAUSE the active solver
    policy is read at trace time) makes the step mesh-native: slot state
    arrives data-sharded, the decode forward stays row-independent under
    GSPMD batch partitioning, and every sampler solve runs through the
    engine's vocab-sharded shard_map path — token streams bit-identical
    to the single-device step (tests/test_sharded_serving.py).

    ``draft_len`` (static) selects the speculative branch: ``draft``
    carries (B, draft_len - 1) host-drafted tokens, the forward becomes
    ONE ``decode_verify`` over the (B, L) grid, acceptance runs through
    ``verify_slots`` on the engine's batch axis, and rejected cache rows
    are rolled back.  ``draft_len == 1`` compiles the serial body above
    UNCHANGED (``draft`` is an unused (B, 0) ride-along) — degeneration
    to the non-speculative step is bit-exact by construction.

    ``greedy_only`` (static): every live slot is greedy, so the sampler
    compiles its argmax-only body — no categorical draws, and for the
    verify branch no rejection-sampling machinery at all.  Key chains
    still advance identically (splits happen here, not in the sampler),
    so mixed-occupancy steps later in the same serve stay bit-exact.

    Returns (token, pos, keys, cache, out (B, draft_len), n_acc (B,)):
    row b emitted ``out[b, :n_acc[b] + 1]``.
    """
    return _step_body(params, token, pos, keys, active, cache, slots,
                      draft, cfg=cfg, spec_k=spec_k, rounds=rounds,
                      backend=backend, enable=enable,
                      top_k_static=top_k_static, policy=policy,
                      draft_len=draft_len, greedy_only=greedy_only)


def _step_body_paged(params, token, pos, keys, active, pool, table, slots,
                     draft, *, cfg, context, spec_k, rounds, backend,
                     enable, top_k_static, policy, draft_len, greedy_only,
                     page_impl):
    """``_step_body`` over the page-table cache — the single traced step
    shared by ``_scheduler_step_paged`` and ``_scheduler_horizon_paged``.
    """
    if draft_len == 1:
        logits, new_pool = decode_step_paged(
            cfg, params, token, pos, pool, table, context=context,
            impl=page_impl)
        ks = jax.vmap(jax.random.split)(keys)               # (B, 2, 2)
        new_keys = jnp.where(active[:, None], ks[:, 0], keys)
        with solver.mesh_policy(policy):
            nxt = sample_slots(logits, ks[:, 1], slots, spec_k=spec_k,
                               rounds=rounds, backend=backend,
                               enable=enable, top_k_static=top_k_static,
                               greedy_only=greedy_only)
        new_token = jnp.where(active, nxt, token)
        new_pos = jnp.where(active, pos + 1, pos)
        return (new_token, new_pos, new_keys, new_pool, nxt[:, None],
                jnp.zeros_like(pos))

    feed = jnp.concatenate([token[:, None], draft], axis=1)  # (B, L)
    grid, wide_pool, stash = decode_verify_paged(
        cfg, params, feed, pos, pool, table, context=context,
        impl=page_impl)
    ks = jax.vmap(jax.random.split)(keys)                    # (B, 2, 2)
    new_keys = jnp.where(active[:, None], ks[:, 0], keys)
    with solver.mesh_policy(policy):
        out, n_acc = verify_slots(grid, draft, ks[:, 1], slots,
                                  spec_k=spec_k, rounds=rounds,
                                  backend=backend, enable=enable,
                                  top_k_static=top_k_static,
                                  greedy_only=greedy_only)
    n_acc = jnp.where(active, n_acc, 0)
    new_pool = rollback_paged_runs(
        wide_pool, stash, table, pos, jnp.where(active, 1 + n_acc, 0),
        context=context)
    bonus = jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0]
    new_token = jnp.where(active, bonus, token)
    new_pos = jnp.where(active, pos + 1 + n_acc, pos)
    return new_token, new_pos, new_keys, new_pool, out, n_acc


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "context", "spec_k", "rounds", "backend",
                     "enable", "top_k_static", "policy", "draft_len",
                     "greedy_only", "page_impl"),
    donate_argnames=("token", "pos", "keys", "pool"),
)
def _scheduler_step_paged(params, token, pos, keys, active, pool, table,
                          slots, draft, *, cfg, context, spec_k, rounds,
                          backend, enable, top_k_static, policy=None,
                          draft_len=1, greedy_only=False,
                          page_impl="gather"):
    """``_scheduler_step`` over the page-table cache (DESIGN.md §13).

    The dense slotted cache is replaced by (page pool, page table): the
    forward goes through the paged duals (``decode_step_paged`` /
    ``decode_verify_paged``) and speculative rollback through
    ``rollback_paged_runs``; key chains, sampler solves, and the
    active-slot masking are IDENTICAL to the dense step, which is what
    keeps paged token streams bit-identical to dense ones.  The table is
    read-only here (admission/eviction own it) and intentionally not
    donated; inactive or evicted slots' table rows point at the null page,
    so their dead per-step writes never touch a live request's pages.
    """
    return _step_body_paged(params, token, pos, keys, active, pool, table,
                            slots, draft, cfg=cfg, context=context,
                            spec_k=spec_k, rounds=rounds, backend=backend,
                            enable=enable, top_k_static=top_k_static,
                            policy=policy, draft_len=draft_len,
                            greedy_only=greedy_only, page_impl=page_impl)


def _horizon_done(active, remaining, eos, out, n_acc):
    """In-scan EOS/budget detection: the device dual of the host's
    truncation rules in ``ContinuousScheduler._finish_run``.

    A live slot emitted ``1 + n_acc`` tokens this iteration.  It is done
    when that meets its remaining budget, or when an EOS lands anywhere in
    the budget-truncated run — the same order the host applies (budget
    first, then EOS within the surviving prefix), so device freeze and
    host eviction always agree on the iteration a slot stops.  ``eos`` is
    -1 for slots without a stop token (never matches a token id >= 0).

    Returns (done (B,) bool, emitted (B,) int32).
    """
    emitted = jnp.where(active, 1 + n_acc, 0)
    lim = jnp.minimum(emitted, remaining)
    cols = jnp.arange(out.shape[1], dtype=jnp.int32)[None, :]
    hit_eos = jnp.any((out == eos[:, None]) & (cols < lim[:, None]), axis=1)
    done = active & ((emitted >= remaining) | hit_eos)
    return done, emitted


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec_k", "rounds", "backend", "enable",
                     "top_k_static", "policy", "draft_len", "greedy_only",
                     "horizon"),
    donate_argnames=("token", "pos", "keys", "cache"),
)
def _scheduler_horizon(params, token, pos, keys, active, remaining, eos,
                       cache, slots, *, cfg, spec_k, rounds, backend,
                       enable, top_k_static, policy=None, draft_len=1,
                       greedy_only=False, horizon=2):
    """``horizon`` (= K) scheduler steps fused into ONE compiled scan.

    The paper's dispatch-amortization move applied to serving (DESIGN.md
    §14): instead of one jitted dispatch + one device→host sync per
    decode step, the scan runs K iterations of the SAME traced step body
    as ``_scheduler_step`` on-device, stacking each iteration's emissions
    into (K, B, L) / (K, B) buffers the host replays once per horizon.

    EOS/budget detection moves inside the scan (``_horizon_done``): a slot
    finishing at iteration j < K drops out of ``active`` and its token /
    pos / key / cache state is bit-frozen by the body's own masking for
    the remaining K - j iterations — exactly the state per-step serving
    would have left at eviction time.  Speculative horizons (draft_len >
    1) draft on-device by repeating the carried token (the device dual of
    ``RepeatLastDrafter``); host drafters cannot run mid-scan.

    ``ys`` also records each iteration's ENTRY active mask so the host
    replay can tell which rows of the emission buffer are real.
    """
    B = token.shape[0]

    def body(carry, _):
        token, pos, keys, cache, active, remaining = carry
        if draft_len > 1:
            draft = jnp.broadcast_to(token[:, None], (B, draft_len - 1))
        else:
            draft = jnp.zeros((B, 0), jnp.int32)
        token, pos, keys, cache, out, n_acc = _step_body(
            params, token, pos, keys, active, cache, slots, draft,
            cfg=cfg, spec_k=spec_k, rounds=rounds, backend=backend,
            enable=enable, top_k_static=top_k_static, policy=policy,
            draft_len=draft_len, greedy_only=greedy_only)
        done, emitted = _horizon_done(active, remaining, eos, out, n_acc)
        new_carry = (token, pos, keys, cache, active & ~done,
                     remaining - emitted)
        return new_carry, (out, n_acc, active)

    carry = (token, pos, keys, cache, active, remaining)
    (token, pos, keys, cache, _, _), (outs, accs, acts) = jax.lax.scan(
        body, carry, None, length=horizon)
    return token, pos, keys, cache, outs, accs, acts


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "context", "spec_k", "rounds", "backend",
                     "enable", "top_k_static", "policy", "draft_len",
                     "greedy_only", "page_impl", "horizon"),
    donate_argnames=("token", "pos", "keys", "pool"),
)
def _scheduler_horizon_paged(params, token, pos, keys, active, remaining,
                             eos, pool, table, slots, *, cfg, context,
                             spec_k, rounds, backend, enable, top_k_static,
                             policy=None, draft_len=1, greedy_only=False,
                             page_impl="gather", horizon=2):
    """``_scheduler_horizon`` over the page-table cache.

    One paged-specific move: each iteration masks the (read-only) page
    table through ``mask_table_rows`` so slots that finished EARLIER IN
    THIS SCAN write their dead K/V into the null page — re-deriving, from
    the carried ``active`` mask, the exact table state per-step eviction
    would have produced on the host.  Without it a frozen slot's stale
    chain keeps absorbing writes, and a wrapped ring position could land
    them in a COW page another slot still reads.
    """
    B = token.shape[0]

    def body(carry, _):
        token, pos, keys, pool, active, remaining = carry
        table_eff = mask_table_rows(table, active)
        if draft_len > 1:
            draft = jnp.broadcast_to(token[:, None], (B, draft_len - 1))
        else:
            draft = jnp.zeros((B, 0), jnp.int32)
        token, pos, keys, pool, out, n_acc = _step_body_paged(
            params, token, pos, keys, active, pool, table_eff, slots,
            draft, cfg=cfg, context=context, spec_k=spec_k, rounds=rounds,
            backend=backend, enable=enable, top_k_static=top_k_static,
            policy=policy, draft_len=draft_len, greedy_only=greedy_only,
            page_impl=page_impl)
        done, emitted = _horizon_done(active, remaining, eos, out, n_acc)
        new_carry = (token, pos, keys, pool, active & ~done,
                     remaining - emitted)
        return new_carry, (out, n_acc, active)

    carry = (token, pos, keys, pool, active, remaining)
    (token, pos, keys, pool, _, _), (outs, accs, acts) = jax.lax.scan(
        body, carry, None, length=horizon)
    return token, pos, keys, pool, outs, accs, acts


class ContinuousScheduler:
    """Slot-based continuous batcher over the runahead sampler.

    One instance owns the slotted cache; callers drive it with ``admit``
    / ``step`` / ``pop_finished``.  The step function is jitted once per
    distinct (cfg, solver statics, feature-gate) key and shared across
    instances — slot occupancy, positions, and per-slot sampler values
    are all traced data, never recompile triggers.  Prompt-length changes
    recompile the admission prefill only, never the step.

    ``mesh`` makes serving mesh-native: slot state shards over the data
    axes (SERVE_RULES "slot"), sampler solves vocab-shard over
    "solver_vocab" via the engine's MeshPolicy, and per-request token
    streams stay bit-identical to the single-device path (the policy is
    part of the compiled step's static key).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int,
        context: int,
        spec_k: int = 5,
        rounds: int = 8,
        backend: str = "jnp",
        cache_dtype=jnp.bfloat16,
        mesh: jax.sharding.Mesh | None = None,
        draft_len: int = 1,
        drafter: DraftSource | None = None,
        page_size: int | None = None,
        cache_pages: int | None = None,
        page_impl: str = "gather",
        step_horizon: int = 1,
        draft_len_auto: bool = False,
        max_draft_len: int | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.context = context
        self.spec_k, self.rounds, self.backend = spec_k, rounds, backend
        self.cache_dtype = cache_dtype
        self.mesh = mesh
        if draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {draft_len}")
        if draft_len_auto and draft_len < 2:
            raise ValueError(
                "draft_len_auto needs an initial draft_len >= 2: L = 1 "
                "never drafts, so the acceptance window that drives "
                "decide_draft_len would stay empty forever"
            )
        if max_draft_len is None:
            max_draft_len = max(draft_len, 8) if draft_len_auto else (
                draft_len)
        if max_draft_len < draft_len:
            raise ValueError(
                f"max_draft_len {max_draft_len} < draft_len {draft_len}"
            )
        if max_draft_len > 1 and not verify_supported(cfg):
            raise ValueError(
                "speculative decoding (draft_len > 1) needs an all-dense "
                "layer stack — this config has recurrent/MoE layers "
                "(see models.decode.verify_supported)"
            )
        if max_draft_len > context:
            raise ValueError(
                f"draft_len {max_draft_len} exceeds cache capacity "
                f"{context}"
            )
        self.draft_len = draft_len
        self.draft_len_auto = draft_len_auto
        self.max_draft_len = max_draft_len
        # acceptance window for live re-deciding of L (DESIGN.md §14): L
        # is re-decided at each horizon boundary once the window holds at
        # least this many drafted tokens
        self.draft_retune_min = 64
        self._retune_drafted_mark = 0
        self._retune_accepted_mark = 0
        self.drafter: DraftSource = (
            drafter if drafter is not None else NGramDrafter()
        )
        if step_horizon < 1:
            raise ValueError(
                f"step_horizon must be >= 1, got {step_horizon}")
        self.step_horizon = step_horizon
        if step_horizon > 1 and max_draft_len > 1 and not getattr(
                self.drafter, "device_capable", False):
            raise ValueError(
                "fused horizons (step_horizon > 1) draft ON-DEVICE inside "
                "the scan, so a speculative scheduler needs a "
                "device-capable drafter (serving.draft.RepeatLastDrafter) "
                "— host drafters cannot run mid-scan"
            )

        self.paged = page_size is not None
        self.page_size = page_size
        self.page_impl = page_impl
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if page_impl not in ("gather", "pallas"):
                raise ValueError(f"unknown page_impl {page_impl!r}")
            if not paged_supported(cfg):
                raise ValueError(
                    "the paged KV cache needs an all-dense layer stack "
                    "(see models.decode.paged_supported)")
            if cache_dtype == jnp.int8:
                raise ValueError("paged cache does not support int8 K/V")
            self.max_chain = pages_for(context, page_size)
            if cache_pages is None:
                # dense-equivalent capacity + the reserved null page
                cache_pages = n_slots * self.max_chain + 1
            self.cache = None
            self.pool = init_paged_pool(cfg, cache_pages, page_size,
                                        cache_dtype)
            self.table = jnp.zeros((n_slots, self.max_chain), jnp.int32)
            self.alloc = PageAllocator(cache_pages, page_size)
            self._chains: list[list[int] | None] = [None] * n_slots
            self.n_prefix_hits = 0       # admissions that forked a prefix
            self.n_prefill_skipped = 0   # prompt tokens never re-prefilled
        else:
            if cache_pages is not None:
                raise ValueError("cache_pages requires page_size")
            self.cache = init_cache(cfg, n_slots, context, cache_dtype)
        self.token = jnp.zeros((n_slots,), jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.keys = jnp.zeros((n_slots, 2), jnp.uint32)
        self._policy = None
        if mesh is not None:
            # each device runs the whole model on its own slots, so the
            # params are replicated over the mesh once, here — left
            # uncommitted they would sit on one device
            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            self._policy, slot_axes = slot_policy(mesh, n_slots)
            self.token, self.pos, self.keys, dense_cache = (
                _shard_slot_state(mesh, slot_axes, self.token, self.pos,
                                  self.keys,
                                  {} if self.paged else self.cache)
            )
            if self.paged:
                page_axes = resolve_axes(mesh, SERVE_RULES, "page")
                n_pg = self.alloc.n_pages
                if page_axes is not None and n_pg % resolved_axis_size(
                        mesh, page_axes):
                    page_axes = None
                self.pool = jax.tree_util.tree_map(
                    lambda leaf: jax.device_put(
                        leaf,
                        NamedSharding(mesh, P(None, page_axes,
                                              *(None,) * (leaf.ndim - 2))),
                    ),
                    self.pool,
                )
                self.table = jax.device_put(
                    self.table, NamedSharding(mesh, P(None, None)))
            else:
                self.cache = dense_cache
        self.slots: list[_SlotInfo | None] = [None] * n_slots
        self._finished: list[FinishedRequest] = []
        self._step_args = None     # (slots_arr, active, enable, k, greedy)
        self.n_decode_steps = 0          # batched decode iterations (stats)
        # compiled step and admission programs launched (stats); the
        # eager array updates around them (slot writes, step inputs) are
        # device launches too and are not counted here: a device trace
        # counts them all (launches_per_step, bench/spans.py)
        self.n_dispatches = 0
        self.n_host_syncs = 0            # device->host reads (stats)
        self.n_drafted = 0               # drafted tokens offered to verify
        self.n_accepted = 0              # drafted tokens accepted
        self.n_admissions = 0            # requests prefilled into a slot
        self.n_horizons = 0              # fused scan launches (K > 1 only)
        self.n_wasted_steps = 0          # all-idle scan iterations (K > 1)
        self.n_draft_retunes = 0         # live decide_draft_len L switches

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted."""
        return self.n_accepted / self.n_drafted if self.n_drafted else 0.0

    # -- occupancy ----------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def has_free_slot(self) -> bool:
        return self.n_active < self.n_slots

    def pop_finished(self) -> list[FinishedRequest]:
        done, self._finished = self._finished, []
        return done

    @property
    def peak_pages(self) -> int:
        """High-water mark of live pool pages (paged mode; else 0)."""
        return self.alloc.peak_used if self.paged else 0

    def validate_request(self, n_new: int, sampler: SamplerConfig,
                         prompt_len: int | None = None) -> None:
        """Reject what the shared compiled step cannot serve — called by
        the server at submit() time, BEFORE a request enters the queue."""
        if n_new < 1:
            raise ValueError(f"n_new must be >= 1, got {n_new}")
        if (sampler.spec_k, sampler.rounds, sampler.backend) != (
            self.spec_k, self.rounds, self.backend
        ):
            raise ValueError(
                "request sampler spec_k/rounds/backend must match the "
                "scheduler's (they are compiled into the shared step)"
            )
        if self.paged and prompt_len is not None:
            # chains are provisioned for max_draft_len so a live retune
            # of L never outgrows an in-flight request's pages
            plan = plan_chain(prompt_len, n_new, self.context,
                              self.page_size, self.max_draft_len)
            if plan.chain_len > self.alloc.n_pages - 1:
                raise ValueError(
                    f"request needs {plan.chain_len} pages even with an "
                    f"empty pool; pool holds {self.alloc.n_pages - 1} "
                    "(admission could never succeed — raise cache_pages)"
                )

    # -- admission ----------------------------------------------------------

    def admit(
        self,
        rid: Any,
        prompt,
        n_new: int,
        seed: int,
        sampler: SamplerConfig = SamplerConfig(),
        *,
        encoder_frames: jax.Array | None = None,
        eos_id: int | None = None,
    ) -> bool:
        """Prefill one request into a free slot; False when pool is full.

        Replays exactly the one-shot engine's opening moves for this
        request at B=1: prefill, split the request key, sample the first
        token from the prefill logits with the request's own config.
        """
        with TraceAnnotation("serve.admit", rid=rid,
                             length=np.size(prompt)):
            return self._admit(rid, prompt, n_new, seed, sampler,
                               encoder_frames, eos_id)

    def _admit(self, rid, prompt, n_new, seed, sampler, encoder_frames,
               eos_id) -> bool:
        prompt = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
        self.validate_request(n_new, sampler, prompt_len=prompt.shape[1])
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return False
        i = free[0]
        chain: list[int] | None = None
        if self.paged:
            if encoder_frames is not None:
                raise ValueError("paged cache does not serve enc-dec archs")
            ptoks = [int(t) for t in np.asarray(prompt[0])]
            plan = plan_chain(prompt.shape[1], n_new, self.context,
                              self.page_size, self.max_draft_len)
            # longest registered prefix wins: each hit is one page of
            # prompt K/V admission never recomputes (COW fork)
            chain = []
            skip = 0
            if not plan.wrap:
                for j in range(1, plan.share_cap + 1):
                    pid = self.alloc.lookup_prefix(
                        prefix_key(ptoks, j * self.page_size))
                    if pid is None:
                        break
                    chain.append(pid)
                skip = len(chain)
            if skip:
                self.alloc.fork_prefix(chain)
                self.n_prefix_hits += 1
                self.n_prefill_skipped += skip * self.page_size
            for _ in range(plan.chain_len - skip):
                pid = self.alloc.alloc()
                if pid is None:          # pool exhausted: undo, try later
                    self.alloc.release(chain)
                    return False
                chain.append(pid)
            with TraceAnnotation("serve.admit.prefill"):
                logits, self.pool, key, sub = _admit_paged(
                    self.params, prompt, self.pool,
                    jnp.asarray(chain, jnp.int32), jax.random.PRNGKey(seed),
                    cfg=self.cfg, context=self.context,
                    page_size=self.page_size, skip=skip,
                )
            if not plan.wrap:
                for j in range(plan.register_cap):
                    self.alloc.register_prefix(
                        prefix_key(ptoks, (j + 1) * self.page_size),
                        chain[j])
        elif encoder_frames is None:
            with TraceAnnotation("serve.admit.prefill"):
                logits, self.cache, key, sub = _admit_slot(
                    self.params, prompt, self.cache, jnp.int32(i),
                    jax.random.PRNGKey(seed), cfg=self.cfg,
                    context=self.context, cache_dtype=self.cache_dtype,
                )
        else:                        # enc-dec: frames vary per request,
            # keep this rare path eager rather than grow the jit cache
            with TraceAnnotation("serve.admit.prefill"):
                logits, self.cache = prefill_into_slot(
                    self.cfg, self.params, prompt, self.context, self.cache,
                    i, encoder_frames=encoder_frames,
                    kv_dtype=self.cache_dtype,
                )
                key, sub = jax.random.split(jax.random.PRNGKey(seed))
        with TraceAnnotation("serve.admit.sample"):
            first = int(_admit_sample(
                logits, sub[None], SlotSamplers.stack([sampler]),
                spec_k=self.spec_k, rounds=self.rounds,
                backend=self.backend, enable=_enable_bits([sampler]),
                top_k_static=_static_top_k([sampler]),
                greedy_only=sampler.greedy,
            )[0])
        with TraceAnnotation("serve.admit.book"):
            self.n_dispatches += 2       # prefill + first-token sample
            self.n_host_syncs += 1       # int(first)
            self.n_admissions += 1

            self.token = self.token.at[i].set(first)
            self.pos = self.pos.at[i].set(prompt.shape[1])
            self.keys = self.keys.at[i].set(key)
            info = _SlotInfo(
                rid, n_new - 1, [first], sampler,
                context=[int(t) for t in np.asarray(prompt[0])] + [first],
                eos_id=eos_id,
            )
            if info.remaining <= 0 or (eos_id is not None
                                       and first == eos_id):
                self._finished.append(FinishedRequest(rid, info.tokens))
                if self.paged:           # done at admission: pages go back
                    self.alloc.release(chain)
            else:
                self.slots[i] = info
                self._step_args = None   # occupancy changed
                if self.paged:
                    self._chains[i] = chain
                    row = np.zeros((self.max_chain,), np.int32)
                    row[:len(chain)] = chain
                    self.table = self.table.at[i].set(jnp.asarray(row))
        return True

    # -- the compiled decode step -------------------------------------------

    def _ensure_step_args(self, live):
        """(Re)build the occupancy-derived step arguments; cached until
        admission/eviction changes which slots are live."""
        if self._step_args is None:
            idle = SamplerConfig(spec_k=self.spec_k, rounds=self.rounds,
                                 backend=self.backend)
            self._step_args = (
                SlotSamplers.stack([s.sampler if s is not None else idle
                                    for s in self.slots]),
                jnp.asarray([s is not None for s in self.slots]),
                _enable_bits(live),
                _static_top_k(live),
                all(c.greedy for c in live),
            )
        return self._step_args

    def _finish_run(self, info: _SlotInfo, run: list[int]):
        """Budget-then-EOS truncation of one slot's emitted run — the
        host contract ``_horizon_done`` mirrors on-device.  Returns
        (surviving run, done)."""
        done = False
        if len(run) >= info.remaining:       # budget truncation
            run = run[: info.remaining]
            done = True
        if info.eos_id is not None and info.eos_id in run:
            run = run[: run.index(info.eos_id) + 1]   # EOS truncation
            done = True
        return run, done

    def _commit_run(self, i: int, info: _SlotInfo, run: list[int],
                    done: bool, emitted: dict[Any, list[int]]) -> None:
        """Book one slot's surviving run; evict on done."""
        info.tokens.extend(run)
        info.context.extend(run)
        info.remaining -= len(run)
        emitted.setdefault(info.rid, []).extend(run)
        if done:
            self._finished.append(FinishedRequest(info.rid, info.tokens))
            self.slots[i] = None                     # evict: slot free
            self._step_args = None
            if self.paged:
                # decref the chain (shared prefix pages stay live for
                # their other holders) and point the slot's table row
                # at the null page so its dead per-step writes can
                # never land in a recycled page
                self.alloc.release(self._chains[i])
                self._chains[i] = None
                self.table = self.table.at[i].set(0)

    def step(self) -> dict[Any, list[int]]:
        """Advance serving by ONE host-visible boundary.

        ``step_horizon == 1``: one decode step over every active slot —
        one jitted dispatch, one device→host sync, exactly the historical
        per-step scheduler.  ``step_horizon == K > 1``: one fused
        ``lax.scan`` horizon of K decode iterations — still one dispatch
        and one sync, with EOS/budget freezing handled on-device and the
        K iterations replayed into host state here at the boundary
        (DESIGN.md §14).  Either way the return value maps each live
        request to every token it emitted this call.

        Admission/eviction (and therefore the server's drain loop) only
        ever run between calls — fusing K steps moves the host/device
        boundary, never the scheduling semantics.
        """
        with TraceAnnotation("serve.step", live=self.n_active):
            if self.step_horizon == 1:
                return self._step_serial()
            return self._step_fused()

    def _step_serial(self) -> dict[Any, list[int]]:
        """One decode step over every active slot: {rid: tokens emitted}.

        Inactive slots ride along masked out — their token/pos/key stay
        frozen and their cache rows hold dead data until re-admission
        overwrites them — so the launch shape never changes.

        Non-speculative steps emit exactly one token per live slot; with
        ``draft_len`` L > 1 each live slot emits 1..L tokens (accepted
        drafts + the verify correction/bonus).  Emitted runs are truncated
        host-side at the request's remaining budget and at its first
        ``eos_id`` — truncation always coincides with eviction, so a live
        slot's device position never diverges from its host history.
        """
        live = [s.sampler for s in self.slots if s is not None]
        if not live:
            return {}
        L = self.draft_len
        with TraceAnnotation("serve.step.prepare"):
            slots_arr, active, enable, top_k_static, greedy_only = (
                self._ensure_step_args(live))
            if L > 1:                    # host-side draft between steps
                draft_host = np.zeros((self.n_slots, L - 1), np.int32)
                for i, info in enumerate(self.slots):
                    if info is not None:
                        draft_host[i] = self.drafter(info.context, L - 1)
                draft = jnp.asarray(draft_host)
            else:
                draft = jnp.zeros((self.n_slots, 0), jnp.int32)

        with TraceAnnotation("serve.step.launch"):
            if self.paged:
                (self.token, self.pos, self.keys, self.pool, out,
                 n_acc) = _scheduler_step_paged(
                    self.params, self.token, self.pos, self.keys, active,
                    self.pool, self.table, slots_arr, draft,
                    cfg=self.cfg, context=self.context, spec_k=self.spec_k,
                    rounds=self.rounds, backend=self.backend, enable=enable,
                    top_k_static=top_k_static, policy=self._policy,
                    draft_len=L, greedy_only=greedy_only,
                    page_impl=self.page_impl,
                )
            else:
                (self.token, self.pos, self.keys, self.cache, out,
                 n_acc) = _scheduler_step(
                    self.params, self.token, self.pos, self.keys, active,
                    self.cache, slots_arr, draft,
                    cfg=self.cfg, spec_k=self.spec_k, rounds=self.rounds,
                    backend=self.backend, enable=enable,
                    top_k_static=top_k_static, policy=self._policy,
                    draft_len=L, greedy_only=greedy_only,
                )
        self.n_decode_steps += 1
        self.n_dispatches += 1
        self.n_host_syncs += 1
        self.n_drafted += (L - 1) * len(live)

        with TraceAnnotation("serve.step.readback"):
            out_host = np.asarray(out)
            acc_host = np.asarray(n_acc)
        emitted: dict[Any, list[int]] = {}
        with TraceAnnotation("serve.step.commit"):
            for i, info in enumerate(self.slots):
                if info is None:
                    continue
                self.n_accepted += int(acc_host[i])
                run = [int(t) for t in out_host[i, : int(acc_host[i]) + 1]]
                run, done = self._finish_run(info, run)
                self._commit_run(i, info, run, done, emitted)
            self._maybe_retune_draft_len()
        return emitted

    def _step_fused(self) -> dict[Any, list[int]]:
        """One fused horizon: K = ``step_horizon`` decode iterations in a
        single compiled scan, then one host replay (DESIGN.md §14).

        The replay walks the (K, B, L) emission buffer in iteration order
        and pushes each live row through the SAME truncation/eviction
        path as per-step serving; the device's entry-mask record (``acts``)
        must agree with the host slot table at every iteration — a
        divergence would mean the in-scan done logic and the host contract
        drifted apart, so it raises instead of mis-attributing tokens.
        """
        live = [s.sampler for s in self.slots if s is not None]
        if not live:
            return {}
        K = self.step_horizon
        L = self.draft_len
        with TraceAnnotation("serve.step.prepare"):
            slots_arr, active, enable, top_k_static, greedy_only = (
                self._ensure_step_args(live))
            remaining = jnp.asarray(
                [s.remaining if s is not None else 0 for s in self.slots],
                jnp.int32)
            eos = jnp.asarray(
                [-1 if s is None or s.eos_id is None else s.eos_id
                 for s in self.slots], jnp.int32)

        with TraceAnnotation("serve.step.launch"):
            if self.paged:
                (self.token, self.pos, self.keys, self.pool, outs, accs,
                 acts) = _scheduler_horizon_paged(
                    self.params, self.token, self.pos, self.keys, active,
                    remaining, eos, self.pool, self.table, slots_arr,
                    cfg=self.cfg, context=self.context, spec_k=self.spec_k,
                    rounds=self.rounds, backend=self.backend, enable=enable,
                    top_k_static=top_k_static, policy=self._policy,
                    draft_len=L, greedy_only=greedy_only,
                    page_impl=self.page_impl, horizon=K,
                )
            else:
                (self.token, self.pos, self.keys, self.cache, outs, accs,
                 acts) = _scheduler_horizon(
                    self.params, self.token, self.pos, self.keys, active,
                    remaining, eos, self.cache, slots_arr,
                    cfg=self.cfg, spec_k=self.spec_k, rounds=self.rounds,
                    backend=self.backend, enable=enable,
                    top_k_static=top_k_static, policy=self._policy,
                    draft_len=L, greedy_only=greedy_only, horizon=K,
                )
        self.n_decode_steps += K
        self.n_dispatches += 1           # the whole horizon is one launch
        self.n_host_syncs += 1           # ... and one boundary readback
        self.n_horizons += 1

        with TraceAnnotation("serve.step.readback"):
            outs_host = np.asarray(outs)     # (K, B, L)
            accs_host = np.asarray(accs)     # (K, B)
            acts_host = np.asarray(acts)     # (K, B) entry mask per step
        emitted: dict[Any, list[int]] = {}
        with TraceAnnotation("serve.step.commit"):
            self.n_wasted_steps += int((~acts_host.any(axis=1)).sum())
            for j in range(K):
                n_live_j = int(acts_host[j].sum())
                self.n_drafted += (L - 1) * n_live_j
                for i, info in enumerate(self.slots):
                    if bool(acts_host[j, i]) != (info is not None):
                        raise RuntimeError(
                            "fused horizon freeze mask diverged from the "
                            f"host slot table at iteration {j}, slot {i} — "
                            "device done-detection and host truncation "
                            "disagree"
                        )
                    if info is None:
                        continue
                    self.n_accepted += int(accs_host[j, i])
                    run = [int(t) for t in
                           outs_host[j, i, : int(accs_host[j, i]) + 1]]
                    run, done = self._finish_run(info, run)
                    self._commit_run(i, info, run, done, emitted)
            self._maybe_retune_draft_len()
        return emitted

    # -- live re-tuning -----------------------------------------------------

    def _maybe_retune_draft_len(self) -> None:
        """Re-decide L from the LIVE acceptance window at a boundary.

        The startup ``--draft-len auto`` guess prices speculation off an
        assumed acceptance rate; once the verify counters have seen at
        least ``draft_retune_min`` drafted tokens since the last decision,
        the measured window rate replaces it (``tuning.decide_draft_len``).
        L is a static of the compiled step, so a switch costs one retrace
        per distinct L — bounded by ``max_draft_len``, and the floor of 2
        keeps the probe wide enough that the window keeps filling.
        """
        if not self.draft_len_auto:
            return
        drafted = self.n_drafted - self._retune_drafted_mark
        if drafted < self.draft_retune_min:
            return
        accepted = self.n_accepted - self._retune_accepted_mark
        self._retune_drafted_mark = self.n_drafted
        self._retune_accepted_mark = self.n_accepted
        from repro.core.tuning import DISPATCH_OVERHEAD, decide_draft_len
        new_len = max(2, decide_draft_len(
            acceptance=accepted / drafted,
            overhead=DISPATCH_OVERHEAD / self.step_horizon,
            max_draft_len=self.max_draft_len,
        ))
        if new_len != self.draft_len:
            self.draft_len = new_len
            self.n_draft_retunes += 1

    def suggested_step_horizon(self, *, max_horizon: int = 32) -> int:
        """K the cost model would pick for the CURRENT live workload.

        Prices ``tuning.decide_step_horizon`` off live counters: mean
        remaining budget over occupied slots, converted from tokens to
        device iterations through the measured acceptance rate (a
        speculative step emits ~``1 + acceptance * (L - 1)`` tokens).
        The horizon itself stays fixed per scheduler instance — switching
        K retraces the scan — so callers read this between serves.
        """
        live = [s.remaining for s in self.slots if s is not None]
        if not live:
            return self.step_horizon
        per_step = 1.0 + self.acceptance_rate * (self.draft_len - 1)
        mean_steps = max(1.0, (sum(live) / len(live)) / per_step)
        from repro.core.tuning import decide_step_horizon
        return decide_step_horizon(mean_remaining=mean_steps,
                                   max_horizon=max_horizon)
