"""Cost-model-driven solver autotuning (DESIGN.md §11).

The engine's knobs — speculation depth ``spec_k``, placement
(vocab-sharded / data-sharded / single-device), and backend — used to be
hard-coded, and ``BENCH_scaling.json`` proves the hard-coded policy wrong
at scale: the per-round psum join dominates once vocab shards are small
(the collective-overhead regime of the many-core machine model, Haque et
al. arXiv:1402.0264), so the jnp solver round REGRESSES 641 µs -> 1374 µs
from 1 -> 8 forced host devices.  This module makes every knob a
*decision*, selected per static config at trace time:

  key = (kind, B, V, dtype, backend-preference, device_count, device_kind,
         iterations)

Two tiers:

  1. **Analytic cost model** (always on) — seeded from the roofline
     constants in ``benchmarks/roofline.py`` and the loop-aware HLO cost
     extraction in ``launch/hlo_cost.py``:

       per-round  = max(grid FLOPs / peak, grid bytes / mem_bw)
                    + backend dispatch overhead
                    + join term (vocab-sharded only):
                        alpha * log2(shards) + payload * shards / link_bw

     minimised over ``spec_k`` and placement under the constraint
     ``rounds * spec_k >= iterations`` (the caller's serial-step budget,
     which the tuner PRESERVES — that is what keeps every tuned
     configuration bit-identical to the serial sign-bit walk).

  2. **Measured tier** (``tune=True``, :func:`autotune`, or
     ``REPRO_AUTOTUNE=1``) — micro-benchmarks the top analytic candidates
     plus the single-device baseline on the live devices, lowers the
     winning sharded candidates and prices their REAL collective join from
     HLO (``collective_detail`` of ``analyse_hlo``), and persists winners
     in a schema-versioned JSON cache loadable at import.  Because the
     single-device fallback is always in the measured candidate set, a
     measured decision is never worse than single-device (up to timing
     noise) — an active mesh no longer *forces* the regressing
     vocab-sharded join.

Correctness contract: a Decision only re-chooses HOW the serial-step
budget is spent (round decomposition, placement, backend), never how many
steps are spent; the engine's speculative rounds are bit-identical to
serial sign-bit bisection for ANY (rounds, spec_k) decomposition of the
same budget (tests/test_solver_properties.py), so tuning is invisible to
every differential harness in the repo.

Forcing and clearing decisions (see DESIGN.md §11):

  * ``tuning.override(spec_k=3, placement="vocab")`` — force fields for
    the enclosed traces (None fields keep the tuner's choice);
  * ``tuning.disabled()`` or ``REPRO_DISABLE_TUNING=1`` — pin the
    caller's legacy fixed configuration (pre-tuning behaviour);
  * ``tuning.clear_cache()`` — drop in-memory + on-disk measured winners;
  * ``REPRO_TUNING_CACHE=/path.json`` — relocate the persistent cache.

Decisions are read at TRACE time (like ``solver.mesh_policy``): an outer
jit that should re-tune must clear its own cache — a compiled step keeps
the decision it traced with.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import tempfile
import threading
from typing import Callable, Mapping, Sequence

import jax

# v2: ConfigKey grew page_size (the paged-KV cache granularity,
# DESIGN.md §13) — v1 caches are ignored wholesale rather than migrated
# v3: ConfigKey grew step_horizon (the fused serving horizon, DESIGN.md
# §14) and Decision grew the chosen step_horizon — v2 caches likewise
# ignored wholesale
# v4: the file grew a SEPARATE "kernels" section (KernelKey ->
# KernelDecision, DESIGN.md §15).  Solver entries did NOT change shape,
# so a v3 file's entries are replayed legally (its kernel section is
# simply absent -> analytic); v2-and-older still ignored wholesale.
SCHEMA_VERSION = 4
_COMPAT_SCHEMAS = (3, SCHEMA_VERSION)   # solver entries replayable from

# Fixed per-decode-step serving cost (dispatch + host sync) in units of
# one grid row's forward work, calibrated from BENCH_serving.json's
# continuous cells on the CPU box (see decide_draft_len).  Shared by
# decide_draft_len and decide_step_horizon so both knobs price the same
# overhead they are amortizing.
DISPATCH_OVERHEAD = 4.3
CACHE_ENV = "REPRO_TUNING_CACHE"
DISABLE_ENV = "REPRO_DISABLE_TUNING"
AUTOTUNE_ENV = "REPRO_AUTOTUNE"

PLACEMENTS = ("single", "data", "vocab")


# ---------------------------------------------------------------------------
# decision + config key
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Decision:
    """One resolved solver configuration.

    placement: "single" (no sharding even under an active mesh policy —
    the escape hatch from the regressing join), "data" (batch rows over
    the policy's data axes only), or "vocab" (reduction dim over the
    vocab axis + rows over the data axes: the legacy mesh path).
    ``rounds`` is always ``ceil(iterations / spec_k)`` for the caller's
    budget; the engine runs a partial walk in the last round when the
    budget does not divide.
    """

    spec_k: int
    rounds: int
    placement: str
    backend: str
    source: str = "model"       # model | measured | cache | fixed | override
    draft_len: int = 1          # serving speculation depth (DESIGN.md §12):
    # tokens fed per verify step, 1 = serial decode.  Unlike spec_k this
    # knob is workload-sensitive (acceptance rate), so it is decided by
    # decide_draft_len from observed acceptance, not the roofline model.
    step_horizon: int = 1       # fused serving horizon (DESIGN.md §14):
    # decode steps per compiled scan dispatch, 1 = per-step serving.
    # Decided by decide_step_horizon from expected remaining budget —
    # another workload-priced knob, like draft_len.

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: Mapping) -> "Decision":
        return Decision(
            spec_k=int(d["spec_k"]), rounds=int(d["rounds"]),
            placement=str(d["placement"]), backend=str(d["backend"]),
            source=str(d.get("source", "cache")),
            draft_len=int(d.get("draft_len", 1)),
            step_horizon=int(d.get("step_horizon", 1)),
        )


@dataclasses.dataclass(frozen=True)
class ConfigKey:
    """The static configuration a decision is keyed by."""

    kind: str
    batch: int
    vocab: int
    dtype: str
    backend_pref: str
    device_count: int
    device_kind: str
    iterations: int
    page_size: int = 0      # paged-KV page granularity; 0 = dense ring
    # cache.  Part of the key because the paged gather reshapes the
    # attention working set: a backend/placement winner measured against
    # the dense layout must not steer a paged deployment (and vice versa).
    step_horizon: int = 0   # fused serving horizon; 0 = per-step / not
    # serving.  Part of the key because a K-fused scan changes what XLA
    # sees per dispatch (loop-hoisted constants, donation patterns): a
    # winner measured per-step must not steer a fused deployment.

    def cache_key(self) -> str:
        return "|".join((
            self.kind, f"B={self.batch}", f"V={self.vocab}", self.dtype,
            f"pref={self.backend_pref}", f"D={self.device_count}",
            self.device_kind or "cpu", f"iters={self.iterations}",
            f"page={self.page_size}", f"hz={self.step_horizon}",
        ))


def device_platform() -> tuple[str, str]:
    """(platform, device model string) of device 0 — the key's
    ``device_kind`` and the profile selector.

    Raises for a platform no profile describes, and for a TPU whose kind
    is not the chip the "tpu" profile's constants were taken from: an
    unknown device gets no default.
    """
    dev = jax.devices()[0]
    platform, kind = dev.platform, dev.device_kind
    profile_for(platform)
    if platform == "tpu" and kind not in TPU_KINDS:
        raise RuntimeError(
            f"no hardware profile for TPU kind {kind!r}; the 'tpu' "
            f"profile describes {TPU_KINDS}")
    return platform, kind


# ---------------------------------------------------------------------------
# tier 1: the analytic cost model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Per-substrate constants seeding the analytic model.

    flops / mem_bw mirror benchmarks/roofline.py's per-chip peaks (tpu)
    or are calibrated against BENCH_scaling.json's single-device rounds
    (cpu: the 641 µs jnp round at B=8, V=8192, M=15 pins the effective
    bandwidth).  join_alpha is the per-psum base latency at 2 shards —
    the paper's thread-join cost; on forced host devices it is an XLA
    runtime rendezvous measured in hundreds of µs, which is exactly why
    the naive vocab-sharded policy loses on one socket.
    ``broadcast_spill``: fraction of the (B, M, V) candidate grid the
    backend materialises to memory per round (CPU jnp materialises all
    of it; fused/tiled backends stream it).
    """

    flops: float
    mem_bw: float
    join_alpha: float
    link_bw: float
    dispatch: float
    broadcast_spill: float
    backend_overhead: Mapping[str, float] = dataclasses.field(
        default_factory=dict)
    # per-core fast-memory budget bounding one kernel grid step's working
    # set (VMEM on TPU, shared-mem-ish on GPU, a generous L2-slice stand-in
    # on CPU where "VMEM" is emulated by the interpreter anyway)
    vmem_bytes: int = 16 * 1024 * 1024


# The device kinds the "tpu" profile describes (jax's ``device_kind`` of
# a TPU v5e chip); any other TPU raises in device_platform().
TPU_KINDS = ("TPU v5 lite",)

PROFILES: dict[str, HardwareProfile] = {
    # TPU v5e (Google Cloud "TPU v5e" page): 197 TFLOP/s bf16, 819 GB/s
    # HBM, 1,600 Gbit/s ICI; VMEM is Mosaic's default 16 MiB scoped limit
    "tpu": HardwareProfile(
        flops=197e12, mem_bw=819e9, join_alpha=2e-6, link_bw=50e9,
        dispatch=4e-6, broadcast_spill=0.05,
        backend_overhead={"jnp": 0.0, "pallas": 0.0},
        vmem_bytes=16 * 1024 * 1024,
    ),
    # host-platform "devices" are threads of one socket: collectives are
    # runtime rendezvous + memcpy (BENCH_scaling.json join deltas of
    # 0.2-0.7 ms/round), and pallas runs in interpret mode (large
    # per-kernel-call overhead).
    "cpu": HardwareProfile(
        flops=8e9, mem_bw=12e9, join_alpha=350e-6, link_bw=2e9,
        dispatch=30e-6, broadcast_spill=1.0,
        backend_overhead={"jnp": 0.0, "pallas": 400e-6},
        vmem_bytes=128 * 1024 * 1024,
    ),
}

# Rough per-element evaluator cost in flops: count kinds are a compare +
# accumulate; entropy pays exp/log per element.
_KIND_FLOPS = {
    "count_above": 2.0,
    "count_below": 2.0,
    "mass_at_or_above": 3.0,
    "entropy_at_temperature": 12.0,
}


def profile_for(platform: str) -> HardwareProfile:
    """The profile for ``platform``; raises for one it does not know."""
    try:
        return PROFILES[platform]
    except KeyError:
        raise RuntimeError(
            f"no hardware profile for platform {platform!r}; known: "
            f"{sorted(PROFILES)}") from None


def predict_cost(
    key: ConfigKey,
    decision: Decision,
    ways: tuple[int, int],
    profile: HardwareProfile | None = None,
) -> float:
    """Predicted whole-solve seconds for `decision` under `key`.

    ways = (vocab_ways, data_ways) for the decision's placement.
    """
    profile = profile or profile_for(key.device_kind)
    vw, dw = ways
    m = (1 << decision.spec_k) - 1
    bloc = -(-key.batch // dw)
    vloc = -(-key.vocab // vw)
    itemsize = 2 if key.dtype in ("bfloat16", "float16") else 4
    elems = float(bloc) * vloc * m
    flops = elems * _KIND_FLOPS.get(key.kind, 4.0)
    byts = float(bloc) * vloc * itemsize * (1.0 + profile.broadcast_spill * m)
    t_eval = max(flops / profile.flops, byts / profile.mem_bw)
    t_eval += profile.backend_overhead.get(decision.backend, 0.0)
    t_join = 0.0
    if vw > 1:
        # one psum per round: alpha * log2(shards) latency plus the
        # gathered payload (every shard's (bloc, M) partials) on the link
        payload = float(bloc) * m * 4 * vw
        t_join = profile.join_alpha * math.log2(vw) + payload / profile.link_bw
    return decision.rounds * (t_eval + t_join + profile.dispatch)


def decide_draft_len(
    *,
    acceptance: float,
    token_cost: float = 1.0,
    overhead: float | None = None,
    max_draft_len: int = 8,
) -> int:
    """Pick the serving speculation depth from observed acceptance.

    The speculation-overhead pricing the Many-core Machine Model demands:
    a verify step over L grid rows costs ``overhead + L * token_cost``
    (dispatch + per-row forward work) and emits ``E(L) = (1 - a^L) /
    (1 - a)`` tokens in expectation when each drafted token survives with
    probability ``a`` (leading-run acceptance: 1 guaranteed correction /
    bonus token plus a geometric run of accepted drafts).  Returns the
    ``L`` in [1, max_draft_len] maximising expected tokens per second;
    ``a = 0`` prices every draft as rejected work and correctly returns 1.

    ``overhead`` and ``token_cost`` share a unit (only their ratio
    matters).  The default overhead is the fixed-per-step cost measured
    from BENCH_serving.json's continuous cells — serial step ≈ overhead
    + token_cost, L-row verify step ≈ overhead + L·token_cost solves to
    ~4.3 token-costs of launch + host-sync per step on the CPU box —
    NOT the profile's raw ``dispatch`` seconds, which against the
    token_cost=1.0 unit would price steps as free and pin L=1.  Pass a
    measured ``overhead`` (same units as token_cost) to recalibrate per
    deployment.
    """
    if not 0.0 <= acceptance <= 1.0:
        raise ValueError(f"acceptance must be in [0, 1], got {acceptance}")
    if max_draft_len < 1:
        raise ValueError(f"max_draft_len must be >= 1, got {max_draft_len}")
    if overhead is None:
        overhead = DISPATCH_OVERHEAD * token_cost
    a = min(acceptance, 1.0 - 1e-9)
    best_l, best_rate = 1, 0.0
    for length in range(1, max_draft_len + 1):
        expected = (1.0 - a ** length) / (1.0 - a)
        rate = expected / (overhead + length * token_cost)
        if rate > best_rate * (1.0 + 1e-12):
            best_l, best_rate = length, rate
    return best_l


def decide_step_horizon(
    *,
    mean_remaining: float,
    token_cost: float = 1.0,
    overhead: float | None = None,
    load: float = 1.0,
    max_horizon: int = 64,
) -> int:
    """Pick K, the decode steps fused per serving dispatch (DESIGN.md §14).

    The amortization the paper demands, priced against its risk: fusing K
    steps into one scan divides the fixed per-step cost (``overhead``, in
    ``token_cost`` units — the same dispatch + host-sync constant
    ``decide_draft_len`` amortizes) by K, but a request finishing
    mid-horizon rides frozen until the boundary, wasting ``(K - 1) / 2``
    slot-iterations in expectation per completed request.  Against a mean
    per-request budget of ``mean_remaining`` device iterations, the
    useful fraction of slot work is ``m / (m + load * (K - 1) / 2)``
    (``load`` scales how much boundary idling displaces real work: 1.0
    when a queue is waiting for every freed slot, 0.0 when slots would
    idle anyway), and per-iteration cost is ``token_cost + overhead / K``
    — K maximises their ratio.  Ties break toward SMALLER K (admission
    latency: a queued request waits up to K iterations for a boundary).

    Degenerations behave: ``overhead = 0`` returns 1 (nothing to
    amortize), ``load = 0`` returns ``max_horizon`` (idle slots make
    amortization free), and K shrinks with the budget — short tails
    amortize less than long ones (though even ``mean_remaining = 1``
    tolerates a small K: halving a 4.3-token dispatch tax is worth half
    a wasted iteration).
    """
    if mean_remaining < 1:
        raise ValueError(
            f"mean_remaining must be >= 1, got {mean_remaining}")
    if max_horizon < 1:
        raise ValueError(f"max_horizon must be >= 1, got {max_horizon}")
    if not 0.0 <= load <= 1.0:
        raise ValueError(f"load must be in [0, 1], got {load}")
    if overhead is None:
        overhead = DISPATCH_OVERHEAD * token_cost
    best_k, best_rate = 1, 0.0
    for k in range(1, max_horizon + 1):
        idle = load * (k - 1) / 2.0
        useful = mean_remaining / (mean_remaining + idle)
        rate = useful / (token_cost + overhead / k)
        if rate > best_rate * (1.0 + 1e-12):
            best_k, best_rate = k, rate
    return best_k


def decide_page_size(
    *,
    context: int,
    shared_prefix_len: int = 0,
    candidates: Sequence[int] = (4, 8, 16, 32),
    table_overhead_rows: float = 1.0,
) -> int:
    """Pick the paged-KV page size for a deployment (DESIGN.md §13).

    Three costs pull in different directions, all priced in cache rows
    per request so they share a unit:

      * fragmentation — the chain's tail page is half empty on average:
        ``page_size / 2``;
      * lost sharing — only whole pages inside the common prompt prefix
        can be COW-shared, so ``shared_prefix_len % page_size`` rows get
        re-prefilled per sibling that a finer page would have skipped;
      * table overhead — each mapped page costs a table entry, a gather
        index and (pallas) a loop trip: ``table_overhead_rows *
        ceil(context / page_size)``.

    With no sharing the minimum sits near ``sqrt(2 * overhead *
    context)``; a shared prefix drags the choice toward its divisors.
    Ties pick the LARGER page (shorter chains, cheaper admission).
    """
    if context < 1:
        raise ValueError(f"context must be >= 1, got {context}")
    if shared_prefix_len < 0:
        raise ValueError(
            f"shared_prefix_len must be >= 0, got {shared_prefix_len}")
    if not candidates:
        raise ValueError("candidates must be non-empty")

    def cost(p: int) -> float:
        return (p / 2.0
                + shared_prefix_len % p
                + table_overhead_rows * -(-context // p))

    return max(sorted(candidates), key=lambda p: (-cost(p), p))


def join_term_from_hlo(
    hlo_text: str,
    *,
    device_count: int,
    profile: HardwareProfile | None = None,
) -> dict:
    """Price the collective join straight from compiled HLO.

    Uses ``analyse_hlo``'s ``collective_detail`` (per-op execution counts
    and payload bytes, loop-trip multiplied) so the join term reflects
    what XLA actually emitted — all-reduce count per solve, payload bytes
    — rather than the hand model's assumed one-psum-per-round.
    """
    from repro.launch.hlo_cost import analyse_hlo

    profile = profile or profile_for(device_platform()[0])
    detail = analyse_hlo(hlo_text).get("collective_detail", {})
    count = sum(d["count"] for d in detail.values())
    byts = sum(d["bytes"] for d in detail.values())
    seconds = (count * profile.join_alpha
               * math.log2(max(2, device_count))
               + byts / profile.link_bw)
    return {"count": int(count), "bytes": float(byts),
            "seconds": float(seconds), "detail": detail}


def _candidates(
    key: ConfigKey,
    options: Mapping[str, tuple[int, int]],
    backends: Sequence[str],
    max_spec_k: int = 8,
) -> list[tuple[float, Decision]]:
    """All legal (predicted_cost, Decision) pairs, cheapest first."""
    profile = profile_for(key.device_kind)
    out = []
    for spec_k in range(1, min(max_spec_k, max(1, key.iterations)) + 1):
        rounds = -(-key.iterations // spec_k)
        for placement, ways in options.items():
            for backend in backends:
                d = Decision(spec_k=spec_k, rounds=rounds,
                             placement=placement, backend=backend)
                out.append((predict_cost(key, d, ways, profile), d))
    out.sort(key=lambda cd: cd[0])
    return out


# ---------------------------------------------------------------------------
# kernel tier: block/grid geometry decisions (DESIGN.md §15)
# ---------------------------------------------------------------------------

# Per-grid-step (or per-loop-trip) overhead of the Pallas INTERPRETER —
# each step replays the kernel body as jax ops through the interpreter
# harness, hundreds of µs on the CPU box.  Used by the loop-trip models
# (paged_attend) where fewer trips genuinely win; the tiled solver
# kernels are instead cache-bound under the interpreter (see
# kernel_candidates) so the step tax must NOT steer them to huge blocks.
INTERPRET_STEP_COST = 200e-6
# Compiled Mosaic grid-step overhead (revolver bookkeeping + DMA issue).
COMPILED_STEP_COST = 0.5e-6

_KERNEL_LANE = 128           # mirrors kernels/blocks.LANE; core must not
# import from repro.kernels (the dependency arrow points kernels -> core),
# so the tiny geometry math is restated here.

_SOLVER_KERNELS = ("multi_count", "multi_mass",
                   "multi_entropy", "multi_entropy_moments")


def _lpad(n: int, mult: int = _KERNEL_LANE) -> int:
    return -(-max(int(n), 1) // int(mult)) * int(mult)


@dataclasses.dataclass(frozen=True)
class KernelKey:
    """The static configuration a kernel-geometry decision is keyed by.

    ``shape`` is the kernel family's own signature tuple (documented per
    family in :func:`kernel_candidates`), not a single array shape —
    e.g. paged_attend keys on (B, n_kv, n_chain, page, L, R, head_dim).
    ``interpret`` is part of the key because the interpreter's per-step
    tax inverts the geometry trade-off: a block measured under interpret
    mode must never steer a compiled TPU deployment.
    """

    kernel: str
    shape: tuple[int, ...]
    dtype: str
    device_kind: str
    interpret: bool = False

    def cache_key(self) -> str:
        return "|".join((
            "kernel", self.kernel,
            "x".join(str(int(s)) for s in self.shape),
            self.dtype, self.device_kind or "cpu",
            "interp" if self.interpret else "compiled",
        ))


@dataclasses.dataclass(frozen=True)
class KernelDecision:
    """One resolved kernel geometry: a named block-parameter assignment.

    ``block`` is a sorted tuple of (param, value) pairs — hashable, so
    decisions dedupe in candidate sets; read it as a dict via
    :attr:`params`.  Param names are the kernel's own static argnames
    (``block_v``, ``q_chunk``/``kv_chunk``, ``pages_per_step``), which is
    what lets ``kernels/ops.py`` splat a decision straight into the call.
    """

    block: tuple[tuple[str, int], ...]
    source: str = "model"       # model | measured | cache | fixed

    @property
    def params(self) -> dict[str, int]:
        return dict(self.block)

    @staticmethod
    def make(params: Mapping[str, int],
             source: str = "model") -> "KernelDecision":
        return KernelDecision(
            block=tuple(sorted((str(k), int(v)) for k, v in params.items())),
            source=source)

    def to_json(self) -> dict:
        return {"block": dict(self.block), "source": self.source}

    @staticmethod
    def from_json(d: Mapping) -> "KernelDecision":
        return KernelDecision.make(dict(d["block"]),
                                   source=str(d.get("source", "cache")))

    def label(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.block)


def kernel_candidates(
    key: KernelKey,
    profile: HardwareProfile | None = None,
) -> list[tuple[float, KernelDecision]]:
    """Analytic (predicted_seconds, KernelDecision) pairs, cheapest first.

    The first roofline pass of the kernel tier: per candidate geometry,
    cost = steps * (step_tax + max(flops/peak, bytes/bw)), with the
    VMEM-fit filter discarding infeasible blocks up front.

    Interpret mode is modelled differently: the interpreter's cost
    surface is HOST-cache dominated — the materialised (m_pad, block)
    broadcast grows per-step cost superlinearly past L2, so bigger
    blocks LOSE despite fewer grid steps (BENCH_kernels.json: the
    whole-row block is 2x slower than 2048 at (8, 8192, 15) on this
    box).  The analytic tier therefore pins the legacy default under
    interpret mode, ranking candidates by distance from it so the
    measured tier's top-3 stays centred there; genuine interpret-mode
    wins come from measurement (``REPRO_AUTOTUNE``), not the model.

    Key shapes per family:
      multi_count / multi_mass / multi_entropy[_moments]: (B, V, M)
      runahead_topk:  (B, V)
      flash_fwd:      (B, S, H, D)
      paged_attend:   (B, n_kv, n_chain, page_size, L, R, head_dim)
    Unknown families return [] (the caller's fixed geometry stands).
    """
    profile = profile or profile_for(key.device_kind)
    itemsize = 2 if key.dtype in ("bfloat16", "float16") else 4
    step = INTERPRET_STEP_COST if key.interpret else COMPILED_STEP_COST
    budget = profile.vmem_bytes * 0.5    # headroom for double-buffering
    out: list[tuple[float, KernelDecision]] = []

    if key.kernel in _SOLVER_KERNELS:
        B, V, M = key.shape
        m_pad = _lpad(M)
        v_lane = _lpad(V)
        kf = _KIND_FLOPS.get({
            "multi_count": "count_above",
            "multi_mass": "mass_at_or_above",
        }.get(key.kernel, "entropy_at_temperature"), 4.0)
        cands = sorted({min(_lpad(b), v_lane)
                        for b in (256, 512, 1024, 2048, 4096, 8192,
                                  16384, v_lane)})
        default_b = min(_lpad(2048), v_lane)
        for b in cands:
            # streamed tile + resident candidates + accumulator + the
            # broadcast (m_pad, b) compare grid (blocks.solver_tile_bytes)
            tile = itemsize * (b + 2 * m_pad + m_pad * b)
            if tile > budget:
                continue
            if key.interpret:
                cost = abs(math.log2(b) - math.log2(default_b))
            else:
                steps = _lpad(V, b) // b
                flops = float(b) * m_pad * kf
                byts = float(itemsize) * b
                cost = B * steps * (
                    step + max(flops / profile.flops,
                               byts / profile.mem_bw))
            out.append((cost, KernelDecision.make({"block_v": b})))

    elif key.kernel == "runahead_topk":
        B, V = key.shape[0], key.shape[1]
        for b in (128, 256, 512):
            # whole row stays resident; block only sets padding — minimal
            # padded bytes win, so LANE is the stable choice
            v_pad = _lpad(V, b)
            if itemsize * v_pad > budget:
                continue
            cost = B * (step + itemsize * float(v_pad) / profile.mem_bw)
            out.append((cost, KernelDecision.make({"block_v": b})))

    elif key.kernel == "flash_fwd":
        B, S, H, D = key.shape
        cset = {c for c in (128, 256, 512, 1024, 2048)
                if c < S and S % c == 0}
        cset.add(int(S))
        # the legacy 512/1024 defaults, legalised to divisors of S the
        # way ops.flash_fwd's fixed geometry is (blocks.divisor_chunk)
        default_qc = max(c for c in cset if c <= 512) \
            if any(c <= 512 for c in cset) else min(cset)
        default_kc = max(c for c in cset if c <= 1024) \
            if any(c <= 1024 for c in cset) else min(cset)
        for qc in sorted(cset):
            for kc in sorted(cset):
                # q tile + k/v tiles + the f32 (qc, kc) score tile
                tile = itemsize * (qc * D + 2 * kc * D) + 4 * qc * kc
                if tile > budget:
                    continue
                if key.interpret:
                    cost = (abs(math.log2(qc) - math.log2(default_qc))
                            + abs(math.log2(kc) - math.log2(default_kc)))
                else:
                    steps = (S // qc) * (S // kc)
                    flops = 4.0 * qc * kc * D        # qk^T + pv matmuls
                    byts = float(itemsize) * (qc * D + 2 * kc * D)
                    cost = B * H * steps * (
                        step + max(flops / profile.flops,
                                   byts / profile.mem_bw))
                out.append((cost, KernelDecision.make(
                    {"q_chunk": qc, "kv_chunk": kc})))

    elif key.kernel == "paged_attend":
        B, nkv, n_chain, P, L, R, D = key.shape
        for d in sorted({min(d, max(1, int(n_chain)))
                         for d in (1, 2, 4, 8)}):
            if key.interpret:
                # under the interpreter the chain loop is NOT a pallas
                # grid step (grid is (B, n_kv)), so there is no per-trip
                # interpreter tax for unrolling to amortise — depth is a
                # noise-level wash; pin the default, measured tier only
                cost = math.log2(2 * d)
            else:
                steps = -(-n_chain // d)
                pages = steps * d    # trailing masked pages still cost
                page_work = max(
                    4.0 * L * R * P * D / profile.flops,
                    float(itemsize) * 2 * P * D / profile.mem_bw)
                cost = B * nkv * (steps * step + pages * page_work)
            out.append((cost, KernelDecision.make({"pages_per_step": d})))

    out.sort(key=lambda cd: (cd[0], cd[1].block))
    return out


# ---------------------------------------------------------------------------
# state: thread-local modes + the persistent cache
# ---------------------------------------------------------------------------

_tls = threading.local()


def _stack(name: str) -> list:
    st = getattr(_tls, name, None)
    if st is None:
        st = []
        setattr(_tls, name, st)
    return st


@contextlib.contextmanager
def disabled():
    """Pin the caller's fixed legacy configuration for enclosed traces
    (what the engine did before tuning existed)."""
    _stack("disabled").append(True)
    try:
        yield
    finally:
        _stack("disabled").pop()


@contextlib.contextmanager
def autotune(enabled: bool = True):
    """Enable the measured tier for enclosed traces: top candidates are
    micro-benchmarked on device and winners persisted to the cache."""
    _stack("autotune").append(bool(enabled))
    try:
        yield
    finally:
        _stack("autotune").pop()


@contextlib.contextmanager
def override(
    *,
    spec_k: int | None = None,
    placement: str | None = None,
    backend: str | None = None,
):
    """Force decision fields for enclosed traces; None fields keep the
    tuner's choice.  An infeasible forced placement (e.g. "vocab" with no
    mesh) falls back to single-device at execution, like any decision."""
    if placement is not None and placement not in PLACEMENTS:
        raise ValueError(f"placement must be one of {PLACEMENTS}")
    _stack("override").append(
        {"spec_k": spec_k, "placement": placement, "backend": backend})
    try:
        yield
    finally:
        _stack("override").pop()


def _is_disabled() -> bool:
    st = _stack("disabled")
    return bool(st and st[-1]) or bool(os.environ.get(DISABLE_ENV))


def _autotune_active(tune: bool | None) -> bool:
    if tune is not None:
        return bool(tune)
    st = _stack("autotune")
    if st:
        return bool(st[-1])
    return bool(os.environ.get(AUTOTUNE_ENV))


def _active_override() -> dict | None:
    st = _stack("override")
    return st[-1] if st else None


class Tuner:
    """Decision store: in-memory + schema-versioned JSON persistence."""

    def __init__(self, cache_path: str | None = None):
        self._lock = threading.Lock()
        self._path = cache_path
        self._entries: dict[str, dict] = {}
        self._kernels: dict[str, dict] = {}     # KernelKey -> entry (§15)
        self._loaded = False
        self.recent: dict[str, Decision] = {}   # last decisions, for logs
        self.recent_kernels: dict[str, KernelDecision] = {}

    # -- persistence --------------------------------------------------------

    def cache_path(self) -> str:
        if self._path is None:
            self._path = os.environ.get(CACHE_ENV) or os.path.join(
                os.path.expanduser("~"), ".cache", "repro",
                "solver_tuning.json")
        return self._path

    def set_cache_path(self, path: str | None):
        with self._lock:
            self._path = path
            self._entries = {}
            self._kernels = {}
            self._loaded = False

    def _load_locked(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.cache_path()) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return
        # stale / future schema: ignore wholesale — a bad entry must never
        # steer the solver (the roundtrip test pins this).  v3 is the one
        # compatible back-rev: solver entries kept their shape, so they
        # replay; its (absent) kernel section just means analytic.
        if not isinstance(data, dict) \
                or data.get("schema") not in _COMPAT_SCHEMAS:
            return
        entries = data.get("entries")
        if isinstance(entries, dict):
            self._entries = dict(entries)
        if data.get("schema") == SCHEMA_VERSION:
            kernels = data.get("kernels")
            if isinstance(kernels, dict):
                self._kernels = dict(kernels)

    def _save_locked(self):
        path = self.cache_path()
        payload = {"schema": SCHEMA_VERSION, "entries": self._entries,
                   "kernels": self._kernels}
        d = os.path.dirname(path) or "."
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass      # persistence is best-effort; decisions still served

    def clear_cache(self):
        with self._lock:
            self._entries = {}
            self._kernels = {}
            self._loaded = True
            try:
                os.unlink(self.cache_path())
            except OSError:
                pass

    # -- the decision procedure --------------------------------------------

    def decide(
        self,
        key: ConfigKey,
        *,
        options: Mapping[str, tuple[int, int]],
        backends: Sequence[str],
        fixed: Decision,
        measure: Callable[[Sequence[Decision]], Sequence[float]] | None
            = None,
        tune: bool | None = None,
    ) -> Decision:
        """Resolve the Decision for `key`.

        options: legal placements -> (vocab_ways, data_ways); must contain
        "single".  backends: candidates honouring the caller's preference
        ("auto" expands upstream).  fixed: the caller's legacy hard-coded
        configuration, returned verbatim when tuning is disabled and
        always included in the measured candidate set.  measure: callback
        timing candidate Decisions (seconds each), supplied by the engine.
        """
        if _is_disabled():
            decision = dataclasses.replace(fixed, source="fixed")
            self.recent[key.cache_key()] = decision
            return decision

        ov = _active_override()
        decision = self._decide_inner(key, options, backends, fixed,
                                      measure, tune)
        if ov is not None:
            fields = {k: v for k, v in ov.items() if v is not None}
            if "spec_k" in fields:
                fields["rounds"] = -(-key.iterations // fields["spec_k"])
            decision = dataclasses.replace(
                decision, source="override", **fields)
        self.recent[key.cache_key()] = decision
        if len(self.recent) > 256:
            self.recent.pop(next(iter(self.recent)))
        return decision

    def _decide_inner(self, key, options, backends, fixed, measure, tune):
        with self._lock:
            self._load_locked()
            hit = self._entries.get(key.cache_key())
        if hit is not None:
            try:
                d = Decision.from_json(hit["decision"])
            except (KeyError, TypeError, ValueError):
                d = None
            # a cached replay must still be legal: the placement on THIS
            # mesh, the backend in the caller's set, and every budget
            # knob a sane positive value (a hand-edited or corrupted
            # entry must never steer the solver)
            if d is not None and d.placement in options \
                    and d.backend in backends \
                    and d.spec_k >= 1 and d.rounds >= 1 \
                    and d.draft_len >= 1 and d.step_horizon >= 1:
                return dataclasses.replace(d, source="cache")

        ranked = _candidates(key, options, backends)
        best = ranked[0][1] if ranked else fixed

        if measure is not None and _autotune_active(tune):
            cand = [d for _, d in ranked[:3]]
            for extra in (
                # never-worse-than-single-device baseline + legacy config
                dataclasses.replace(fixed, placement="single"),
                fixed,
            ):
                if extra.placement in options and extra.backend in backends \
                        and extra not in cand:
                    cand.append(extra)
            reports = list(measure(cand))
            pairs = [(r["seconds"], d) for r, d in zip(reports, cand)
                     if r["seconds"] == r["seconds"]
                     and r["seconds"] > 0]     # drop NaN/failed
            if len(reports) != len(cand) or not pairs:
                raise RuntimeError(
                    f"every measured candidate failed for {key}")
            _, d_best = min(pairs, key=lambda p: p[0])
            d_best = dataclasses.replace(d_best, source="measured")
            label = (lambda d: f"{d.placement}/{d.backend}"
                     f"/k{d.spec_k}")
            entry = {
                "decision": d_best.to_json(),
                "measured_us": {
                    label(d): round(r["seconds"] * 1e6, 1)
                    for r, d in zip(reports, cand)
                },
                # REAL join term per sharded candidate, priced
                # from compiled HLO (analyse_hlo collective_detail)
                "join_hlo": {
                    label(d): r["collectives"]
                    for r, d in zip(reports, cand)
                    if r.get("collectives")
                },
            }
            with self._lock:
                self._entries[key.cache_key()] = entry
                self._save_locked()
            return d_best
        return dataclasses.replace(best, source="model")

    # -- the kernel-geometry decision procedure (DESIGN.md §15) -------------

    def decide_kernel(
        self,
        key: KernelKey,
        *,
        fixed: Mapping[str, int],
        measure: Callable[[Sequence[Mapping[str, int]]], Sequence[float]]
            | None = None,
        tune: bool | None = None,
    ) -> KernelDecision:
        """Resolve the block geometry for `key`.

        fixed: the kernel's legacy hard-coded params (e.g.
        ``{"block_v": 2048}``) — returned verbatim when tuning is
        disabled, always in the measured candidate set.  measure:
        callback timing candidate param dicts (seconds each, NaN for a
        failed candidate; the tier raises when every one failed),
        supplied by ``kernels/ops.py``.  Mirrors
        :meth:`decide`: disabled -> fixed, cache hit -> legality-checked
        replay, analytic -> cheapest roofline candidate, measured
        (``tune``/:func:`autotune`/``REPRO_AUTOTUNE``) -> timed top-3 +
        fixed, winner persisted under the cache's "kernels" section.
        """
        ck = key.cache_key()

        def _remember(d: KernelDecision) -> KernelDecision:
            self.recent_kernels[ck] = d
            if len(self.recent_kernels) > 256:
                self.recent_kernels.pop(next(iter(self.recent_kernels)))
            return d

        if _is_disabled():
            return _remember(KernelDecision.make(fixed, source="fixed"))

        with self._lock:
            self._load_locked()
            hit = self._kernels.get(ck)
        if hit is not None:
            try:
                d = KernelDecision.from_json(hit["decision"])
            except (KeyError, TypeError, ValueError):
                d = None
            # replay legality: the entry must name exactly the params this
            # kernel takes, all sane positive values — a hand-edited or
            # corrupted entry must never steer a kernel launch
            if d is not None and set(d.params) == set(fixed) \
                    and all(v >= 1 for v in d.params.values()):
                return _remember(
                    dataclasses.replace(d, source="cache"))

        ranked = kernel_candidates(key)
        best = (ranked[0][1] if ranked
                else KernelDecision.make(fixed, source="model"))

        if measure is not None and _autotune_active(tune):
            cand = [d for _, d in ranked[:3]]
            fx = KernelDecision.make(fixed, source="fixed")
            if all(c.block != fx.block for c in cand):
                cand.append(fx)
            times = list(measure([c.params for c in cand]))
            pairs = [(t, c) for t, c in zip(times, cand)
                     if t == t and t > 0]        # drop NaN/failed
            if len(times) != len(cand) or not pairs:
                raise RuntimeError(
                    f"every measured geometry failed for {key}")
            _, d_best = min(pairs, key=lambda p: p[0])
            d_best = dataclasses.replace(d_best, source="measured")
            entry = {
                "decision": d_best.to_json(),
                "measured_us": {
                    c.label(): round(t * 1e6, 1)
                    for t, c in zip(times, cand) if t == t
                },
            }
            with self._lock:
                self._kernels[ck] = entry
                self._save_locked()
            return _remember(d_best)
        return _remember(dataclasses.replace(best, source="model"))


# module-level singleton ------------------------------------------------------

_TUNER = Tuner()


def tuner() -> Tuner:
    return _TUNER


def decide(key: ConfigKey, **kw) -> Decision:
    return _TUNER.decide(key, **kw)


def decide_kernel(key: KernelKey, **kw) -> KernelDecision:
    return _TUNER.decide_kernel(key, **kw)


def clear_cache():
    _TUNER.clear_cache()


def set_cache_path(path: str | None):
    _TUNER.set_cache_path(path)


def cache_path() -> str:
    return _TUNER.cache_path()


def explain() -> list[tuple[str, Decision]]:
    """Recent (config key, decision) pairs — what the tuner chose and why
    (``source`` says which tier produced each)."""
    return list(_TUNER.recent.items())


def explain_kernels() -> list[tuple[str, KernelDecision]]:
    """Recent (kernel key, geometry decision) pairs — kept separate from
    :func:`explain` because the two decision types share no fields."""
    return list(_TUNER.recent_kernels.items())
