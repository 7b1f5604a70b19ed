"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels always compile natively.  Elsewhere (the CPU
test rig) they run in interpret mode, which executes the kernel body as
jax ops for correctness validation — the BlockSpec tiling is identical.
The resolved mode is computed ONCE.  ``REPRO_PALLAS_INTERPRET=0|1`` is
honoured off-TPU only (``=0`` to trust a non-TPU Mosaic backend); on a
TPU, asking for the interpreter raises rather than hide the chip.

Every launch is routed through the tuner's kernel tier (DESIGN.md §15):
a :class:`~repro.core.tuning.KernelKey` built from the call's static
shape/dtype resolves to a :class:`~repro.core.tuning.KernelDecision`
naming the block geometry (``block_v``, ``q_chunk``/``kv_chunk``,
``pages_per_step``), analytic by default, measured + cached under
``REPRO_AUTOTUNE``/``tuning.autotune()``.  Callers keep the exact same
signatures — tuning is invisible here just as it is for the solver.
"""
from __future__ import annotations

import functools
import logging
import os

import jax

from repro.core import tuning
from repro.kernels import flash_fwd as _ff
from repro.kernels import multi_count as _mc
from repro.kernels import multi_entropy as _me
from repro.kernels import multi_mass as _mm
from repro.kernels import paged_attend as _pa
from repro.kernels import runahead_threshold as _rt
from repro.kernels import taylor_eval as _te
from repro.kernels import blocks

log = logging.getLogger("repro.kernels")

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"
_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")

_INTERPRET: bool | None = None      # resolved once, see interpret_mode()


def interpret_mode() -> bool:
    """The resolved Pallas interpret mode, computed once per process.

    False on a TPU backend, where ``REPRO_PALLAS_INTERPRET=1`` raises.
    Off-TPU, True unless ``REPRO_PALLAS_INTERPRET=0``.
    :func:`reset_interpret_mode` drops the memo (tests that flip the env
    var mid-process).
    """
    global _INTERPRET
    if _INTERPRET is None:
        env = os.environ.get(INTERPRET_ENV, "").strip().lower()
        if jax.default_backend() == "tpu":
            if env in _TRUE:
                raise RuntimeError(
                    f"{INTERPRET_ENV}={env!r} asks for the Pallas "
                    "interpreter on a TPU backend; kernels compile there")
            _INTERPRET = False
        else:
            _INTERPRET = env not in _FALSE
    return _INTERPRET


def interpret_mode_source() -> str:
    """"env" when REPRO_PALLAS_INTERPRET forced the mode, else "auto"."""
    env = os.environ.get(INTERPRET_ENV, "").strip().lower()
    return "env" if env in _TRUE + _FALSE else "auto"


def reset_interpret_mode() -> None:
    global _INTERPRET
    _INTERPRET = None


def _interpret() -> bool:
    """Legacy alias (benchmarks/common.py and older callers)."""
    return interpret_mode()


# ---------------------------------------------------------------------------
# the decision plumbing: KernelKey -> block params for each launch
# ---------------------------------------------------------------------------

# Legacy hard-coded geometries — what every call used before PR 10, what
# ``tuning.disabled()`` pins, and the baseline the measured tier must beat.
_FIXED_SOLVER = {"block_v": blocks.DEFAULT_BLOCK_V}
_FIXED_TOPK = {"block_v": blocks.LANE}


def _decide(kernel: str, shape: tuple[int, ...], dtype,
            fixed: dict[str, int]) -> dict[str, int]:
    """Resolve the block params for one launch (trace-time, like the
    solver's Decisions — a compiled caller keeps what it traced with)."""
    key = tuning.KernelKey(
        kernel=kernel, shape=tuple(int(s) for s in shape), dtype=str(dtype),
        device_kind=tuning.device_platform()[0],
        interpret=interpret_mode(),
    )
    decision = tuning.decide_kernel(
        key, fixed=fixed,
        measure=lambda cands: _measure_kernel(kernel, key, cands),
    )
    return decision.params


def _measure_kernel(kernel, key, candidates):
    """Time candidate geometries on the live device (measured tier).

    Synthetic operands of the keyed shapes; each candidate compiled,
    warmed, median of 5 — the benchmark-harness convention.  A failing
    candidate reports NaN and is never selected.
    """
    import time

    import numpy as np

    # Swap out the ambient trace so measurement is truly eager even when
    # the triggering launch is itself being traced (see
    # solver._measure_candidates for why eval_context, not
    # ensure_compile_time_eval).
    from jax._src.core import eval_context

    with eval_context():
        return _measure_kernel_eager(kernel, key, candidates, time, np)


def _measure_kernel_eager(kernel, key, candidates, time, np):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    interp = key.interpret

    if kernel in ("multi_count", "multi_mass", "multi_entropy",
                  "multi_entropy_moments"):
        B, V, M = key.shape
        x = rng.normal(size=(B, V)).astype(np.float32) * 2.0
        if kernel == "multi_mass":
            x = np.exp(x)
            x /= x.sum(-1, keepdims=True)
        if kernel == "multi_entropy_moments":
            x = x - x.max(-1, keepdims=True)
        t = np.linspace(0.2, 2.0, M, dtype=np.float32)
        second = np.broadcast_to(t, (B, M)).copy()
        fn = {"multi_count": _mc.multi_count,
              "multi_mass": _mm.multi_mass,
              "multi_entropy": _me.multi_entropy,
              "multi_entropy_moments": _me.multi_entropy_moments}[kernel]
        args = (jnp.asarray(x), jnp.asarray(second))

        def make(p):
            return functools.partial(fn, **p, interpret=interp)

    elif kernel == "runahead_topk":
        B, V = key.shape[0], key.shape[1]
        x = rng.normal(size=(B, V)).astype(np.float32)
        args = (jnp.asarray(x),)

        def make(p):
            return functools.partial(
                _rt.runahead_topk_threshold, k_target=max(1, V // 8),
                rounds=4, spec_k=4, **p, interpret=interp)

    elif kernel == "flash_fwd":
        B, S, H, D = key.shape
        q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)),
                               dtype=key.dtype) for _ in range(3))
        args = (q, k, v)

        def make(p):
            return lambda *a: _ff.flash_fwd(
                *a, p["q_chunk"], p["kv_chunk"], 0, interp)

    elif kernel == "paged_attend":
        B, nkv, n_chain, P, L, R, D = key.shape
        n_pages = B * n_chain + 1
        pool_k = jnp.asarray(rng.normal(size=(n_pages, P, nkv, D)),
                             dtype=key.dtype)
        pool_v = jnp.asarray(rng.normal(size=(n_pages, P, nkv, D)),
                             dtype=key.dtype)
        table = jnp.asarray(
            rng.permutation(n_pages - 1)[: B * n_chain].reshape(B, n_chain),
            dtype=jnp.int32)
        context = n_chain * P
        pos = jnp.full((B,), context - L, jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, L, nkv * R, D)), dtype=key.dtype)
        args = (pool_k, pool_v, table, pos, q)

        def make(p):
            return functools.partial(
                _pa.paged_attend, context=context, **p, interpret=interp)

    else:
        raise ValueError(f"no measurement harness for kernel {kernel!r}")

    times = []
    for params in candidates:
        try:
            fn = jax.jit(make(dict(params)))
            jax.block_until_ready(fn(*args))            # compile + warm
            reps = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                reps.append(time.perf_counter() - t0)
            reps.sort()
            times.append(reps[len(reps) // 2])
        except Exception:
            # reported as NaN, never selected; the tuner raises if no
            # candidate survives
            log.warning("%s geometry %s failed for %s", kernel, params, key,
                        exc_info=True)
            times.append(float("nan"))
    return times


# ---------------------------------------------------------------------------
# the public wrappers (signatures unchanged by tuning)
# ---------------------------------------------------------------------------

def multi_count(logits: jax.Array, taus: jax.Array) -> jax.Array:
    """Fused multi-threshold count (one vocab sweep, all candidates)."""
    p = _decide("multi_count",
                (logits.shape[0], logits.shape[1], taus.shape[1]),
                logits.dtype, _FIXED_SOLVER)
    return _mc.multi_count(logits, taus, **p, interpret=interpret_mode())


def multi_mass(probs: jax.Array, taus: jax.Array) -> jax.Array:
    """Fused multi-threshold probability mass (one vocab sweep)."""
    p = _decide("multi_mass",
                (probs.shape[0], probs.shape[1], taus.shape[1]),
                probs.dtype, _FIXED_SOLVER)
    return _mm.multi_mass(probs, taus, **p, interpret=interpret_mode())


def multi_entropy(logits: jax.Array, ts: jax.Array) -> jax.Array:
    """Fused multi-temperature softmax entropy (one vocab sweep)."""
    p = _decide("multi_entropy",
                (logits.shape[0], logits.shape[1], ts.shape[1]),
                logits.dtype, _FIXED_SOLVER)
    return _me.multi_entropy(logits, ts, **p, interpret=interpret_mode())


def multi_entropy_moments(z_shifted: jax.Array, ts: jax.Array):
    """Raw (normaliser, expectation) accumulator pair for PRE-SHIFTED
    logits — the vocab-sharded solver backend psums these partials
    across shards before finalising H (DESIGN.md §5)."""
    p = _decide("multi_entropy_moments",
                (z_shifted.shape[0], z_shifted.shape[1], ts.shape[1]),
                z_shifted.dtype, _FIXED_SOLVER)
    return _me.multi_entropy_moments(z_shifted, ts, **p,
                                     interpret=interpret_mode())


def runahead_topk_threshold(
    logits: jax.Array, *, k_target: int, rounds: int = 8, spec_k: int = 5
):
    """Fully fused multi-round runahead top-k bracket (VMEM-resident rows)."""
    p = _decide("runahead_topk", tuple(logits.shape), logits.dtype,
                _FIXED_TOPK)
    return _rt.runahead_topk_threshold(
        logits, k_target=k_target, rounds=rounds, spec_k=spec_k, **p,
        interpret=interpret_mode(),
    )


def taylor_sincos_eval(x: jax.Array, *, terms: int) -> jax.Array:
    """Speculative-grid evaluation of the paper's sin(cos(x)) Taylor f."""
    return _te.taylor_sincos_eval(x, terms=terms, interpret=interpret_mode())


def flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
              window: int = 0) -> jax.Array:
    """Causal flash attention with tuned (q_chunk, kv_chunk) tiling.

    The underlying kernel requires S to divide by both chunks, so the
    fixed geometry legalises the legacy 512/1024 defaults with
    :func:`blocks.divisor_chunk` — a 256-row sequence folds to 256/256
    rather than erroring.
    """
    B, S, H, D = q.shape
    fixed = {"q_chunk": blocks.divisor_chunk(S, 512),
             "kv_chunk": blocks.divisor_chunk(S, 1024)}
    p = _decide("flash_fwd", (B, S, H, D), q.dtype, fixed)
    return _ff.flash_fwd(q, k, v, p["q_chunk"], p["kv_chunk"], window,
                         interpret_mode())


def paged_attend(pool_k, pool_v, table, pos, q, *, context: int):
    """Fused paged decode/verify attention over a page-table KV cache —
    streams each slot's page chain instead of gathering it (§13)."""
    n_pages, P, nkv, D = pool_k.shape
    B, L, nq, _ = q.shape
    p = _decide(
        "paged_attend",
        (B, nkv, table.shape[1], P, L, nq // nkv, D),
        q.dtype, {"pages_per_step": 1})
    return _pa.paged_attend(pool_k, pool_v, table, pos, q, context=context,
                            **p, interpret=interpret_mode())
