"""Batched runahead solve engine — ONE speculative-bisection loop for every
monotone solve in the repo (DESIGN.md §4).

The paper collapses ``k`` serial bisection steps into one parallel round by
evaluating all ``2**k - 1`` interior points of the uniform ``2**k``-partition
at once.  The LM stack needs that solve *per row* of a batch (one threshold
per vocab row, one temperature per sequence, one capacity cut per expert), so
batch is a NATIVE axis of this engine — no ``vmap`` of a scalar solve:

  * the speculative grid is built as a ``(B, 2**k + 1)`` midpoint tree
    (bit-identical per row to serial bisection's midpoint recurrence);
  * one ``multi_eval`` call answers all ``(B, M = 2**k - 1)`` candidates —
    for the LM kinds this is a single fused pass over the large operand;
  * the serial-exact sign walk runs as ``(B,)`` integer index vectors.

Problem *kinds* name the monotone function family (``count_above``,
``mass_at_or_above``, ``entropy_at_temperature``, ``count_below``); a
registry maps ``(kind, backend)`` to a factory producing a
:class:`MonotoneProblem`.  The ``"jnp"`` backend (this module) is the
always-available broadcast-compare-reduce oracle; the ``"pallas"`` backend
(``repro.kernels.solver_backends``, loaded lazily) answers the same
candidates with fused VMEM-tiled kernels and may additionally supply a
whole-solve kernel that keeps the operand row on-chip across ALL rounds.

Sign convention (paper §IV.A): the stored bit is '1' iff the value is
negative; an exact zero counts positive.  The walk only compares bits, so
monotone non-increasing problems work unchanged — the bracket invariant is
``sign(f(lo)) != sign(f(hi))``, not a direction.

Mesh execution (DESIGN.md §5.1): under an active :func:`mesh_policy` the
engine runs mesh-native — batch rows data-parallel over the policy's data
axes, the operand's reduction dim sharded over its vocab axis with each
device partial-reducing its shard and one ``psum`` per round as the
paper's thread-join.  One ``jit(shard_map)`` per static configuration is
cached module-wide; ``core/sharded.py`` is the B=1 point-sharded view of
the same machinery.

Autotuning (DESIGN.md §11): ``solve_kind`` no longer hard-codes HOW the
caller's serial-step budget is spent.  Per static config it consults
``repro.core.tuning`` for speculation depth, placement (vocab-sharded /
data-sharded / single-device fallback — the escape hatch from the
regressing small-shard psum join), and backend; every decision preserves
the budget ``rounds * spec_k``, so tuned solves stay bit-identical to the
serial sign-bit walk.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import logging
import threading
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bisect import _sign_bit

log = logging.getLogger("repro.solver")

Array = jax.Array
MultiEval = Callable[[Array], Array]          # taus (B, M) -> f values (B, M)


@dataclasses.dataclass(frozen=True)
class MonotoneProblem:
    """A batch of monotone root-finds sharing one fused evaluator.

    multi_eval: evaluates f at a ``(B, M)`` grid of candidates in one pass,
        returning ``(B, M)`` values.  M varies between calls (1 for the
        bracket-sign probe, ``2**spec_k - 1`` per round).
    lo0 / hi0:  ``(B,)`` initial bracket endpoints, ``f`` changing sign
        across each row's bracket.
    sign_bit:   the sign convention mapping values to walk bits (default:
        paper §IV.A, negative -> 1, exact zero -> 0).
    sign_lo:    optional precomputed ``(B,)`` bit of ``f(lo0)``; when None
        the engine spends one extra M=1 ``multi_eval`` probe on it.
    fused_solve: optional whole-solve override ``(rounds=, spec_k=) ->
        (lo, hi) | None`` — a backend's multi-round fused kernel (e.g. the
        VMEM-resident top-k kernel).  Returning None falls back to the
        generic round loop.
    """

    multi_eval: MultiEval
    lo0: Array
    hi0: Array
    sign_bit: Callable[[Array], Array] = _sign_bit
    sign_lo: Array | None = None
    fused_solve: Callable[..., tuple[Array, Array] | None] | None = None


# ---------------------------------------------------------------------------
# the batched round loop
# ---------------------------------------------------------------------------

def _midpoint_tree(lo: Array, hi: Array, k: int) -> Array:
    """(B,) brackets -> (B, 2**k + 1) bisection-tree grids.

    Every interior point is the exact float midpoint of its parents, so each
    row's grid is bit-identical to the midpoints serial bisection would
    generate along any root path (see core/runahead.py for the scalar
    derivation).
    """
    n = 1 << k
    grid = jnp.zeros(lo.shape + (n + 1,), dtype=jnp.result_type(lo, hi))
    grid = grid.at[..., 0].set(lo)
    grid = grid.at[..., n].set(hi)
    for level in range(1, k + 1):
        d = 1 << (k - level)
        idx = jnp.arange(d, n, 2 * d)  # odd multiples of d
        grid = grid.at[..., idx].set(
            (grid[..., idx - d] + grid[..., idx + d]) / 2
        )
    return grid


def _select_walk(signs: Array, sign_lo: Array, k: int, steps=None):
    """Serial-exact sign walk over (B,) index grids [0, 2**k].

    signs[b, i] is the bit of grid point i+1 (interior points only).
    ``steps`` (scalar, <= k) limits the walk to a partial round — the
    tail iterations of a non-divisible ``iterations`` budget; None walks
    all k steps.  Returns (lo_idx, hi_idx, sign_lo_new, last_mid_idx),
    each (B,); last_mid_idx is the last grid index examined (Algorithm
    1's `root`), initialised to the interval midpoint 2**(k-1).
    """
    n = 1 << k
    batch = signs.shape[0]

    def body(j, st):
        l, h, sl, lm = st
        mid = (l + h) // 2
        smid = jnp.take_along_axis(signs, (mid - 1)[:, None], axis=1)[:, 0]
        go_left = sl != smid
        new_l = jnp.where(go_left, l, mid)
        new_h = jnp.where(go_left, mid, h)
        new_sl = jnp.where(go_left, sl, smid)
        if steps is None:
            return new_l, new_h, new_sl, mid
        active = j < steps
        return (
            jnp.where(active, new_l, l),
            jnp.where(active, new_h, h),
            jnp.where(active, new_sl, sl),
            jnp.where(active, mid, lm),
        )

    l0 = jnp.zeros((batch,), jnp.int32)
    h0 = jnp.full((batch,), n, jnp.int32)
    lm0 = jnp.full((batch,), n // 2, jnp.int32)
    return jax.lax.fori_loop(0, k, body, (l0, h0, sign_lo, lm0))


def _solve_rounds(
    multi_eval: MultiEval,
    lo0: Array,
    hi0: Array,
    *,
    rounds: int,
    spec_k: int,
    sign_lo: Array | None = None,
    sign_bit: Callable[[Array], Array] = _sign_bit,
    iterations: int | None = None,
    return_last_mid: bool = False,
):
    """Run `rounds` speculative rounds natively over (B,) problems.

    ``iterations`` optionally caps the serial-step budget (the paper's n):
    rounds become ceil(iterations / spec_k) with a partial walk in the
    last round — the Algorithm-1-facing contract `find_root_runahead_
    sharded` needs.  ``return_last_mid`` additionally returns the (B,)
    last midpoints examined.
    """
    lo0 = jnp.asarray(lo0)
    hi0 = jnp.asarray(hi0, dtype=lo0.dtype)
    if iterations is not None:
        rounds = -(-iterations // spec_k)
    if sign_lo is None:
        sign_lo = sign_bit(multi_eval(lo0[:, None])[:, 0])

    def round_body(r, carry):
        lo, hi, sl, lm = carry
        grid = _midpoint_tree(lo, hi, spec_k)            # (B, 2**k + 1)
        signs = sign_bit(multi_eval(grid[:, 1:-1]))      # (B, 2**k - 1)
        steps = (None if iterations is None
                 else jnp.minimum(iterations - r * spec_k, spec_k))
        li, hi_i, new_sl, lmi = _select_walk(signs, sl, spec_k, steps)
        new_lo = jnp.take_along_axis(grid, li[:, None], axis=1)[:, 0]
        new_hi = jnp.take_along_axis(grid, hi_i[:, None], axis=1)[:, 0]
        new_lm = jnp.take_along_axis(grid, lmi[:, None], axis=1)[:, 0]
        return new_lo, new_hi, new_sl, new_lm

    lo, hi, _, lm = jax.lax.fori_loop(
        0, rounds, round_body, (lo0, hi0, sign_lo, (lo0 + hi0) / 2)
    )
    if return_last_mid:
        return lo, hi, lm
    return lo, hi


def solve(
    problem: MonotoneProblem,
    *,
    rounds: int,
    spec_k: int,
    iterations: int | None = None,
) -> tuple[Array, Array]:
    """Solve a batch of monotone problems: final (lo, hi) brackets, (B,) each.

    ``rounds * spec_k`` serial-equivalent bisection steps per row (paper
    §IV.B).  If the problem carries a ``fused_solve`` whole-solve kernel it
    is preferred; a None return falls through to the generic loop.
    ``iterations`` caps the serial-step budget when it does not divide
    ``spec_k`` (a tuner-chosen decomposition): the last round walks only
    the remaining steps, and the fused whole-solve hook — which always
    walks full rounds — is bypassed.
    """
    if problem.fused_solve is not None and iterations is None:
        out = problem.fused_solve(rounds=rounds, spec_k=spec_k)
        if out is not None:
            return out
    return _solve_rounds(
        problem.multi_eval,
        problem.lo0,
        problem.hi0,
        rounds=rounds,
        spec_k=spec_k,
        sign_lo=problem.sign_lo,
        sign_bit=problem.sign_bit,
        iterations=iterations,
    )


# ---------------------------------------------------------------------------
# mesh execution policy (DESIGN.md §5): the engine's chip-level form
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshPolicy:
    """How the engine maps a batch of solves onto a device mesh.

    vocab_axis: mesh axis sharding the operand's reduction (vocab) dim —
        each device evaluates every candidate against its vocab shard and
        partial-reduces locally; one psum per round plays the paper's
        thread-join.  None disables vocab sharding.
    data_axes:  mesh axes sharding the batch/slot dim (rows are
        independent solves — pure data parallelism).  None derives every
        mesh axis except ``vocab_axis``, in mesh order.

    Hashable (mesh + axis names), so a policy can ride jit static args —
    which it MUST: the active policy is read at trace time, so any outer
    jit has to key its cache on the policy (see serving/scheduler.py).
    """

    mesh: jax.sharding.Mesh
    vocab_axis: str | None = "model"
    data_axes: tuple[str, ...] | None = None

    def __post_init__(self):
        if self.data_axes is None:
            object.__setattr__(
                self, "data_axes",
                tuple(a for a in self.mesh.axis_names
                      if a != self.vocab_axis),
            )


_policy_state = threading.local()


def current_policy() -> MeshPolicy | None:
    return getattr(_policy_state, "policy", None)


@contextlib.contextmanager
def mesh_policy(policy: MeshPolicy | jax.sharding.Mesh | None, **kw):
    """Activate a MeshPolicy (or build one from a mesh) for the enclosed
    trace; ``None`` is a no-op so callers can pass an optional mesh
    straight through."""
    if policy is not None and not isinstance(policy, MeshPolicy):
        policy = MeshPolicy(policy, **kw)
    prev = current_policy()
    _policy_state.policy = policy
    try:
        yield policy
    finally:
        _policy_state.policy = prev


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

# (kind, backend) -> factory(operand, **params) -> MonotoneProblem
_REGISTRY: dict[tuple[str, str], Callable[..., MonotoneProblem]] = {}

# (kind, backend) -> factory(local_operand, *, vocab_axis, global_v,
#                            **params) -> MonotoneProblem
# Runs INSIDE shard_map on the device-local vocab shard: multi_eval must
# partial-reduce locally and psum over `vocab_axis`; bracket inits must
# pmin/pmax so every device agrees on the global bracket bit-for-bit.
_SHARDED_REGISTRY: dict[tuple[str, str], Callable[..., MonotoneProblem]] = {}

# Backends whose factories live outside core/ register themselves on first
# use (keeps core free of kernel imports; kernels import core, never the
# reverse at module scope).
_LAZY_BACKEND_MODULES = {"pallas": "repro.kernels.solver_backends"}


def register(kind: str, backend: str):
    """Decorator: register a problem factory for (kind, backend)."""

    def deco(factory: Callable[..., MonotoneProblem]):
        _REGISTRY[(kind, backend)] = factory
        return factory

    return deco


def register_sharded(kind: str, backend: str):
    """Decorator: register a vocab-sharded factory for (kind, backend)."""

    def deco(factory: Callable[..., MonotoneProblem]):
        _SHARDED_REGISTRY[(kind, backend)] = factory
        return factory

    return deco


def problem(
    kind: str, operand: Array, *, backend: str = "jnp", **params
) -> MonotoneProblem:
    """Build the MonotoneProblem for `kind` on `operand` via `backend`."""
    module = _LAZY_BACKEND_MODULES.get(backend)
    if module is not None:
        importlib.import_module(module)
    try:
        factory = _REGISTRY[(kind, backend)]
    except KeyError:
        raise KeyError(
            f"no solver backend {backend!r} for kind {kind!r}; "
            f"registered: {sorted(_REGISTRY)}"
        ) from None
    return factory(operand, **params)


def solve_kind(
    kind: str,
    operand: Array,
    *,
    backend: str = "jnp",
    rounds: int,
    spec_k: int,
    tune: bool | None = None,
    **params,
) -> tuple[Array, Array]:
    """problem() + solve() in one call — the applications' entry point.

    The caller's ``rounds * spec_k`` fixes the SERIAL-STEP BUDGET; how it
    is spent — round decomposition, mesh placement, backend — is decided
    per static config by the tuner (``repro.core.tuning``): the analytic
    cost model by default, measured winners when ``tune=True`` /
    ``tuning.autotune()`` is active.  ``backend`` is a *preference*:
    binding when "jnp"/"pallas", free for the tuner when "auto".  Every
    decision preserves the budget, so results stay bit-identical to the
    serial sign-bit walk regardless of what the tuner picks.

    Under an active :func:`mesh_policy` the decision additionally selects
    vocab-sharding vs data-sharding vs the single-device fallback — an
    active mesh no longer FORCES the vocab-sharded psum join the scaling
    bench shows regressing on small shards.  ``tuning.disabled()`` pins
    the legacy fixed behaviour.
    """
    from repro.core import tuning

    z = jnp.asarray(operand)
    policy = current_policy()
    if z.ndim != 2:
        if backend == "auto":
            backend = "jnp"
        return solve(problem(kind, z, backend=backend, **params),
                     rounds=rounds, spec_k=spec_k)

    iterations = rounds * spec_k
    options = _placement_options(policy, z.shape[0], z.shape[1])
    if backend == "auto":
        cand_backends = tuple(backends_for(kind)) or ("jnp",)
    else:
        cand_backends = (backend,)
    fixed = tuning.Decision(
        spec_k=spec_k, rounds=rounds,
        placement="vocab" if "vocab" in options else (
            "data" if "data" in options else "single"),
        backend=cand_backends[0], source="fixed",
    )
    key = tuning.ConfigKey(
        kind=kind, batch=z.shape[0], vocab=z.shape[1],
        dtype=str(z.dtype), backend_pref=backend,
        device_count=(int(policy.mesh.devices.size)
                      if policy is not None else 1),
        device_kind=tuning.device_platform()[0],
        iterations=iterations,
    )
    statics = {k: p for k, p in params.items() if _static_param(p)}
    decision = tuning.decide(
        key,
        options=options,
        backends=cand_backends,
        fixed=fixed,
        measure=(lambda cands: _measure_candidates(
            key, cands, policy, statics)),
        tune=tune,
    )
    return _execute_decision(decision, kind, z, params, policy, iterations)


def _execute_decision(
    decision,
    kind: str,
    operand: Array,
    params: dict,
    policy: MeshPolicy | None,
    iterations: int,
) -> tuple[Array, Array]:
    """Run one solve the way a tuning Decision says to.

    The decision's (rounds, spec_k) always covers the budget
    (``rounds * spec_k >= iterations``); when it overshoots, the engine's
    partial-last-round walk spends EXACTLY ``iterations`` serial steps —
    the bit-exactness contract vs the serial walk.
    """
    iters_arg = (None if iterations == decision.rounds * decision.spec_k
                 else iterations)
    if decision.placement in ("vocab", "data") and policy is not None:
        out = _solve_kind_sharded(
            policy, kind, operand, backend=decision.backend,
            rounds=decision.rounds, spec_k=decision.spec_k,
            iterations=iters_arg, placement=decision.placement, **params,
        )
        if out is not None:
            return out
    return solve(
        problem(kind, operand, backend=decision.backend, **params),
        rounds=decision.rounds, spec_k=decision.spec_k,
        iterations=iters_arg,
    )


def _placement_options(
    policy: MeshPolicy | None, b: int, v: int
) -> dict[str, tuple[int, int]]:
    """Legal placements -> (vocab_ways, data_ways) for this operand.

    Mirrors the divisibility rules of the sharded path: an axis that does
    not divide its dim is dropped.  "vocab" keeps the data axes too (the
    engine shards both); "single" is always legal.
    """
    opts: dict[str, tuple[int, int]] = {"single": (1, 1)}
    if policy is None:
        return opts
    mesh = policy.mesh
    va = policy.vocab_axis
    vw = 1
    if va is not None and va in mesh.axis_names and mesh.shape[va] > 1 \
            and v % mesh.shape[va] == 0:
        vw = mesh.shape[va]
    dw = 1
    for a in policy.data_axes:
        if a in mesh.axis_names:
            dw *= mesh.shape[a]
    if dw <= 1 or b % dw:
        dw = 1
    if dw > 1:
        opts["data"] = (1, dw)
    if vw > 1:
        opts["vocab"] = (vw, dw)
    return opts


def _measure_candidates(key, candidates, policy, statics) -> list[dict]:
    """Micro-benchmark candidate Decisions (the tuner's measured tier).

    Synthetic operands/params of the keyed shapes; each candidate is
    compiled (jit around the full tuned solve, matching how the engine is
    driven) and timed with a warmup + median, exactly the benchmark
    harness convention.  Runs eagerly on the live devices even when the
    triggering solve is itself being traced.

    Returns one report per candidate: ``{"seconds": median, "collectives":
    join-term-from-HLO | None}`` — sharded candidates get their REAL
    collective count/payload read out of the compiled HLO via
    ``analyse_hlo``, so the cache records what the join actually costs on
    this mesh rather than the hand model's estimate.
    """
    import time

    import numpy as np

    from repro.core import tuning

    # The triggering solve is usually mid-trace; without swapping the
    # ambient trace out, jnp.asarray would stage a TRACER here and every
    # compiled candidate call would fail.  eval_context (not
    # ensure_compile_time_eval, whose eager-constant-folding flag leaks
    # into the nested jit trace) makes the measurement truly eager.
    from jax._src.core import eval_context

    with eval_context():
        return _measure_candidates_eager(key, candidates, policy, statics,
                                         time, np, tuning)


def _measure_candidates_eager(key, candidates, policy, statics, time, np,
                              tuning) -> list[dict]:
    rng = np.random.default_rng(0)
    x = rng.normal(size=(key.batch, key.vocab)).astype(np.float32) * 2.0
    if key.kind == "mass_at_or_above":
        x = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    x = jnp.asarray(x, dtype=key.dtype)
    params = dict(statics)
    if key.kind == "count_above" and "k" not in params:
        params["k"] = max(1, key.vocab // 8)
    if key.kind == "count_below" and "q" not in params:
        params["q"] = 0.3
    if key.kind == "mass_at_or_above" and "p" not in params:
        params["p"] = 0.9
    if key.kind == "entropy_at_temperature" and "target" not in params:
        params["target"] = 2.0

    reports = []
    for decision in candidates:
        fn = jax.jit(lambda op, d=decision: _execute_decision(
            d, key.kind, op, params, policy, key.iterations))
        try:
            compiled = fn.lower(x).compile()
            jax.block_until_ready(compiled(x))          # warm
            reps = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(x))
                reps.append(time.perf_counter() - t0)
            reps.sort()
            coll = None
            if decision.placement != "single" and policy is not None:
                try:
                    coll = tuning.join_term_from_hlo(
                        compiled.as_text(),
                        device_count=key.device_count)
                except Exception:
                    coll = None
            reports.append({"seconds": reps[len(reps) // 2],
                            "collectives": coll})
        except Exception:
            # infeasible candidate (e.g. forced placement the mesh
            # cannot honour) — reported as NaN, never selected; the
            # tuner raises if no candidate survives
            log.warning("solver candidate %s failed for %s", decision, key,
                        exc_info=True)
            reports.append({"seconds": float("nan"), "collectives": None})
    return reports


# ---------------------------------------------------------------------------
# the mesh-native solve path
# ---------------------------------------------------------------------------
#
# One compiled shard_map per static configuration, cached module-wide the
# way serving/scheduler.py::_scheduler_step is (PR 2) — repeated solves
# re-use the compiled step instead of rebuilding jit(shard_map) around a
# fresh closure every call (the core/sharded.py retrace bug this PR
# retires).

_SHARDED_SOLVE_CACHE: dict[tuple, Callable] = {}
_SHARDED_SOLVE_CACHE_MAX = 128     # FIFO-evicted; mirrors sharded.py's 64


def _static_param(v) -> bool:
    """Python scalars stay static (they select known-sign fast paths and
    key the compile cache); arrays/tracers ride in as sharded operands."""
    return v is None or isinstance(v, (bool, int, float, str))


def _solve_kind_sharded(
    policy: MeshPolicy,
    kind: str,
    operand: Array,
    *,
    backend: str,
    rounds: int,
    spec_k: int,
    iterations: int | None = None,
    placement: str = "vocab",
    **params,
):
    """Mesh-native solve_kind; None when the policy cannot shard anything.

    The operand's batch dim shards over the policy's data axes (dropped
    when it does not divide) and its reduction dim over ``vocab_axis``
    (dropped likewise).  With vocab sharded, the per-device problem comes
    from the _SHARDED_REGISTRY (local partial reduce + psum join); with
    vocab replicated the ordinary factory runs on the local batch shard —
    including whole-solve fused kernels, which stay legal because each
    device then holds full rows.

    ``placement`` comes from the tuner: "vocab" is the legacy behaviour
    (prefer the vocab axis, fall back to data-only when it cannot shard);
    "data" forces pure data parallelism — no psum join at all.
    ``iterations`` caps the serial-step budget for tuner-chosen
    decompositions that overshoot it (partial last-round walk).
    """
    if operand.ndim != 2:
        return None
    mesh = policy.mesh
    b, v = operand.shape

    va = policy.vocab_axis if placement == "vocab" else None
    if va is not None and (va not in mesh.axis_names
                           or mesh.shape[va] <= 1 or v % mesh.shape[va]):
        va = None
    data = tuple(a for a in policy.data_axes if a in mesh.axis_names)
    d_size = 1
    for a in data:
        d_size *= mesh.shape[a]
    if d_size <= 1 or b % d_size:
        data = ()
    if va is None and not data:
        return None

    statics = {k: p for k, p in params.items() if _static_param(p)}
    arrays = {k: jnp.asarray(p) for k, p in params.items()
              if k not in statics}
    arr_names = tuple(sorted(arrays))
    key = (
        mesh, kind, backend, rounds, spec_k, iterations, va, data,
        b, v, str(operand.dtype),
        tuple(sorted(statics.items())),
        tuple((n, arrays[n].shape, str(arrays[n].dtype))
              for n in arr_names),
    )
    fn = _SHARDED_SOLVE_CACHE.get(key)
    if fn is None:
        fn = _build_sharded_solve(
            mesh, kind, backend, rounds, spec_k, iterations, va, data, v,
            statics, arr_names,
            tuple(arrays[n].ndim for n in arr_names),
        )
        while len(_SHARDED_SOLVE_CACHE) >= _SHARDED_SOLVE_CACHE_MAX:
            _SHARDED_SOLVE_CACHE.pop(next(iter(_SHARDED_SOLVE_CACHE)))
        _SHARDED_SOLVE_CACHE[key] = fn
    return fn(operand, *(arrays[n] for n in arr_names))


def _build_sharded_solve(mesh, kind, backend, rounds, spec_k, iterations,
                         va, data, global_v, statics, arr_names, arr_ndims):
    module = _LAZY_BACKEND_MODULES.get(backend)
    if module is not None:
        importlib.import_module(module)
    data_spec = data if data else None

    def per_device(op_local, *arrs):
        kw = dict(statics)
        kw.update(zip(arr_names, arrs))
        if va is None:
            # pure data parallelism: full rows per device, fused
            # whole-solve hooks stay available on the local batch shard
            return solve(
                _REGISTRY[(kind, backend)](op_local, **kw),
                rounds=rounds, spec_k=spec_k, iterations=iterations,
            )
        try:
            factory = _SHARDED_REGISTRY[(kind, backend)]
        except KeyError:
            raise KeyError(
                f"no SHARDED solver backend {backend!r} for kind "
                f"{kind!r}; registered: {sorted(_SHARDED_REGISTRY)}"
            ) from None
        prob = factory(op_local, vocab_axis=va, global_v=global_v, **kw)
        return _solve_rounds(
            prob.multi_eval, prob.lo0, prob.hi0,
            rounds=rounds, spec_k=spec_k,
            sign_lo=prob.sign_lo, sign_bit=prob.sign_bit,
            iterations=iterations,
        )

    # 0-d params replicate; (B,) per-row params shard with the batch
    in_specs = ((P(data_spec, va),)
                + tuple(P(data_spec) if nd else P() for nd in arr_ndims))
    out_specs = (P(data_spec), P(data_spec))
    return jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def kinds() -> list[str]:
    return sorted({k for k, _ in _REGISTRY})


def backends_for(kind: str) -> list[str]:
    for module in _LAZY_BACKEND_MODULES.values():
        importlib.import_module(module)
    return sorted(b for k, b in _REGISTRY if k == kind)


# ---------------------------------------------------------------------------
# "jnp" oracle backends — broadcast-compare-reduce, always available
# ---------------------------------------------------------------------------

def _known_negative_sign_lo(batch: int, known: bool) -> Array | None:
    """sign bit of f(lo0) when it is statically known to be negative —
    skips the engine's M=1 probe pass (one whole operand sweep)."""
    return jnp.ones((batch,), bool) if known else None


def _param_col(p, dtype=jnp.float32) -> Array:
    """Problem parameter as a broadcast-ready column.

    Scalars stay 0-d (broadcast over the whole (B, M) grid); per-row
    parameter vectors (B,) become (B, 1) columns — this is how per-slot
    sampler configs ride the engine's native batch axis (serving PR).
    """
    arr = jnp.asarray(p, dtype)
    if arr.ndim == 0:
        return arr
    if arr.ndim == 1:
        return arr[:, None]
    raise ValueError(f"problem parameter must be scalar or (B,), "
                     f"got shape {arr.shape}")


@register("count_above", "jnp")
def _count_above_jnp(operand: Array, *, k) -> MonotoneProblem:
    """f(tau) = k - #{v : row[v] > tau}; monotone non-decreasing in tau.

    Counts are small integers — exact in f32 under ANY summation order — so
    this oracle is bit-identical to the tiled Pallas backend.
    """
    x = operand.astype(jnp.float32)
    lo0 = jnp.min(x, axis=-1) - 1.0
    hi0 = jnp.max(x, axis=-1) + 1.0

    k_col = _param_col(k)

    def multi_eval(taus: Array) -> Array:
        counts = jnp.sum(x[:, None, :] > taus[:, :, None], axis=-1)
        return k_col - counts.astype(jnp.float32)

    # f(lo0) = k - V: negative whenever k < V (the non-degenerate case).
    sign_lo = _known_negative_sign_lo(
        x.shape[0], isinstance(k, int) and k < x.shape[-1]
    )
    return MonotoneProblem(multi_eval, lo0, hi0, sign_lo=sign_lo)


@register("mass_at_or_above", "jnp")
def _mass_jnp(operand: Array, *, p) -> MonotoneProblem:
    """f(tau) = p - sum(row[v] where row[v] >= tau); non-decreasing."""
    probs = operand
    lo0 = jnp.zeros(probs.shape[:-1], probs.dtype)
    hi0 = jnp.max(probs, axis=-1) + jnp.asarray(1e-6, probs.dtype)

    p_col = _param_col(p, probs.dtype)

    def multi_eval(taus: Array) -> Array:
        keep = probs[:, None, :] >= taus[:, :, None]
        mass = jnp.sum(jnp.where(keep, probs[:, None, :], 0.0), axis=-1)
        return p_col - mass

    return MonotoneProblem(multi_eval, lo0, hi0)


@register("entropy_at_temperature", "jnp")
def _entropy_jnp(
    operand: Array, *, target, t_lo: float = 0.05, t_hi: float = 20.0
) -> MonotoneProblem:
    """f(T) = target - H(softmax(row / T)); H increasing in T."""
    z = operand.astype(jnp.float32)
    batch = z.shape[0]
    lo0 = jnp.full((batch,), t_lo, jnp.float32)
    hi0 = jnp.full((batch,), t_hi, jnp.float32)

    target_col = _param_col(target)

    def multi_eval(ts: Array) -> Array:
        zt = z[:, None, :] / ts[:, :, None]                 # (B, M, V)
        lse = jax.nn.logsumexp(zt, axis=-1, keepdims=True)
        logp = zt - lse
        h = -jnp.sum(jnp.exp(logp) * logp, axis=-1)          # (B, M)
        return target_col - h

    return MonotoneProblem(multi_eval, lo0, hi0)


# ---------------------------------------------------------------------------
# "jnp" vocab-sharded evaluators — run per device under shard_map
# ---------------------------------------------------------------------------
#
# Each mirrors its oracle above on the LOCAL vocab shard: the reduction
# over the vocab becomes a local partial sum + one `psum` over the policy's
# vocab axis (the paper's thread-join, now a collective), and bracket
# init pmin/pmaxes so every device in the vocab group agrees bit-for-bit.
# Count partials are small integers — psum is order-invariant, so the
# count kinds stay BIT-exact vs the unsharded oracle; mass/entropy psums
# reassociate float sums, which can only flip a walk decision when f sits
# within rounding noise of zero at a candidate (the sign walk consumes
# nothing but signs, so brackets — and downstream sampled tokens — are
# bit-identical whenever no candidate lands on such a knife edge; the
# subprocess harness in tests/test_sharded_serving.py pins this).

@register_sharded("count_above", "jnp")
def _count_above_jnp_sharded(
    local: Array, *, vocab_axis: str, global_v: int, k
) -> MonotoneProblem:
    x = local.astype(jnp.float32)
    lo0 = jax.lax.pmin(jnp.min(x, axis=-1), vocab_axis) - 1.0
    hi0 = jax.lax.pmax(jnp.max(x, axis=-1), vocab_axis) + 1.0
    k_col = _param_col(k)

    def multi_eval(taus: Array) -> Array:
        counts = jnp.sum(x[:, None, :] > taus[:, :, None], axis=-1)
        counts = jax.lax.psum(counts.astype(jnp.float32), vocab_axis)
        return k_col - counts

    sign_lo = _known_negative_sign_lo(
        x.shape[0], isinstance(k, int) and k < global_v
    )
    return MonotoneProblem(multi_eval, lo0, hi0, sign_lo=sign_lo)


@register_sharded("mass_at_or_above", "jnp")
def _mass_jnp_sharded(
    local: Array, *, vocab_axis: str, global_v: int, p
) -> MonotoneProblem:
    probs = local
    lo0 = jnp.zeros(probs.shape[:-1], probs.dtype)
    hi0 = (jax.lax.pmax(jnp.max(probs, axis=-1), vocab_axis)
           + jnp.asarray(1e-6, probs.dtype))
    p_col = _param_col(p, probs.dtype)

    def multi_eval(taus: Array) -> Array:
        keep = probs[:, None, :] >= taus[:, :, None]
        mass = jnp.sum(jnp.where(keep, probs[:, None, :], 0.0), axis=-1)
        return p_col - jax.lax.psum(mass, vocab_axis)

    return MonotoneProblem(multi_eval, lo0, hi0)


@register_sharded("entropy_at_temperature", "jnp")
def _entropy_jnp_sharded(
    local: Array, *, vocab_axis: str, global_v: int, target,
    t_lo: float = 0.05, t_hi: float = 20.0,
) -> MonotoneProblem:
    z = local.astype(jnp.float32)
    batch = z.shape[0]
    lo0 = jnp.full((batch,), t_lo, jnp.float32)
    hi0 = jnp.full((batch,), t_hi, jnp.float32)
    target_col = _param_col(target)

    def multi_eval(ts: Array) -> Array:
        zt = z[:, None, :] / ts[:, :, None]                 # (B, M, Vloc)
        m = jax.lax.pmax(jnp.max(zt, axis=-1), vocab_axis)  # (B, M) global
        se = jax.lax.psum(
            jnp.sum(jnp.exp(zt - m[..., None]), axis=-1), vocab_axis
        )
        lse = m + jnp.log(se)
        logp = zt - lse[..., None]
        h = -jax.lax.psum(
            jnp.sum(jnp.exp(logp) * logp, axis=-1), vocab_axis
        )
        return target_col - h

    return MonotoneProblem(multi_eval, lo0, hi0)


@register_sharded("count_below", "jnp")
def _count_below_jnp_sharded(
    local: Array, *, vocab_axis: str, global_v: int, q
) -> MonotoneProblem:
    x = local.astype(jnp.float32)
    lo0 = jax.lax.pmin(jnp.min(x, axis=-1), vocab_axis) - 1.0
    hi0 = jax.lax.pmax(jnp.max(x, axis=-1), vocab_axis) + 1.0
    q_col = _param_col(q)

    def multi_eval(cs: Array) -> Array:
        below = jnp.sum(x[:, None, :] < cs[:, :, None], axis=-1)
        below = jax.lax.psum(below.astype(jnp.float32), vocab_axis)
        return below / global_v - q_col

    sign_lo = _known_negative_sign_lo(
        x.shape[0], isinstance(q, float) and q > 0
    )
    return MonotoneProblem(multi_eval, lo0, hi0, sign_lo=sign_lo)


@register("count_below", "jnp")
def _count_below_jnp(operand: Array, *, q) -> MonotoneProblem:
    """f(c) = #{v : row[v] < c} / N - q; non-decreasing (quantile solve)."""
    x = operand.astype(jnp.float32)
    n = x.shape[-1]
    lo0 = jnp.min(x, axis=-1) - 1.0
    hi0 = jnp.max(x, axis=-1) + 1.0

    q_col = _param_col(q)

    def multi_eval(cs: Array) -> Array:
        below = jnp.sum(x[:, None, :] < cs[:, :, None], axis=-1)
        return below.astype(jnp.float32) / n - q_col

    # f(lo0) = 0/N - q: negative for any positive static q.
    sign_lo = _known_negative_sign_lo(
        x.shape[0], isinstance(q, float) and q > 0
    )
    return MonotoneProblem(multi_eval, lo0, hi0, sign_lo=sign_lo)
