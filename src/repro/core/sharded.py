"""Chip-level runahead bisection: speculative points across the mesh.

This is the multicore-substrate form of the paper's scheme on a TPU pod:
each chip along the ``model`` mesh axis plays the role of a block of helper
threads, evaluating its shard of the 2**k - 1 speculative points.  The
paper's shared sign-array becomes ONE tiny ``all_gather`` of values
(2**k - 1 floats) — this collective latency is the TPU analogue of the
paper's thread-join cost and drives the Fig. 6 crossover benchmark.

Since the mesh-native engine PR this module is a THIN B=1 VIEW of the
batched solver engine (``repro.core.solver``), exactly the way
``runahead_solve`` is the engine's B=1 scalar view: the round loop, the
midpoint tree, and the serial-exact sign walk are the engine's own
(``_solve_rounds`` with an ``iterations`` budget and last-mid tracking);
only the point-sharded ``multi_eval`` — slice my chunk, evaluate, gather —
lives here.

Implementation notes:
  * 2**k - 1 points don't tile evenly over D devices, so the grid is padded
    via ``_pad_fill`` (a repeat of the last point); the padded evaluations'
    signs are computed and DISCARDED — the gathered value vector is
    truncated to 2**k - 1 before the walk ever looks at it (the uneven-
    split tests poison the pad to prove it).
  * Every device runs the identical O(k) index walk on the gathered signs,
    so the new interval is consistent everywhere with no broadcast step —
    exactly the paper's "each thread compares its neighbours" symmetry.
  * The compiled step is CACHED per (f, iterations, spec_k, mesh, axis,
    dtype): repeated calls re-use one jit(shard_map) instead of rebuilding
    it around a fresh closure every invocation (the per-call retrace the
    Fig. 6 chip-level bench used to pay).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bisect import _sign_bit
from repro.core.solver import _solve_rounds


def _pad_fill(interior: jax.Array, n_fill: int) -> jax.Array:
    """Pad values for the uneven split: repeats of the last interior point.

    Any value is legal here — the padded signs never reach the walk — so
    tests monkeypatch this with poison (NaN/inf) to assert the discard.
    """
    return jnp.full((n_fill,), interior[-1], interior.dtype)


@functools.lru_cache(maxsize=64)
def _cached_sharded_solve(
    f: Callable[[jax.Array], jax.Array],
    iterations: int,
    spec_k: int,
    mesh: jax.sharding.Mesh,
    axis: str,
    dtype: str,
):
    """Build (once) the compiled point-sharded solve for this config.

    Keyed on ``f`` BY IDENTITY: reuse one callable across calls (as the
    benches and tests do) to hit the cache — a fresh closure per call is
    a miss every time, i.e. exactly the old rebuild-per-call cost, and
    the evicting cache additionally retains up to 64 stale closures plus
    whatever arrays they capture.
    """
    k = spec_k
    n_pts = (1 << k) - 1
    d = mesh.shape[axis]
    padded = -(-n_pts // d) * d

    def per_device(a, b, sign_lo):
        # Executed under shard_map: a/b/sign_lo are replicated scalars.
        idx = jax.lax.axis_index(axis)

        def multi_eval(taus: jax.Array) -> jax.Array:    # (1, 2**k - 1)
            pts = jnp.concatenate(
                [taus[0], _pad_fill(taus[0], padded - n_pts)]
            )
            my = jax.lax.dynamic_slice(
                pts, (idx * (padded // d),), (padded // d,)
            )
            vals = f(my)                                 # local evals
            gathered = jax.lax.all_gather(vals, axis, tiled=True)
            return gathered[:n_pts][None]                # pad discarded

        _, _, lm = _solve_rounds(
            multi_eval, a[None], b[None],
            rounds=0, spec_k=k, sign_lo=sign_lo[None],
            iterations=iterations, return_last_mid=True,
        )
        return lm[0]

    shmapped = jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False,
    )
    return jax.jit(shmapped)


def find_root_runahead_sharded(
    f: Callable[[jax.Array], jax.Array],
    a: jax.Array,
    b: jax.Array,
    iterations: int,
    spec_k: int,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
) -> jax.Array:
    """Runahead bisection with speculative evals sharded over a mesh axis.

    A B=1 view of the engine's mesh path: returns the last midpoint
    examined (Algorithm 1's contract), trajectory-identical to
    ``find_root_serial(mode="signbit")``.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b, dtype=a.dtype)
    sign_lo = _sign_bit(f(a[None])[0])
    solve = _cached_sharded_solve(
        f, iterations, spec_k, mesh, axis, str(a.dtype)
    )
    return solve(a, b, sign_lo)
