"""The tuner's no-regression contract, as an executable assertion (CI).

Under N forced host devices, a tuned ``solve_kind`` with an ACTIVE mesh
policy must be no slower than the single-device fixed-policy solve
(within ``--tolerance``) for the BENCH_scaling solver config (B=8,
V=8192) — the configuration whose fixed vocab-sharded policy regresses
641 -> 1374 µs/round from 1 -> 8 devices in the seed artifact.  The
tuner's escape hatch (placement "single" always in the candidate set)
makes this hold by construction; this guard keeps it held.

Runs the measurement in a subprocess because the forced-device flag must
be set before jax touches the backend:

  PYTHONPATH=src python -m benchmarks.tuned_guard --devices 8 \\
      --tolerance 1.1

Exit code 0 iff the contract holds.  The tuning cache the measured tier
persisted (REPRO_TUNING_CACHE, default CWD ``tuning_cache.json`` here)
is left on disk for CI to upload as an artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os, sys
    D = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={D}")
    import json, time
    import jax, jax.numpy as jnp
    from repro.core import solver, tuning
    from repro.launch.mesh import make_mesh

    B, V, K = 8, 8192, 50
    ROUNDS, SPEC_K = 6, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (B, V), jnp.float32)
    mesh = make_mesh((1, D), ("data", "model"))

    def timed(fn, reps=7):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    # baseline: the fixed single-device solve (no mesh policy), pinned
    # legacy configuration — the "1-device latency" of the contract
    @jax.jit
    def fixed_single(x=x):
        return solver.solve_kind("count_above", x, k=K,
                                 rounds=ROUNDS, spec_k=SPEC_K)
    with tuning.disabled():
        single_s = timed(fixed_single)

    # tuned: same budget, mesh policy ACTIVE, measured tier on — the
    # tuner may shard or take the single-device escape hatch
    @jax.jit
    def tuned(x=x):
        with solver.mesh_policy(mesh):
            return solver.solve_kind("count_above", x, k=K,
                                     rounds=ROUNDS, spec_k=SPEC_K)
    with tuning.autotune():
        jax.block_until_ready(tuned())          # trace + tune
    tuned_s = timed(tuned)

    ref, out = fixed_single(x), tuned(x)
    exact = bool(jnp.array_equal(ref[0], out[0])
                 & jnp.array_equal(ref[1], out[1]))
    decision = tuning.explain()[-1][1].to_json() if tuning.explain() else None
    print("GUARD " + json.dumps({
        "devices": D,
        "single_round_us": round(1e6 * single_s / ROUNDS, 1),
        "tuned_round_us": round(1e6 * tuned_s / ROUNDS, 1),
        "bit_exact": exact,
        "decision": decision,
        "cache": tuning.cache_path(),
    }), flush=True)
""")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--tolerance", type=float, default=1.1,
                    help="tuned round must be <= tolerance * single round")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a CPU-rig child: pinned to the CPU so it can never contend with
    # this or any other process for a TPU
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.setdefault("REPRO_TUNING_CACHE",
                   os.path.join(os.getcwd(), "tuning_cache.json"))

    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(args.devices)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    sys.stderr.write(r.stderr[-3000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("GUARD ")]
    if r.returncode != 0 or not lines:
        print("tuned_guard: measurement subprocess failed")
        return 1
    g = json.loads(lines[-1][len("GUARD "):])
    ratio = g["tuned_round_us"] / max(g["single_round_us"], 1e-9)
    ok = ratio <= args.tolerance and g["bit_exact"]
    print(json.dumps({**g, "ratio": round(ratio, 3),
                      "tolerance": args.tolerance,
                      "ok": ok}, indent=1))
    if not g["bit_exact"]:
        print("tuned_guard: FAIL — tuned brackets diverged from fixed")
        return 1
    if ratio > args.tolerance:
        print(f"tuned_guard: FAIL — tuned round {g['tuned_round_us']} us > "
              f"{args.tolerance}x single round {g['single_round_us']} us")
        return 1
    print(f"tuned_guard: OK — tuned {g['tuned_round_us']} us/round vs "
          f"single {g['single_round_us']} us/round "
          f"({args.devices} devices)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
