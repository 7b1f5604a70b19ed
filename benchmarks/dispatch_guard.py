"""Fused-horizon serving's twin contracts, as an executable assertion (CI).

Under N forced host devices, a fused-horizon server (``step_horizon=K``)
on a greedy workload must (a) emit per-request token streams
BIT-IDENTICAL to per-step (K=1) serving — the horizon scan runs the same
traced step body, so any divergence means the in-scan done-masking or the
host replay rotted — and (b) actually amortize dispatch: steady-state
decode dispatches (total dispatches minus the two prefill launches each
admission costs) must come in at or under ``--max-dispatch-ratio`` of the
per-step run's, and the fused warm pass must not be SLOWER than per-step
(``--min-speedup``, default 1.0 — the guard pins the floor, the serving
benchmark reports the actual win).

Runs the measurement in a subprocess because the forced-device flag must
be set before jax touches the backend:

  PYTHONPATH=src python -m benchmarks.dispatch_guard --devices 8 \\
      --step-horizon 8 --max-dispatch-ratio 0.25

Exit code 0 iff all contracts hold.  Writes ``dispatch_guard.json``
(CWD) with dispatch/throughput detail for CI to upload as an artifact.
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
    K = int(sys.argv[2])
    if D > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={D}")
    import dataclasses, json, time
    import jax, jax.numpy as jnp
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request, RunaheadServer

    mesh = None
    if D > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, D // 2), ("data", "model"))

    # Same tie-free shape rationale as spec_guard: small vocab (96) keeps
    # bf16 top-logit ties out of the greedy bit-exactness contract, and
    # n_new=80 streams give the horizon a long steady state where
    # dispatch accounting is admission-free.
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=48,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=96, vocab=96,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    CONTEXT = 112
    sc = SamplerConfig(greedy=True, top_k=50)
    pats = [[3, 5, 7, 11], [2, 4, 6, 8], [9, 9, 1, 3]]
    reqs = [
        Request(f"r{i}", (pats[i % 3] * 3)[:8], 80, seed=10 + i, sampler=sc)
        for i in range(6)
    ]

    def serve(horizon):
        server = RunaheadServer(cfg, params, n_slots=4, context=CONTEXT,
                                mesh=mesh, step_horizon=horizon)
        walls = []
        done = {}
        for _ in range(2):                    # report the jit-warm pass
            t0 = time.perf_counter()
            done = {c.rid: c for c in server.run(reqs)}
            walls.append(time.perf_counter() - t0)
        s = server.scheduler
        return done, walls[-1], s

    ref, wall_ref, s_ref = serve(1)
    fused, wall_fused, s_fused = serve(K)
    mismatches = [r.rid for r in reqs
                  if fused[r.rid].tokens != ref[r.rid].tokens]

    # admission prefill costs 2 dispatches in both modes; subtract it so
    # the ratio measures the steady-state decode loop the horizon fuses
    decode_ref = s_ref.n_dispatches - 2 * s_ref.n_admissions
    decode_fused = s_fused.n_dispatches - 2 * s_fused.n_admissions
    tokens = sum(len(c.tokens) for c in fused.values())
    print("GUARD " + json.dumps({
        "devices": D,
        "step_horizon": K,
        "bit_exact": not mismatches,
        "mismatched_rids": mismatches,
        "dispatches_per_step": s_ref.n_dispatches,
        "dispatches_fused": s_fused.n_dispatches,
        "decode_dispatches_per_step": decode_ref,
        "decode_dispatches_fused": decode_fused,
        "dispatch_ratio": round(decode_fused / max(1, decode_ref), 4),
        "host_syncs_per_step": s_ref.n_host_syncs,
        "host_syncs_fused": s_fused.n_host_syncs,
        "wasted_steps": s_fused.n_wasted_steps,
        "tokens": tokens,
        "wall_per_step_s": round(wall_ref, 3),
        "wall_fused_s": round(wall_fused, 3),
        "speedup": round(wall_ref / wall_fused, 3),
    }), flush=True)
""")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--step-horizon", type=int, default=8)
    ap.add_argument("--max-dispatch-ratio", type=float, default=0.25)
    ap.add_argument("--min-speedup", type=float, default=1.0)
    ap.add_argument("--out", default="dispatch_guard.json",
                    help="artifact path for the guard report")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a CPU-rig child: pinned to the CPU so it can never contend with
    # this or any other process for a TPU
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(args.devices),
         str(args.step_horizon)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    sys.stderr.write(r.stderr[-3000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("GUARD ")]
    if r.returncode != 0 or not lines:
        print("dispatch_guard: measurement subprocess failed")
        return 1
    g = json.loads(lines[-1][len("GUARD "):])
    ok = (g["bit_exact"]
          and g["dispatch_ratio"] <= args.max_dispatch_ratio
          and g["speedup"] >= args.min_speedup)
    report = {**g, "max_dispatch_ratio": args.max_dispatch_ratio,
              "min_speedup": args.min_speedup, "ok": ok}
    print(json.dumps(report, indent=1))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if not g["bit_exact"]:
        print("dispatch_guard: FAIL — fused streams diverged from "
              f"per-step for {g['mismatched_rids']}")
        return 1
    if g["dispatch_ratio"] > args.max_dispatch_ratio:
        print("dispatch_guard: FAIL — decode dispatch ratio "
              f"{g['dispatch_ratio']} > {args.max_dispatch_ratio} "
              f"({g['decode_dispatches_fused']} fused vs "
              f"{g['decode_dispatches_per_step']} per-step)")
        return 1
    if g["speedup"] < args.min_speedup:
        print(f"dispatch_guard: FAIL — fused warm pass {g['speedup']}x "
              f"per-step, below {args.min_speedup}x")
        return 1
    print(f"dispatch_guard: OK — bit-exact streams, dispatch ratio "
          f"{g['dispatch_ratio']}, {g['speedup']}x warm speedup "
          f"({args.devices} devices, K={args.step_horizon})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
