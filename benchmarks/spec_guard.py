"""Speculative decoding's twin contracts, as an executable assertion (CI).

Under N forced host devices, a mesh-native speculative server on a
repetitive greedy workload must (a) emit per-request token streams
BIT-IDENTICAL to greedy serial decode — the sequence-level analogue of the
solver's serial-equivalence contract — and (b) accept at least
``--min-acceptance`` of the n-gram self-drafted tokens (the workload is
built so self-drafting wins; a collapse here means the draft/verify
plumbing rotted even if bit-exactness still holds via rejecting
everything).

Runs the measurement in a subprocess because the forced-device flag must
be set before jax touches the backend:

  PYTHONPATH=src python -m benchmarks.spec_guard --devices 8 \\
      --min-acceptance 0.5

Exit code 0 iff both contracts hold.  Writes ``spec_guard.json`` (CWD)
with acceptance/throughput detail for CI to upload as an artifact.
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
    DRAFT_LEN = int(sys.argv[2])
    if D > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={D}")
    import dataclasses, json, time
    import jax, jax.numpy as jnp
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import (
        Request, RunaheadServer, generate_oneshot_reference)

    mesh = None
    if D > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, D // 2), ("data", "model"))

    # Shape chosen with care, both knobs matter:
    #   * streams long enough (n_new=80) that greedy decode settles into
    #     loops the n-gram drafter predicts — that is where acceptance
    #     comes from;
    #   * vocab SMALL (96).  The forward computes in bf16, whose ~8-bit
    #     mantissa grid makes EXACT top-logit ties common at large
    #     vocabs; a tie's argmax can legitimately differ between the
    #     reference and serving compilations (reassociation), which
    #     would make greedy "bit-exactness" a coin flip, not a contract.
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
    refs = {r.rid: generate_oneshot_reference(cfg, params, r,
                                              context=CONTEXT)
            for r in reqs}

    server = RunaheadServer(cfg, params, n_slots=4, context=CONTEXT,
                            mesh=mesh, draft_len=DRAFT_LEN)
    t0 = time.perf_counter()
    done = {c.rid: c for c in server.run(reqs)}
    wall = time.perf_counter() - t0
    mismatches = [r.rid for r in reqs if done[r.rid].tokens != refs[r.rid]]
    s = server.scheduler
    print("GUARD " + json.dumps({
        "devices": D,
        "draft_len": DRAFT_LEN,
        "bit_exact": not mismatches,
        "mismatched_rids": mismatches,
        "drafted": s.n_drafted,
        "accepted": s.n_accepted,
        "acceptance_rate": round(s.acceptance_rate, 4),
        "decode_steps": s.n_decode_steps,
        "tokens": sum(len(c.tokens) for c in done.values()),
        "wall_s": round(wall, 3),
    }), flush=True)
""")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--draft-len", type=int, default=4)
    ap.add_argument("--min-acceptance", type=float, default=0.5)
    ap.add_argument("--out", default="spec_guard.json",
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
         str(args.draft_len)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    sys.stderr.write(r.stderr[-3000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("GUARD ")]
    if r.returncode != 0 or not lines:
        print("spec_guard: measurement subprocess failed")
        return 1
    g = json.loads(lines[-1][len("GUARD "):])
    ok = g["bit_exact"] and g["acceptance_rate"] >= args.min_acceptance
    report = {**g, "min_acceptance": args.min_acceptance, "ok": ok}
    print(json.dumps(report, indent=1))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if not g["bit_exact"]:
        print("spec_guard: FAIL — greedy speculative streams diverged "
              f"from serial for {g['mismatched_rids']}")
        return 1
    if g["acceptance_rate"] < args.min_acceptance:
        print(f"spec_guard: FAIL — acceptance {g['acceptance_rate']} < "
              f"{args.min_acceptance} (drafted {g['drafted']}, accepted "
              f"{g['accepted']})")
        return 1
    print(f"spec_guard: OK — bit-exact greedy streams, acceptance "
          f"{g['acceptance_rate']} over {g['drafted']} drafts "
          f"({args.devices} devices, draft_len {args.draft_len})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
