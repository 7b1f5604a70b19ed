"""Paged-KV cache contracts, as an executable assertion (CI).

Under N forced host devices, a paged continuous server on a shared-prefix
greedy workload must (a) emit per-request token streams BIT-IDENTICAL to
the dense ring-buffer server — the paged differential contract from
DESIGN.md §13 — and (b) keep its peak resident cache rows
(``peak_pages * page_size``) at most ``--max-rows-frac`` of what the
dense cache pins for the same concurrency (``n_slots * context`` rows,
allocated up front whether used or not).  The workload's requests share
long prompt prefixes, so copy-on-write page sharing plus prefill skip is
exactly where the row savings must come from; the report also counts
prefix hits and skipped prefill tokens so a silent COW regression (bit
exactness intact, every admission cold) still fails the bar.

Runs the measurement in a subprocess because the forced-device flag must
be set before jax touches the backend:

  PYTHONPATH=src python -m benchmarks.paged_guard --devices 8 \\
      --page-size 4 --max-rows-frac 0.7

Exit code 0 iff both contracts hold.  Writes ``paged_guard.json`` (CWD)
with page/row/skip detail for CI to upload as an artifact.
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
    PAGE = int(sys.argv[2])
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

    # Small vocab (96): the forward computes in bf16, whose ~8-bit
    # mantissa grid makes exact top-logit ties common at large vocabs;
    # a tie's argmax can differ between compilations, which would turn
    # greedy bit-exactness into a coin flip instead of a contract.
    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=48,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=96, vocab=96,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    CONTEXT = 64
    N_SLOTS = 4
    sc = SamplerConfig(greedy=True, top_k=50)

    # Shared-prefix workload: 3 families x 3 requests.  Every family
    # shares a 16-token prompt prefix (4 full pages at PAGE=4) and
    # diverges in the last 4 prompt tokens, so siblings admitted while
    # the first holder is live fork its prefix pages (refcount bump, no
    # prefill) instead of recomputing them.  The prompt constants are
    # chosen so no greedy step in any trajectory lands on an EXACT
    # bf16 top-logit tie — a tie's argmax can legitimately differ
    # between the dense and paged compilations, which would make the
    # bit-exactness check a coin flip instead of a contract.
    reqs = []
    for fam in range(3):
        base = [(9 + 17 * fam + i) % 96 for i in range(16)]
        for j in range(3):
            reqs.append(Request(
                f"f{fam}r{j}", base + [(40 + 5 * fam + j) % 96] * 4,
                16 + 4 * j, seed=10 + fam * 3 + j, sampler=sc))

    dense = RunaheadServer(cfg, params, n_slots=N_SLOTS, context=CONTEXT,
                           mesh=mesh)
    refs = {c.rid: c.tokens for c in dense.run(reqs)}

    server = RunaheadServer(cfg, params, n_slots=N_SLOTS, context=CONTEXT,
                            mesh=mesh, page_size=PAGE)
    t0 = time.perf_counter()
    done = {c.rid: c for c in server.run(reqs)}
    wall = time.perf_counter() - t0
    mismatches = [r.rid for r in reqs if done[r.rid].tokens != refs[r.rid]]
    s = server.scheduler
    dense_rows = N_SLOTS * CONTEXT
    print("GUARD " + json.dumps({
        "devices": D,
        "page_size": PAGE,
        "bit_exact": not mismatches,
        "mismatched_rids": mismatches,
        "peak_pages": s.peak_pages,
        "peak_rows": s.peak_pages * PAGE,
        "dense_rows": dense_rows,
        "rows_frac": round(s.peak_pages * PAGE / dense_rows, 4),
        "prefix_hits": s.n_prefix_hits,
        "prefill_tokens_skipped": s.n_prefill_skipped,
        "decode_steps": s.n_decode_steps,
        "tokens": sum(len(c.tokens) for c in done.values()),
        "wall_s": round(wall, 3),
    }), flush=True)
""")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--max-rows-frac", type=float, default=0.7,
                    help="peak paged rows must be <= this fraction of the "
                         "dense cache's n_slots*context resident rows")
    ap.add_argument("--out", default="paged_guard.json",
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
         str(args.page_size)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    sys.stderr.write(r.stderr[-3000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("GUARD ")]
    if r.returncode != 0 or not lines:
        print("paged_guard: measurement subprocess failed")
        return 1
    g = json.loads(lines[-1][len("GUARD "):])
    ok = (g["bit_exact"] and g["rows_frac"] <= args.max_rows_frac
          and g["prefix_hits"] > 0)
    report = {**g, "max_rows_frac": args.max_rows_frac, "ok": ok}
    print(json.dumps(report, indent=1))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if not g["bit_exact"]:
        print("paged_guard: FAIL — paged streams diverged from dense "
              f"for {g['mismatched_rids']}")
        return 1
    if g["rows_frac"] > args.max_rows_frac:
        print(f"paged_guard: FAIL — peak rows {g['peak_rows']} is "
              f"{g['rows_frac']:.0%} of dense {g['dense_rows']} "
              f"(bar {args.max_rows_frac:.0%})")
        return 1
    if g["prefix_hits"] == 0:
        print("paged_guard: FAIL — shared-prefix workload produced zero "
              "prefix hits (COW sharing regressed)")
        return 1
    print(f"paged_guard: OK — bit-exact paged streams, peak rows "
          f"{g['peak_rows']}/{g['dense_rows']} ({g['rows_frac']:.0%}), "
          f"{g['prefix_hits']} prefix hits, "
          f"{g['prefill_tokens_skipped']} prefill tokens skipped "
          f"({args.devices} devices, page_size {args.page_size})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
