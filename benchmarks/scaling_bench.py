"""Device-scaling benchmark for the mesh-native solver engine (DESIGN.md §5).

One subprocess per device count (1/2/4/8 forced host devices — the flag
must be set before jax touches the backend, hence subprocesses), each
measuring both engine backends:

  * solver round latency — a batched ``count_above`` solve with the vocab
    sharded over a (1, d) ("data", "model") mesh: d-way partial counting
    plus the per-round psum join (the paper's thread-join cost, Fig. 6's
    collective-overhead regime — on one CPU socket the collective is a
    memcpy, so expect overhead-dominated numbers, shape only);
  * serving throughput — the continuous-batching server slot-sharded over
    a (d, 1) mesh (pure data parallelism; d=1 is the meshless baseline),
    measured per-step AND fused (``step_horizon=4``, DESIGN.md §14) so
    the dispatch-amortization trajectory is on the board per device
    count alongside per-cell dispatch/host-sync counts.

Every (devices, backend) cell is measured twice: ``policy=fixed`` under
``tuning.disabled()`` (the legacy hard-coded vocab-sharded path — the
regressing line of the seed artifact) and ``policy=tuned`` with the
autotuner's measured tier on, recording the Decision it picked.  Cells
also stamp ``device_kind`` / ``pallas_interpret`` so trajectories across
machines are comparable.

Emits ``BENCH_scaling.json`` via the run.py artifact hook.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap

from benchmarks.common import row

DEVICE_COUNTS = (1, 2, 4, 8)
BACKENDS = ("jnp", "pallas")

_PAYLOAD: dict | None = None

_SCRIPT = textwrap.dedent("""
    import os, sys
    D = int(sys.argv[1])
    BACKENDS = sys.argv[2].split(",")
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={D}")
    import dataclasses, json, time
    import jax, jax.numpy as jnp
    from repro.core import solver, tuning
    from repro.launch.mesh import make_mesh
    from repro.models.testing import reduced_config
    from repro.models.transformer import init_params
    from repro.serving.sampler import SamplerConfig
    from repro.serving.server import Request, RunaheadServer

    B, V, K = 8, 8192, 50
    ROUNDS, SPEC_K = 6, 4
    N_SLOTS, N_REQ, PROMPT, NEW = 8, 10, 8, 8
    HZ = 4                        # fused cells' steps per dispatch

    x = jax.random.normal(jax.random.PRNGKey(0), (B, V), jnp.float32)
    mesh_v = make_mesh((1, D), ("data", "model"))
    mesh_s = make_mesh((D, 1), ("data", "model"))

    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, vocab=512,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)

    def timed(fn, reps=5):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    dev0 = jax.devices()[0]
    from repro.kernels.ops import _interpret
    cell_env = {"device_kind": dev0.platform,
                "pallas_interpret": bool(_interpret())}

    for backend in BACKENDS:
        # jit the whole solve so d=1 (plain path, otherwise eager) and
        # d>1 (already-compiled shard_map) compare compiled-to-compiled;
        # the policy is read at trace time, closure-static per backend.
        # tuning.disabled() pins the legacy fixed policy — these are the
        # rows the tuned cell below is judged against.
        @jax.jit
        def solve(x=x, backend=backend):
            with solver.mesh_policy(mesh_v if D > 1 else None):
                return solver.solve_kind(
                    "count_above", x, backend=backend, k=K,
                    rounds=ROUNDS, spec_k=SPEC_K)
        with tuning.disabled():
            solver_s = timed(solve)

        # tuned cell: same budget, the tuner picks the decomposition /
        # placement / backend-within-preference (measured tier on)
        @jax.jit
        def solve_tuned(x=x, backend=backend):
            with solver.mesh_policy(mesh_v if D > 1 else None):
                return solver.solve_kind(
                    "count_above", x, backend=backend, k=K,
                    rounds=ROUNDS, spec_k=SPEC_K)
        with tuning.autotune():
            jax.block_until_ready(solve_tuned())   # trace + tune
        tuned_s = timed(solve_tuned)
        decision = (tuning.explain()[-1][1].to_json()
                    if tuning.explain() else None)
        print("CELL " + json.dumps(dict(
            cell_env, devices=D, backend=backend, policy="tuned",
            solver_round_us=round(1e6 * tuned_s / ROUNDS, 1),
            solver_solve_us=round(1e6 * tuned_s, 1),
            decision=decision,
        )), flush=True)

        reqs = [
            Request(rid=i, prompt=[(7 * i + j) % cfg.vocab
                                   for j in range(PROMPT)],
                    n_new=NEW, seed=100 + i,
                    sampler=SamplerConfig(top_k=K, backend=backend))
            for i in range(N_REQ)
        ]
        server = RunaheadServer(
            cfg, params, n_slots=N_SLOTS, context=PROMPT + NEW,
            backend=backend, mesh=mesh_s if D > 1 else None)
        with tuning.disabled():
            t0 = time.perf_counter()
            for r in reqs:
                server.submit(r)
            done = server.drain()
            wall = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in done)
        print("CELL " + json.dumps(dict(
            cell_env, devices=D, backend=backend, policy="fixed",
            solver_round_us=round(1e6 * solver_s / ROUNDS, 1),
            solver_solve_us=round(1e6 * solver_s, 1),
            serving_wall_s=round(wall, 3),
            serving_tok_per_s=round(toks / wall, 2),
            decode_steps=server.scheduler.n_decode_steps,
            dispatches=server.scheduler.n_dispatches,
            host_syncs=server.scheduler.n_host_syncs,
        )), flush=True)

        # fused-horizon serving cell: same workload with K=HZ decode
        # steps per compiled dispatch — the per-device-count view of the
        # dispatch amortization (streams identical; the interesting
        # trajectory is dispatches vs the per-step row above)
        server_f = RunaheadServer(
            cfg, params, n_slots=N_SLOTS, context=PROMPT + NEW,
            backend=backend, mesh=mesh_s if D > 1 else None,
            step_horizon=HZ)
        with tuning.disabled():
            t0 = time.perf_counter()
            for r in reqs:
                server_f.submit(r)
            done_f = server_f.drain()
            wall_f = time.perf_counter() - t0
        toks_f = sum(len(c.tokens) for c in done_f)
        sf = server_f.scheduler
        print("CELL " + json.dumps(dict(
            cell_env, devices=D, backend=backend, policy="fused",
            step_horizon=HZ,
            serving_wall_s=round(wall_f, 3),
            serving_tok_per_s=round(toks_f / wall_f, 2),
            decode_steps=sf.n_decode_steps,
            dispatches=sf.n_dispatches,
            host_syncs=sf.n_host_syncs,
            wasted_steps=sf.n_wasted_steps,
        )), flush=True)
""")


def run() -> list[str]:
    global _PAYLOAD
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a CPU-rig child: pinned to the CPU so it can never contend with
    # this or any other process for a TPU
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # tuned cells micro-benchmark + persist winners; keep that out of the
    # user's real cache (one throwaway cache shared across device counts)
    if "REPRO_TUNING_CACHE" not in env:
        env["REPRO_TUNING_CACHE"] = os.path.join(
            tempfile.mkdtemp(prefix="repro_scaling_"), "tuning.json")

    out, results = [], []
    for d in DEVICE_COUNTS:
        try:
            r = subprocess.run(
                [sys.executable, "-c", _SCRIPT, str(d),
                 ",".join(BACKENDS)], env=env,
                capture_output=True, text=True, timeout=560,
            )
            stdout, stderr = r.stdout, r.stderr
        except subprocess.TimeoutExpired as e:
            stdout, stderr = "", f"timeout after {e.timeout}s"
        cells = [json.loads(line[len("CELL "):])
                 for line in stdout.splitlines()
                 if line.startswith("CELL ")]
        if not cells:
            out.append(row(f"scaling/d{d}_FAILED", 0.0,
                           stderr[-200:].replace(",", ";")
                           .replace("\n", " ")))
            continue
        results.extend(cells)
        for c in cells:
            if c.get("policy") == "tuned":
                dec = c.get("decision") or {}
                out.append(row(
                    f"scaling/d{d}_{c['backend']}_tuned",
                    c["solver_round_us"],
                    f"placement={dec.get('placement')};"
                    f"spec_k={dec.get('spec_k')};"
                    f"source={dec.get('source')}",
                ))
            elif c.get("policy") == "fused":
                out.append(row(
                    f"scaling/d{d}_{c['backend']}_fused",
                    1e6 * c["serving_wall_s"],
                    f"serve_tok_per_s={c['serving_tok_per_s']};"
                    f"dispatches={c['dispatches']};"
                    f"hz={c['step_horizon']}",
                ))
            else:
                out.append(row(
                    f"scaling/d{d}_{c['backend']}", c["solver_round_us"],
                    f"serve_tok_per_s={c['serving_tok_per_s']};"
                    f"decode_steps={c['decode_steps']}",
                ))

    _PAYLOAD = {
        "bench": "scaling",
        "unit": "solver us per speculative round; serving tok/s",
        "config": {
            "device_counts": list(DEVICE_COUNTS),
            "backends": list(BACKENDS),
            "policies": ["fixed", "tuned", "fused"],
            "solver": {"batch": 8, "vocab": 8192, "k": 50,
                       "rounds": 6, "spec_k": 4,
                       "mesh": "(1, d) vocab-sharded"},
            "serving": {"n_slots": 8, "requests": 10, "prompt_len": 8,
                        "n_new": 8, "vocab": 512,
                        "mesh": "(d, 1) slot-sharded"},
            "note": "forced host devices on one CPU socket: collective "
                    "cost is real, compute scaling is not — shape only",
        },
        "results": results,
    }
    return out


def json_payload() -> tuple[str, dict] | None:
    """(filename, payload) for run.py to write; None before run()."""
    if _PAYLOAD is None:
        return None
    return "BENCH_scaling.json", _PAYLOAD


if __name__ == "__main__":
    print("\n".join(run()))
    print(json.dumps(_PAYLOAD, indent=2))
