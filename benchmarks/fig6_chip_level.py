"""Paper Fig. 6, chip-level variant: the overhead cliff reproduced.

The paper's slowdown-for-cheap-f regime comes from thread create/join
cost.  On a TPU pod the analogous cost is the per-round sign all_gather
when speculative points live on DIFFERENT CHIPS (core/sharded.py).  This
benchmark runs the shard_map implementation on 8 forced host devices in a
subprocess and sweeps the function latency — the collective overhead
recreates the paper's crossover qualitatively.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import row

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import time, jax
    import jax.numpy as jnp
    from repro.core import find_root_serial, find_root_runahead_sharded, make_paper_f

    mesh = jax.make_mesh((8,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    N, K = 6, 2
    for terms in (10, 100, 1000, 5000):
        f = make_paper_f(terms)
        a, b = jnp.float32(1.0), jnp.float32(2.0)
        def serial(aa, bb):
            return find_root_serial(f, aa, bb, N, "signbit")
        def sharded(aa, bb):
            return find_root_runahead_sharded(f, aa, bb, N, K, mesh)
        for fn in (serial, sharded):
            fn(a, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(10): out = serial(a, b)
        out.block_until_ready(); ts = (time.perf_counter() - t0) / 10
        t0 = time.perf_counter()
        for _ in range(10): out = sharded(a, b)
        out.block_until_ready(); tr = (time.perf_counter() - t0) / 10
        print(f"CHIP,{terms},{tr*1e6:.1f},{ts/tr - 1.0:+.3f}")
""")


def run() -> list[str]:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a CPU-rig child: pinned to the CPU so it can never contend with
    # this or any other process for a TPU
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    out = []
    for line in r.stdout.splitlines():
        if line.startswith("CHIP,"):
            _, terms, us, speedup = line.split(",")
            out.append(row(f"fig6chip/terms_{terms}", float(us),
                           f"speedup={speedup};paper_cliff_analogue"))
    if not out:
        out.append(row("fig6chip/FAILED", 0.0, r.stderr[-200:].replace(
            ",", ";").replace("\n", " ")))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
