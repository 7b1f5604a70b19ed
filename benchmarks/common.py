"""Timing helpers for the benchmark harness (CPU wall-clock; jit-warmed,
second execution onward — the paper's own convention: 'we run each program
two times and report the results of the second execution')."""
from __future__ import annotations

import time

import jax


def timed_s(fn, *args, reps: int = 5, warmup: int = 1) -> float:
    """Median of `reps` timed calls (median resists CPU scheduler noise on
    the microsecond-scale paper benches)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"


def env_info() -> dict:
    """Machine identity stamped into every BENCH_*.json artifact, so
    trajectories across machines are comparable (a 5422 µs/round pallas
    cell means something different in interpret mode on one CPU socket
    than compiled on a TPU slice)."""
    dev = jax.devices()[0]
    from repro.kernels.ops import interpret_mode, interpret_mode_source

    interpret = interpret_mode()
    interpret_source = interpret_mode_source()
    return {
        "device_kind": dev.platform,
        "device_model": str(getattr(dev, "device_kind", "") or ""),
        "platform_version": str(getattr(dev.client, "platform_version", "")),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "pallas_interpret": interpret,
        # "env" when REPRO_PALLAS_INTERPRET forced the mode, else "auto"
        "pallas_interpret_source": interpret_source,
    }
