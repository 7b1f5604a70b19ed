"""Production launch hygiene: process environment setup (DESIGN.md §15).

The launcher knobs every serious JAX deployment sets before the runtime
initialises, collected from the launch scripts of real TPU training
stacks (olmax / HomebrewNLP style):

  * ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` — silence tcmalloc's
    large-alloc spam for multi-GB parameter buffers;
  * ``TF_CPP_MIN_LOG_LEVEL`` — quiet the XLA/TSL C++ log firehose;
  * ``JAX_DEFAULT_DTYPE_BITS=32`` / ``JAX_ENABLE_X64=0`` — pin the
    default dtype story so a stray python float never upcasts a model
    to f64 on CPU;
  * ``XLA_FLAGS`` — ``--xla_force_host_platform_device_count`` (the
    multi-device CPU test rig), merged WITHOUT clobbering flags the
    operator already exported.  No TPU-only flag goes here: jaxlib
    parses ``XLA_FLAGS`` against its own flag list on every platform and
    aborts on one it does not know (``--xla_step_marker_location``);
    TPU compiler flags belong in ``LIBTPU_INIT_ARGS``;
  * the persistent compile cache — ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else one fixed directory in the checkout.

Two launch-time facts cannot be fixed from inside the process and are
handled by ``launch/run.sh`` instead:

  * ``LD_PRELOAD`` of tcmalloc — the dynamic linker reads it at exec
    time, before the interpreter exists;
  * everything here must run before jax first touches the backend —
    ``configure()`` is called at the top of serve/train ``main()``,
    before any jax API, and uses ``setdefault`` semantics so the shell
    wrapper (or operator) always wins.
"""
from __future__ import annotations

import logging
import os

log = logging.getLogger("repro.launch.env")

# tcmalloc reports every allocation above ~1 GB by default; model params
# trip it constantly.  60 GB = effectively silent (olmax's value).
TCMALLOC_THRESHOLD = "60000000000"

_MERGE_FLAGS = "XLA_FLAGS"

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The persistent compile cache's home when the environment names none:
# one fixed directory inside the checkout (git-ignored).  The path is
# part of what a cache entry is found by, so it never depends on a temp
# name, a pid or the time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    in-checkout :data:`DEFAULT_COMPILE_CACHE`."""
    return os.environ.get(COMPILE_CACHE_ENV) or DEFAULT_COMPILE_CACHE


def _merge_xla_flags(flags: list[str]) -> str:
    """Append flags to $XLA_FLAGS, skipping any --flag the operator (or a
    prior configure call) already set — their value wins, not ours."""
    existing = os.environ.get(_MERGE_FLAGS, "")
    present = {f.split("=")[0] for f in existing.split() if f}
    added = [f for f in flags if f.split("=")[0] not in present]
    merged = " ".join(x for x in [existing.strip(), *added] if x)
    if merged:
        os.environ[_MERGE_FLAGS] = merged
    return merged


def configure(
    *,
    host_devices: int | None = None,
    dtype_bits: int = 32,
    quiet: bool = True,
    extra_xla_flags: tuple[str, ...] = (),
) -> dict:
    """Apply launch hygiene to ``os.environ``; returns what was resolved.

    Must run before jax initialises its backend (XLA_FLAGS and the dtype
    pins are read at first touch), and points jax's persistent compile
    cache at :func:`compile_cache_dir`.  Everything uses setdefault
    semantics: an operator's explicit export always wins.
    ``host_devices`` forces N CPU host devices (the multi-device test
    rig — serve.py's old inline flag append, now merged properly so
    repeated calls don't stack duplicates).
    """
    if quiet:
        os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    os.environ.setdefault(
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", TCMALLOC_THRESHOLD)
    os.environ.setdefault("JAX_DEFAULT_DTYPE_BITS", str(int(dtype_bits)))
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    flags = []
    if host_devices:
        flags.append(
            f"--xla_force_host_platform_device_count={int(host_devices)}")
    flags.extend(extra_xla_flags)
    merged = _merge_xla_flags(flags)

    # jax reads $JAX_COMPILATION_CACHE_DIR itself when it is set; this
    # sets the same directory, or the fixed default, and never another
    import jax

    cache_dir = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", cache_dir)

    if "libtcmalloc" not in os.environ.get("LD_PRELOAD", ""):
        # can't be retrofitted here — the linker read LD_PRELOAD at exec.
        log.debug("tcmalloc not preloaded; use launch/run.sh to LD_PRELOAD "
                  "it (glibc malloc fragments multi-GB arena workloads)")

    return {
        "xla_flags": merged,
        "tf_cpp_min_log_level": os.environ.get("TF_CPP_MIN_LOG_LEVEL"),
        "tcmalloc_threshold":
            os.environ.get("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"),
        "jax_default_dtype_bits": os.environ.get("JAX_DEFAULT_DTYPE_BITS"),
        "jax_enable_x64": os.environ.get("JAX_ENABLE_X64"),
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "compile_cache_dir": cache_dir,
    }


def describe() -> dict:
    """The resolved launch environment, for logs and bench artifacts —
    includes the pallas interpret mode actually in effect."""
    from repro.kernels.ops import interpret_mode, interpret_mode_source

    interp = interpret_mode()
    interp_src = interpret_mode_source()
    return {
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "tcmalloc_preloaded":
            "libtcmalloc" in os.environ.get("LD_PRELOAD", ""),
        "jax_default_dtype_bits": os.environ.get("JAX_DEFAULT_DTYPE_BITS"),
        "jax_enable_x64": os.environ.get("JAX_ENABLE_X64"),
        "pallas_interpret": interp,
        "pallas_interpret_source": interp_src,
    }
