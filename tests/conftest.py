import os
import tempfile

import jax
import pytest

# Tests run on the single CPU device (the 512-device dry-run is exercised
# via its own launcher subprocess, never inside pytest — DESIGN.md §5).

# Hermetic tuning cache: without this, a measured winner persisted by an
# earlier benchmark (or test) run in ~/.cache/repro would be replayed
# into every solve_kind in the suite — decisions must come from the
# tests' own state.  Subprocess tests inherit the same path via env.
os.environ.setdefault(
    "REPRO_TUNING_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="repro_test_tuning_"),
                 "tuning.json"),
)


@pytest.fixture(autouse=True, scope="module")
def _bounded_jit_cache():
    """Clear jax's compiled-executable caches after every test module.

    The suite has grown past the point where one pytest process can hold
    every module's jit cache at once: with ~450 tests' executables live,
    XLA CPU segfaulted deterministically inside a later
    compile — dropping any module from the run (or running the crashing
    module alone) makes it pass, so the crash is accumulated native
    state, not any one test's graph.  Per-module clearing caps the live
    executable count at one module's worth; within a module caching is
    untouched (compile-count and cache_info assertions still hold)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
