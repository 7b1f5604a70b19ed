"""Tuner correctness (DESIGN.md §11).

Two layers:

  * decision procedure — cache roundtrip (persist -> reload -> same
    decision), stale-schema cache ignored wholesale, disabled/override
    semantics, and the analytic model reproducing the BENCH_scaling
    verdict (vocab-sharding loses to single-device on CPU at B=8,
    V=8192, D=8);
  * engine integration — tuned ``solve_kind`` stays BIT-identical to the
    scalar serial sign-bit walk for every registered (kind, backend)
    pair and for every forced decomposition of the same serial-step
    budget (the tuner only re-chooses HOW the budget is spent, never how
    much is spent — reusing the property harness's serial reference).
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_solver_properties import PAIRS, _serial_bracket

from repro.core import solver, tuning


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    # This module compiles dozens of (kind, backend, decomposition)
    # variants on top of everything the rest of the suite already jitted;
    # on the CPU backend that combined executable load deterministically
    # segfaults XLA's compiler mid-suite (fine in isolation).  Shedding
    # the suite's accumulated executables first keeps the full run stable.
    jax.clear_caches()
    yield


def _operand_and_params(kind: str, seed: int, B: int, V: int):
    """Mirror the property harness's randomisation, but return the raw
    (operand, params) that drive solve_kind."""
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32) * 2.0)
    if kind == "count_above":
        return z, dict(k=int(rng.integers(1, V)))
    if kind == "count_below":
        return z, dict(q=float(rng.uniform(0.05, 0.95)))
    if kind == "mass_at_or_above":
        probs = jnp.asarray(np.exp(z) / np.exp(z).sum(-1, keepdims=True))
        return probs, dict(p=float(rng.uniform(0.1, 0.9)))
    if kind == "entropy_at_temperature":
        return z, dict(target=float(rng.uniform(0.5, 0.9 * math.log(V))))
    raise AssertionError(f"unhandled kind {kind!r} — extend the harness")


# ---------------------------------------------------------------------------
# engine integration: tuned solves stay bit-exact vs serial
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,backend", PAIRS)
def test_tuned_solve_bit_exact_vs_serial(kind, backend):
    """Default tuning (analytic tier) — whatever the tuner picks must
    reproduce the serial walk bit-for-bit."""
    z, params = _operand_and_params(kind, seed=5, B=3, V=50)
    rounds, spec_k = 4, 3
    ref = _serial_bracket(
        solver.problem(kind, z, backend=backend, **params),
        rounds * spec_k)
    lo, hi = solver.solve_kind(kind, z, backend=backend,
                               rounds=rounds, spec_k=spec_k, **params)
    np.testing.assert_array_equal(np.asarray(lo), ref[0])
    np.testing.assert_array_equal(np.asarray(hi), ref[1])


@pytest.mark.parametrize("kind,backend", PAIRS)
@pytest.mark.parametrize("forced_k", [1, 2, 5])
def test_every_forced_decomposition_bit_exact(kind, backend, forced_k):
    """tuning.override(spec_k=...) sweeps decompositions of the SAME
    12-step budget — including the non-divisible spec_k=5 (partial last
    round).  All must land on the serial walk's brackets."""
    z, params = _operand_and_params(kind, seed=11, B=2, V=41)
    ref = _serial_bracket(
        solver.problem(kind, z, backend=backend, **params), 12)
    with tuning.override(spec_k=forced_k):
        lo, hi = solver.solve_kind(kind, z, backend=backend,
                                   rounds=4, spec_k=3, **params)
    np.testing.assert_array_equal(np.asarray(lo), ref[0],
                                  err_msg=f"{kind}/{backend} k={forced_k}")
    np.testing.assert_array_equal(np.asarray(hi), ref[1],
                                  err_msg=f"{kind}/{backend} k={forced_k}")


def test_auto_backend_preference_is_free_choice():
    """backend='auto' lets the tuner choose among registered backends —
    and the result is still the serial walk's."""
    z, params = _operand_and_params("count_above", seed=3, B=2, V=32)
    ref = _serial_bracket(
        solver.problem("count_above", z, backend="jnp", **params), 12)
    lo, hi = solver.solve_kind("count_above", z, backend="auto",
                               rounds=4, spec_k=3, **params)
    np.testing.assert_array_equal(np.asarray(lo), ref[0])
    np.testing.assert_array_equal(np.asarray(hi), ref[1])


def test_disabled_pins_fixed_configuration():
    with tuning.disabled():
        z, params = _operand_and_params("count_above", seed=9, B=2, V=32)
        solver.solve_kind("count_above", z, rounds=4, spec_k=3, **params)
        key, d = tuning.explain()[-1]
    assert d.source == "fixed"
    assert (d.rounds, d.spec_k) == (4, 3)


# ---------------------------------------------------------------------------
# the decision procedure (no engine needed)
# ---------------------------------------------------------------------------

def _key(**kw):
    base = dict(kind="count_above", batch=8, vocab=8192, dtype="float32",
                backend_pref="jnp", device_count=8, device_kind="cpu",
                iterations=24)
    base.update(kw)
    return tuning.ConfigKey(**base)


OPTIONS = {"single": (1, 1), "vocab": (8, 1)}


def _measure_fastest(spec_k: int, placement: str):
    """A measure callback scoring one (spec_k, placement) fastest."""
    def measure(cands):
        return [{"seconds": (1e-4 if (d.spec_k, d.placement)
                             == (spec_k, placement) else 1e-2),
                 "collectives": None} for d in cands]
    return measure


def test_cache_roundtrip_and_stale_schema(tmp_path):
    path = str(tmp_path / "cache.json")
    fixed = tuning.Decision(spec_k=4, rounds=6, placement="vocab",
                            backend="jnp", source="fixed")

    # score the legacy vocab/k4 config (always in the measured candidate
    # set) fastest: the measured winner must be exactly that
    t1 = tuning.Tuner(path)
    with tuning.autotune():
        d1 = t1.decide(_key(), options=OPTIONS, backends=("jnp",),
                       fixed=fixed, measure=_measure_fastest(4, "vocab"))
    assert d1.source == "measured"
    assert (d1.spec_k, d1.rounds, d1.placement) == (4, 6, "vocab")

    on_disk = json.load(open(path))
    assert on_disk["schema"] == tuning.SCHEMA_VERSION
    [entry] = on_disk["entries"].values()
    assert entry["decision"]["spec_k"] == 4
    assert "vocab/jnp/k4" in entry["measured_us"]

    # reload in a FRESH tuner: same decision, served from the cache,
    # no measure callback consulted
    t2 = tuning.Tuner(path)
    d2 = t2.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed,
                   measure=lambda c: pytest.fail("cache hit must not measure"))
    assert d2.source == "cache"
    assert (d2.spec_k, d2.rounds, d2.placement, d2.backend) == \
        (4, 6, "vocab", "jnp")

    # stale schema: poison the file with a wrong version — ignored
    # wholesale, the tuner falls back to the analytic model.  (v3 — one
    # back — is the deliberate exception: solver entries kept their
    # shape across the v4 kernel-section addition, so it must REPLAY;
    # pinned separately in test_v3_cache_solver_entries_replay.)
    poisoned = dict(on_disk, schema=tuning.SCHEMA_VERSION - 2)
    with open(path, "w") as f:
        json.dump(poisoned, f)
    t3 = tuning.Tuner(path)
    d3 = t3.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed)
    assert d3.source == "model"


def test_cached_placement_must_stay_legal(tmp_path):
    """A cached vocab-sharded winner is NOT replayed on a mesh that can't
    vocab-shard (e.g. the same config later solved without a policy)."""
    path = str(tmp_path / "cache.json")
    fixed = tuning.Decision(spec_k=4, rounds=6, placement="vocab",
                            backend="jnp", source="fixed")
    t1 = tuning.Tuner(path)
    with tuning.autotune():
        t1.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed,
                  measure=_measure_fastest(4, "vocab"))
    t2 = tuning.Tuner(path)
    d = t2.decide(_key(), options={"single": (1, 1)}, backends=("jnp",),
                  fixed=tuning.Decision(spec_k=4, rounds=6,
                                        placement="single", backend="jnp"))
    assert d.placement == "single"
    assert d.source == "model"


def test_measured_tier_includes_single_device_baseline(tmp_path):
    """The never-worse-than-single guarantee: the single-device fallback
    is always in the measured candidate set, so when it wins the timing
    it wins the decision."""
    seen = []

    def measure(cands):
        seen.extend(cands)
        return [{"seconds": 1e-4 if d.placement == "single" else 1e-2,
                 "collectives": None} for d in cands]

    t = tuning.Tuner(str(tmp_path / "cache.json"))
    with tuning.autotune():
        d = t.decide(_key(), options=OPTIONS, backends=("jnp",),
                     fixed=tuning.Decision(spec_k=4, rounds=6,
                                           placement="vocab",
                                           backend="jnp"),
                     measure=measure)
    assert any(c.placement == "single" for c in seen)
    assert d.placement == "single"
    assert d.source == "measured"


def test_override_forces_fields_and_recomputes_rounds(tmp_path):
    fixed = tuning.Decision(spec_k=4, rounds=6, placement="single",
                            backend="jnp")
    t = tuning.Tuner(str(tmp_path / "cache.json"))
    with tuning.override(spec_k=5, placement="single"):
        d = t.decide(_key(), options=OPTIONS, backends=("jnp",),
                     fixed=fixed)
    assert d.source == "override"
    assert d.spec_k == 5
    assert d.rounds == -(-24 // 5)
    assert d.placement == "single"
    with pytest.raises(ValueError):
        with tuning.override(placement="nonsense"):
            pass


def test_analytic_model_prefers_single_on_cpu_scaling_shape():
    """The model must reproduce the BENCH_scaling verdict: at B=8,
    V=8192 on 8 forced host devices the per-round psum join dwarfs the
    shard-compute saving, so single-device wins."""
    ranked = tuning._candidates(_key(), OPTIONS, ("jnp",))
    assert ranked[0][1].placement == "single"
    # and every vocab-sharded candidate is priced strictly worse than its
    # single-device sibling at the same spec_k
    by = {}
    for cost, d in ranked:
        by[(d.spec_k, d.placement)] = cost
    for k in (1, 2, 3, 4):
        assert by[(k, "vocab")] > by[(k, "single")]


def test_budget_always_preserved_by_candidates():
    for _, d in tuning._candidates(_key(iterations=23), OPTIONS, ("jnp",)):
        assert d.rounds * d.spec_k >= 23
        assert (d.rounds - 1) * d.spec_k < 23


# ---------------------------------------------------------------------------
# serving speculation depth (DESIGN.md §12)
# ---------------------------------------------------------------------------


def test_decision_draft_len_roundtrips_and_defaults():
    d = tuning.Decision(spec_k=4, rounds=8, placement="single",
                        backend="jnp", draft_len=5)
    assert tuning.Decision.from_json(d.to_json()) == d
    # pre-§12 cache entries carry no draft_len: default to serial decode
    legacy = dict(d.to_json())
    legacy.pop("draft_len")
    assert tuning.Decision.from_json(legacy).draft_len == 1


def test_decide_draft_len_zero_acceptance_is_serial():
    # a=0 prices every draft as rejected work: E(L) = 1 for all L, so any
    # L > 1 only adds cost and the decision must stay serial
    assert tuning.decide_draft_len(acceptance=0.0, overhead=5.0) == 1


def test_decide_draft_len_monotone_in_acceptance():
    ls = [tuning.decide_draft_len(acceptance=a, overhead=5.0)
          for a in (0.0, 0.3, 0.6, 0.9, 0.99)]
    assert ls == sorted(ls), ls
    assert ls[-1] > 1


def test_decide_draft_len_overhead_deepens_drafts():
    # dispatch-dominated steps (CPU interpret mode) amortise better over
    # deep drafts; free dispatch shifts the optimum back toward serial
    cheap = tuning.decide_draft_len(acceptance=0.6, overhead=0.0)
    costly = tuning.decide_draft_len(acceptance=0.6, overhead=20.0)
    assert costly >= cheap
    assert costly > 1


def test_decide_draft_len_respects_cap_and_validates():
    assert tuning.decide_draft_len(acceptance=0.99, overhead=50.0,
                                   max_draft_len=3) <= 3
    with pytest.raises(ValueError):
        tuning.decide_draft_len(acceptance=1.5)
    with pytest.raises(ValueError):
        tuning.decide_draft_len(acceptance=0.5, max_draft_len=0)


# ---------------------------------------------------------------------------
# fused serving horizon (DESIGN.md §14)
# ---------------------------------------------------------------------------


def test_decision_step_horizon_roundtrips_and_defaults():
    d = tuning.Decision(spec_k=4, rounds=8, placement="single",
                        backend="jnp", step_horizon=6)
    assert tuning.Decision.from_json(d.to_json()) == d
    # pre-§14 cache entries carry no step_horizon: default to per-step
    legacy = dict(d.to_json())
    legacy.pop("step_horizon")
    assert tuning.Decision.from_json(legacy).step_horizon == 1


def test_config_key_carries_step_horizon():
    assert "hz=0" in _key().cache_key()
    assert "hz=8" in _key(step_horizon=8).cache_key()
    assert _key().cache_key() != _key(step_horizon=8).cache_key()


def test_cached_insane_budget_knobs_not_replayed(tmp_path):
    """A corrupted cache entry (step_horizon 0) must fall through to the
    analytic model instead of steering the solver."""
    path = str(tmp_path / "cache.json")
    fixed = tuning.Decision(spec_k=4, rounds=6, placement="vocab",
                            backend="jnp", source="fixed")
    t1 = tuning.Tuner(path)
    with tuning.autotune():
        t1.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed,
                  measure=_measure_fastest(4, "vocab"))
    import json
    with open(path) as f:
        data = json.load(f)
    entry = next(iter(data["entries"].values()))
    entry["decision"]["step_horizon"] = 0
    with open(path, "w") as f:
        json.dump(data, f)
    t2 = tuning.Tuner(path)
    d = t2.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed)
    assert d.source == "model"


def test_decide_step_horizon_nothing_to_amortize_is_per_step():
    assert tuning.decide_step_horizon(mean_remaining=32.0,
                                      overhead=0.0) == 1


def test_decide_step_horizon_idle_slots_make_fusion_free():
    assert tuning.decide_step_horizon(mean_remaining=4.0, load=0.0,
                                      max_horizon=16) == 16


def test_decide_step_horizon_grows_with_budget_and_overhead():
    ks = [tuning.decide_step_horizon(mean_remaining=m)
          for m in (1.0, 8.0, 32.0, 128.0)]
    assert ks == sorted(ks), ks
    assert ks[-1] > ks[0] > 0
    cheap = tuning.decide_step_horizon(mean_remaining=32.0, overhead=1.0)
    costly = tuning.decide_step_horizon(mean_remaining=32.0, overhead=20.0)
    assert costly >= cheap > 1


def test_decide_step_horizon_respects_cap_and_validates():
    assert tuning.decide_step_horizon(mean_remaining=1000.0,
                                      max_horizon=8) <= 8
    with pytest.raises(ValueError):
        tuning.decide_step_horizon(mean_remaining=0.5)
    with pytest.raises(ValueError):
        tuning.decide_step_horizon(mean_remaining=8.0, max_horizon=0)
    with pytest.raises(ValueError):
        tuning.decide_step_horizon(mean_remaining=8.0, load=1.5)


# ---------------------------------------------------------------------------
# the kernel-geometry tier (DESIGN.md §15)
# ---------------------------------------------------------------------------

def _kkey(**kw):
    base = dict(kernel="multi_count", shape=(8, 8192, 15), dtype="float32",
                device_kind="cpu", interpret=True)
    base.update(kw)
    return tuning.KernelKey(**base)


_KFIXED = {"block_v": 2048}


def test_kernel_decision_roundtrips_and_label():
    d = tuning.KernelDecision.make({"kv_chunk": 256, "q_chunk": 128},
                                   source="measured")
    assert d.params == {"q_chunk": 128, "kv_chunk": 256}
    assert d.label() == "kv_chunk=256,q_chunk=128"
    assert tuning.KernelDecision.from_json(d.to_json()).block == d.block


def test_kernel_disabled_pins_fixed_geometry():
    t = tuning.Tuner(None)
    with tuning.disabled():
        d = t.decide_kernel(_kkey(), fixed=_KFIXED,
                            measure=lambda c: pytest.fail(
                                "disabled must not measure"))
    assert d.source == "fixed"
    assert d.params == _KFIXED


def test_kernel_analytic_interpret_pins_legacy_defaults():
    """The interpreter's cost surface is host-cache dominated (bigger
    blocks LOSE); the analytic tier must pin the legacy geometry and
    leave interpret-mode wins to the measured tier."""
    best = tuning.kernel_candidates(_kkey())[0][1]
    assert best.params == {"block_v": 2048}
    best = tuning.kernel_candidates(
        _kkey(kernel="paged_attend", shape=(4, 2, 8, 8, 2, 2, 16)))[0][1]
    assert best.params == {"pages_per_step": 1}
    best = tuning.kernel_candidates(
        _kkey(kernel="flash_fwd", shape=(1, 2048, 16, 128)))[0][1]
    assert best.params == {"q_chunk": 512, "kv_chunk": 1024}


def test_kernel_analytic_compiled_roofline_scales_blocks():
    """Compiled on TPU the step tax rewards bigger blocks — up to the
    VMEM-fit filter: at M=15 (m_pad 128) the broadcast compare tile puts
    16384 past the half-VMEM budget, so 8192 is the ceiling."""
    ranked = tuning.kernel_candidates(
        _kkey(shape=(8, 152064, 15), device_kind="tpu", interpret=False))
    blocks_seen = {d.params["block_v"] for _, d in ranked}
    assert 16384 not in blocks_seen          # VMEM-filtered
    assert ranked[0][1].params == {"block_v": 8192}


def test_kernel_unknown_family_keeps_fixed():
    t = tuning.Tuner(None)
    d = t.decide_kernel(_kkey(kernel="no_such_kernel", shape=(4,)),
                        fixed={"block_v": 64})
    assert d.source == "model"
    assert d.params == {"block_v": 64}


def test_kernel_cache_roundtrip_and_stale_schema(tmp_path):
    path = str(tmp_path / "cache.json")

    # measured tier: score the SECOND-ranked candidate fastest — the
    # winner must be exactly that geometry, persisted under "kernels"
    seen = []

    def measure(cands):
        seen.append([dict(c) for c in cands])
        return [1e-4 if i == 1 else 1e-2 for i in range(len(cands))]

    t1 = tuning.Tuner(path)
    with tuning.autotune():
        d1 = t1.decide_kernel(_kkey(), fixed=_KFIXED, measure=measure)
    assert d1.source == "measured"
    assert len(seen) == 1 and len(seen[0]) >= 2
    assert d1.params == seen[0][1]

    on_disk = json.load(open(path))
    assert on_disk["schema"] == tuning.SCHEMA_VERSION
    [(ck, entry)] = on_disk["kernels"].items()
    assert ck == _kkey().cache_key()
    assert entry["decision"]["block"] == d1.params
    assert d1.label() in entry["measured_us"]

    # fresh tuner: replayed from the cache, measure never consulted even
    # with autotune active (the cache hit precedes the measured tier)
    t2 = tuning.Tuner(path)
    with tuning.autotune():
        d2 = t2.decide_kernel(
            _kkey(), fixed=_KFIXED,
            measure=lambda c: pytest.fail("cache hit must not measure"))
    assert d2.source == "cache"
    assert d2.params == d1.params

    # a DIFFERENT key (compiled vs interpret) must not hit that entry
    d3 = t2.decide_kernel(_kkey(interpret=False), fixed=_KFIXED)
    assert d3.source == "model"

    # stale schema: ignored wholesale, back to the analytic model
    poisoned = dict(on_disk, schema=tuning.SCHEMA_VERSION - 2)
    with open(path, "w") as f:
        json.dump(poisoned, f)
    t4 = tuning.Tuner(path)
    d4 = t4.decide_kernel(_kkey(), fixed=_KFIXED)
    assert d4.source == "model"


def test_v3_cache_solver_entries_replay_kernels_do_not(tmp_path):
    """The deliberate v3 compatibility: solver entries kept their shape
    across the v4 kernel-section addition, so a v3 file's entries still
    replay — but any kernel section it carries is ignored (that shape
    only exists at v4), leaving kernel decisions to the analytic tier."""
    path = str(tmp_path / "cache.json")
    fixed = tuning.Decision(spec_k=4, rounds=6, placement="vocab",
                            backend="jnp", source="fixed")
    t1 = tuning.Tuner(path)
    with tuning.autotune():
        t1.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed,
                  measure=_measure_fastest(4, "vocab"))
        t1.decide_kernel(_kkey(), fixed=_KFIXED,
                         measure=lambda c: [1e-4] * len(c))

    on_disk = json.load(open(path))
    assert on_disk["entries"] and on_disk["kernels"]
    with open(path, "w") as f:
        json.dump(dict(on_disk, schema=3), f)

    t2 = tuning.Tuner(path)
    ds = t2.decide(_key(), options=OPTIONS, backends=("jnp",), fixed=fixed,
                   measure=lambda c: pytest.fail("v3 entries must replay"))
    assert ds.source == "cache"
    dk = t2.decide_kernel(_kkey(), fixed=_KFIXED)
    assert dk.source == "model"


@pytest.mark.parametrize("bad_block", [
    {"block_v": 0},                          # insane value
    {"blocks_v": 2048},                      # wrong param name
    {"block_v": 2048, "q_chunk": 128},       # extra param
    {},                                      # empty
])
def test_kernel_corrupted_entry_not_replayed(tmp_path, bad_block):
    """A hand-edited or corrupted kernel entry must never steer a
    launch: params must match the kernel's own argnames exactly, all
    values sane — anything else falls back to the analytic model."""
    path = str(tmp_path / "cache.json")
    blob = {"schema": tuning.SCHEMA_VERSION, "entries": {},
            "kernels": {_kkey().cache_key(): {
                "decision": {"block": bad_block, "source": "measured"}}}}
    with open(path, "w") as f:
        json.dump(blob, f)
    t = tuning.Tuner(path)
    d = t.decide_kernel(_kkey(), fixed=_KFIXED)
    assert d.source == "model"
    assert d.params == _KFIXED or set(d.params) == set(_KFIXED)


def test_kernel_measured_failures_fall_back(tmp_path):
    """All-NaN measurements (every candidate crashed) must not persist a
    winner, and must not fall back in silence either: the tier raises
    and the cache stays empty."""
    import os

    path = str(tmp_path / "cache.json")
    t = tuning.Tuner(path)
    with tuning.autotune(), pytest.raises(RuntimeError, match="failed"):
        t.decide_kernel(_kkey(), fixed=_KFIXED,
                        measure=lambda c: [float("nan")] * len(c))
    if os.path.exists(path):
        assert _kkey().cache_key() not in \
            json.load(open(path)).get("kernels", {})


def test_solver_measured_failures_raise(tmp_path):
    """The solver tier's dual: no surviving candidate is an error."""
    t = tuning.Tuner(str(tmp_path / "cache.json"))
    with tuning.autotune(), pytest.raises(RuntimeError, match="failed"):
        t.decide(_key(), options=OPTIONS, backends=("jnp",),
                 fixed=tuning.Decision(spec_k=4, rounds=6,
                                       placement="single", backend="jnp"),
                 measure=lambda c: [{"seconds": float("nan"),
                                     "collectives": None}] * len(c))


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,ok", [
    ("tpu", "TPU v5 lite", True),
    ("tpu", "TPU v4", False),        # a TPU the "tpu" profile is not from
    ("gpu", "NVIDIA H100", False),   # a platform with no profile at all
    ("cpu", "cpu", True),
])
def test_device_platform_refuses_unknown_devices(monkeypatch, platform,
                                                 kind, ok):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    if ok:
        assert tuning.device_platform() == (platform, kind)
    else:
        with pytest.raises(RuntimeError, match="no hardware profile"):
            tuning.device_platform()


def test_profile_for_unknown_platform_raises():
    assert tuning.profile_for("tpu") is tuning.PROFILES["tpu"]
    with pytest.raises(RuntimeError, match="no hardware profile"):
        tuning.profile_for("gpu")
