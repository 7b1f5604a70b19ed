"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes per the deliverable-(c) requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.multi_count import multi_count
from repro.kernels.runahead_threshold import runahead_topk_threshold
from repro.kernels.taylor_eval import taylor_sincos_eval


@pytest.mark.parametrize("B", [1, 3, 8])
@pytest.mark.parametrize("V", [100, 2048, 5000, 151_936 // 8])
@pytest.mark.parametrize("M", [1, 15, 31])
def test_multi_count_shapes(B, V, M):
    rng = np.random.default_rng(B * V + M)
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32))
    taus = jnp.asarray(rng.normal(size=(B, M)).astype(np.float32))
    got = multi_count(logits, taus, interpret=True)
    want = ref.multi_count_ref(logits, taus)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multi_count_dtypes(dtype):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 1000))).astype(dtype)
    taus = jnp.asarray(rng.normal(size=(2, 7))).astype(dtype)
    got = multi_count(logits.astype(jnp.float32), taus.astype(jnp.float32),
                      interpret=True)
    want = ref.multi_count_ref(logits.astype(jnp.float32),
                               taus.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("V", [100, 2048, 5000])
@pytest.mark.parametrize("M", [1, 15, 31])
def test_multi_mass_shapes(B, V, M):
    from repro.kernels.multi_mass import multi_mass

    rng = np.random.default_rng(B * V + M + 1)
    probs = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(B, V)).astype(np.float32)) * 2, axis=-1
    )
    taus = jnp.asarray(
        rng.uniform(0, 2.0 / V, size=(B, M)).astype(np.float32)
    )
    got = multi_mass(probs, taus, interpret=True)
    want = ref.multi_mass_ref(probs, taus)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("V", [100, 2048, 5000])
@pytest.mark.parametrize("M", [1, 15, 31])
def test_multi_entropy_shapes(B, V, M):
    from repro.kernels.multi_entropy import multi_entropy

    rng = np.random.default_rng(B * V + M + 2)
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32)) * 3
    ts = jnp.asarray(
        rng.uniform(0.05, 20.0, size=(B, M)).astype(np.float32)
    )
    got = multi_entropy(logits, ts, interpret=True)
    want = ref.multi_entropy_ref(logits, ts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_multi_entropy_extreme_logit_range():
    """Padded/clamped logits (-80 below max) must not produce NaN/inf."""
    from repro.kernels.multi_entropy import multi_entropy

    z = jnp.asarray([[0.0, -80.0, 5.0, -80.0] * 64], jnp.float32)
    ts = jnp.asarray([[0.05, 1.0, 20.0]], jnp.float32)
    got = multi_entropy(z, ts, interpret=True)
    want = ref.multi_entropy_ref(z, ts)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("V,k", [(1000, 5), (5000, 50), (18992, 64)])
@pytest.mark.parametrize("spec_k", [3, 5, 8])
def test_fused_runahead_matches_unfused(V, k, spec_k):
    rng = np.random.default_rng(V + k)
    logits = jnp.asarray(rng.normal(size=(3, V)).astype(np.float32))
    lo_k, hi_k = runahead_topk_threshold(
        logits, k_target=k, rounds=8, spec_k=spec_k, interpret=True
    )
    lo_r, hi_r = ref.runahead_topk_threshold_ref(
        logits, k_target=k, rounds=8, spec_k=spec_k
    )
    # bit-exact: both run the identical speculative walk
    np.testing.assert_array_equal(np.asarray(lo_k), np.asarray(lo_r))
    np.testing.assert_array_equal(np.asarray(hi_k), np.asarray(hi_r))


@pytest.mark.parametrize("V,k", [(1000, 5), (5000, 50)])
def test_fused_runahead_exact_topk(V, k):
    rng = np.random.default_rng(V)
    logits = jnp.asarray(rng.normal(size=(4, V)).astype(np.float32))
    lo, hi = runahead_topk_threshold(
        logits, k_target=k, rounds=10, spec_k=5, interpret=True
    )
    counts = (np.asarray(logits) > np.asarray(hi)[:, None]).sum(-1)
    np.testing.assert_array_equal(counts, k)


@pytest.mark.parametrize("terms", [2, 10, 100])
@pytest.mark.parametrize("m", [1, 31, 127, 130])
def test_taylor_eval(terms, m):
    rng = np.random.default_rng(terms * m)
    x = jnp.asarray(rng.uniform(1.0, 2.0, size=m).astype(np.float32))
    got = taylor_sincos_eval(x, terms=terms, interpret=True)
    want = ref.taylor_sincos_ref(x, terms=terms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_taylor_converges_to_true_sincos():
    x = jnp.asarray(np.linspace(1.0, 2.0, 64, dtype=np.float32))
    got = taylor_sincos_eval(x, terms=20, interpret=True)
    want = np.sin(np.cos(np.asarray(x)))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_ops_wrappers_dispatch_interpret_on_cpu():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 512)).astype(np.float32))
    taus = jnp.asarray(rng.normal(size=(2, 3)).astype(np.float32))
    got = ops.multi_count(logits, taus)
    want = ref.multi_count_ref(logits, taus)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("S,window", [(512, 0), (1024, 0), (512, 128)])
def test_flash_fwd_pallas_matches_jnp(S, window):
    from repro.kernels.flash_fwd import flash_fwd
    from repro.models.attention import flash_attend

    B, H, D = 2, 3, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    got = flash_fwd(q, k, v, 128, 128, window, True)
    want = flash_attend(q, k, v, causal=True, window=window,
                        q_chunk=128, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_fwd_pallas_grads():
    from repro.kernels.flash_fwd import flash_fwd
    from repro.models.attention import flash_attend

    B, S, H, D = 1, 256, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)

    g1 = jax.grad(lambda q_: jnp.sum(flash_fwd(q_, k, v, 128, 128, 0, True)
                                     ** 2))(q)
    g2 = jax.grad(lambda q_: jnp.sum(flash_attend(q_, k, v, causal=True,
                                                  q_chunk=128,
                                                  kv_chunk=128) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-4,
                               rtol=1e-3)
