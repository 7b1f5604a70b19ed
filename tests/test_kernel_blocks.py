"""Kernel block-geometry parity (DESIGN.md §15).

The PR-10 contract: block geometry is a PERFORMANCE knob, never a
correctness knob.  Per kernel family:

  * multi_count — integer sums are order-invariant, so every block_v
    must reproduce the default BIT-for-bit;
  * runahead_topk — block_v only sets the resident row's padding
    granularity (lane-masked counts ignore the pad), so bit-identical;
  * paged_attend — the unrolled chain loop folds the SAME per-page
    updates in the same order (trailing fake pages mask to corr=1), so
    every pages_per_step is bit-identical;
  * multi_mass / multi_entropy / flash — float partial sums REGROUP
    across blocks, so the contract is tight allclose, not equality.

Plus unit tests for the shared blocks.py helpers and the
interpret-mode env override in kernels/ops.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import blocks
from repro.kernels import flash_fwd as ff
from repro.kernels import multi_count as mc
from repro.kernels import multi_entropy as me
from repro.kernels import multi_mass as mm
from repro.kernels import ops
from repro.kernels import paged_attend as pa
from repro.kernels import runahead_threshold as rt

INTERP = ops.interpret_mode()


# ---------------------------------------------------------------------------
# blocks.py helpers
# ---------------------------------------------------------------------------

def test_pad_to_and_lane_pad():
    assert blocks.pad_to(0, 128) == 0
    assert blocks.pad_to(1, 128) == 128
    assert blocks.pad_to(128, 128) == 128
    assert blocks.pad_to(129, 128) == 256
    assert blocks.lane_pad(0) == blocks.LANE       # empty axes still tile
    assert blocks.lane_pad(5000) == 5120


def test_clamp_block_v():
    # None -> legacy default, capped at the lane-padded axis
    assert blocks.clamp_block_v(None, 8192) == blocks.DEFAULT_BLOCK_V
    assert blocks.clamp_block_v(None, 100) == 128
    # requests round up to a lane multiple and cap at the padded axis
    assert blocks.clamp_block_v(1, 8192) == 128
    assert blocks.clamp_block_v(200, 8192) == 256
    assert blocks.clamp_block_v(1 << 20, 5000) == 5120


def test_grid_v_covers_axis_exactly():
    for v, b in ((5000, 128), (5000, 2048), (8192, 2048), (1, 128)):
        v_pad, steps = blocks.grid_v(v, b)
        assert v_pad >= v and v_pad % b == 0 and steps == v_pad // b


def test_divisor_chunk_is_a_divisor():
    assert blocks.divisor_chunk(256, 512) == 256     # target > n -> n
    assert blocks.divisor_chunk(2048, 512) == 512
    assert blocks.divisor_chunk(384, 512) == 384
    assert blocks.divisor_chunk(384, 256) == 192     # largest divisor <= 256
    for n, t in ((7, 4), (1000, 512), (96, 64)):
        c = blocks.divisor_chunk(n, t)
        assert n % c == 0 and c <= max(t, 1)


def test_solver_tile_bytes_monotone_and_vmem_filter():
    small = blocks.solver_tile_bytes(256, 15)
    big = blocks.solver_tile_bytes(8192, 15)
    assert big > small
    assert blocks.fits_vmem(small, budget=blocks.VMEM_BYTES // 2)
    assert not blocks.fits_vmem(blocks.VMEM_BYTES, budget=1024)


# ---------------------------------------------------------------------------
# solver-kernel parity across block_v
# ---------------------------------------------------------------------------

def _solver_inputs(B=3, V=5000, M=7, seed=0):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32) * 2.0)
    taus = jnp.asarray(rng.normal(size=(B, M)).astype(np.float32))
    return z, taus


# 4096 > V exercises the degenerate whole-row clamp; 128 the min tile
SWEEP = (128, 512, 2048, 4096)


@pytest.mark.parametrize("block_v", SWEEP)
def test_multi_count_bit_exact_across_blocks(block_v):
    z, taus = _solver_inputs()
    ref = mc.multi_count(z, taus, interpret=INTERP)
    out = mc.multi_count(z, taus, block_v=block_v, interpret=INTERP)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_multi_count_matches_numpy_reference():
    z, taus = _solver_inputs(B=2, V=300, M=5, seed=3)
    zn, tn = np.asarray(z), np.asarray(taus)
    ref = (zn[:, None, :] > tn[:, :, None]).sum(-1).astype(np.float32)
    for b in SWEEP:
        out = mc.multi_count(z, taus, block_v=b, interpret=INTERP)
        np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("block_v", SWEEP)
def test_multi_mass_allclose_across_blocks(block_v):
    z, taus = _solver_inputs(seed=1)
    probs = jnp.asarray(np.exp(np.asarray(z))
                        / np.exp(np.asarray(z)).sum(-1, keepdims=True))
    ref = mm.multi_mass(probs, jnp.abs(taus) * 1e-3, interpret=INTERP)
    out = mm.multi_mass(probs, jnp.abs(taus) * 1e-3, block_v=block_v,
                        interpret=INTERP)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=0)


@pytest.mark.parametrize("block_v", SWEEP)
def test_multi_entropy_allclose_across_blocks(block_v):
    z, _ = _solver_inputs(seed=2)
    B, M = z.shape[0], 7
    ts = jnp.asarray(
        np.linspace(0.3, 2.0, M, dtype=np.float32)[None].repeat(B, 0))
    ref = me.multi_entropy(z, ts, interpret=INTERP)
    out = me.multi_entropy(z, ts, block_v=block_v, interpret=INTERP)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=0)


@pytest.mark.parametrize("block_v", (128, 512))
def test_runahead_topk_bit_identical_across_blocks(block_v):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 5000)).astype(np.float32))
    ref = rt.runahead_topk_threshold(x, k_target=50, rounds=6, spec_k=4,
                                     interpret=INTERP)
    out = rt.runahead_topk_threshold(x, k_target=50, rounds=6, spec_k=4,
                                     block_v=block_v, interpret=INTERP)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# attention-kernel parity
# ---------------------------------------------------------------------------

def _paged_inputs(B=3, P=8, nkv=2, D=16, L=2, R=2, chain=7, seed=11):
    rng = np.random.default_rng(seed)
    n_pages = B * chain + 1
    pool_k = jnp.asarray(
        rng.normal(size=(n_pages, P, nkv, D)).astype(np.float32))
    pool_v = jnp.asarray(
        rng.normal(size=(n_pages, P, nkv, D)).astype(np.float32))
    table = jnp.asarray(rng.permutation(n_pages - 1)[: B * chain]
                        .reshape(B, chain).astype(np.int32))
    ctx = chain * P
    pos = jnp.full((B,), ctx - L, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, nkv * R, D)).astype(np.float32))
    return (pool_k, pool_v, table, pos, q), ctx


# 3 leaves a partial final trip; 8 > chain exercises the clamp
@pytest.mark.parametrize("depth", (2, 3, 8))
def test_paged_attend_bit_identical_across_unroll(depth):
    args, ctx = _paged_inputs()
    ref = pa.paged_attend(*args, context=ctx, pages_per_step=1,
                          interpret=INTERP)
    out = pa.paged_attend(*args, context=ctx, pages_per_step=depth,
                          interpret=INTERP)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("chunks", ((128, 128), (256, 128), (128, 256)))
def test_flash_fwd_allclose_across_chunks(chunks):
    rng = np.random.default_rng(13)
    B, S, H, D = 1, 256, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
               for _ in range(3))
    ref = ff.flash_fwd(q, k, v, S, S, 0, INTERP)       # one whole-row tile
    qc, kc = chunks
    out = ff.flash_fwd(q, k, v, qc, kc, 0, INTERP)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# interpret-mode resolution (kernels/ops.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def _restore_interpret():
    yield
    ops.reset_interpret_mode()      # recompute from the real environment


def test_interpret_env_override(monkeypatch, _restore_interpret):
    monkeypatch.setenv(ops.INTERPRET_ENV, "1")
    ops.reset_interpret_mode()
    assert ops.interpret_mode() is True
    assert ops.interpret_mode_source() == "env"

    monkeypatch.setenv(ops.INTERPRET_ENV, "0")
    ops.reset_interpret_mode()
    assert ops.interpret_mode() is False
    assert ops.interpret_mode_source() == "env"


def test_interpret_refused_on_tpu(monkeypatch, _restore_interpret):
    """On a TPU backend the kernels always compile: the env override
    asking for the interpreter raises instead of hiding the chip."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(ops.INTERPRET_ENV, raising=False)
    ops.reset_interpret_mode()
    assert ops.interpret_mode() is False
    monkeypatch.setenv(ops.INTERPRET_ENV, "1")
    ops.reset_interpret_mode()
    with pytest.raises(RuntimeError, match="TPU"):
        ops.interpret_mode()


def test_interpret_autodetect_and_memo(monkeypatch, _restore_interpret):
    monkeypatch.delenv(ops.INTERPRET_ENV, raising=False)
    ops.reset_interpret_mode()
    assert ops.interpret_mode_source() == "auto"
    first = ops.interpret_mode()
    # memoized: flipping the env WITHOUT a reset must not change it
    monkeypatch.setenv(ops.INTERPRET_ENV, "0" if first else "1")
    assert ops.interpret_mode() is first
    ops.reset_interpret_mode()
    assert ops.interpret_mode() is not first
