"""Pallas kernels (interpret=True on CPU) vs pure-jnp ref.py oracles.

Per the brief: sweep shapes/dtypes per kernel and assert_allclose against the
oracle.  Integer paths (exact / trunc) must be bit-exact; the low-rank path
matches the XLA reference within f32 ULPs (FMA contraction differences only).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.approx import gemm as G
from repro.core import multipliers as mm
from repro.core import netlist as nl
from repro.kernels import approx_qgemm as qk
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand_q(shape):
    return RNG.integers(-128, 128, shape).astype(np.int8)


def _lowrank_spec(rank=6, seed=1):
    rng = np.random.default_rng(seed)
    mask = rng.random(len(nl.bw8().prunable_gates())) < 0.03
    m = mm.pruned(mask, name=f"lr_test_{seed}")
    return m, G.from_multiplier(m, rank=rank)


GEMM_SHAPES = [(8, 16, 8), (64, 96, 80), (128, 128, 128), (100, 130, 50),
               (1, 256, 257), (300, 64, 512)]


@pytest.mark.parametrize("shape", GEMM_SHAPES)
@pytest.mark.parametrize("mult", ["exact", "trunc2x2", "trunc3x1"])
def test_qgemm_kernel_bitexact_int_paths(shape, mult):
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    mobj = mm.get_multiplier(mult)
    spec = G.from_multiplier(mobj)
    oracle = np.asarray(ref.lut_matmul(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(mobj.lut)))
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec))
    np.testing.assert_array_equal(got, oracle.astype(np.float32))


@pytest.mark.parametrize("shape", [(32, 48, 40), (128, 128, 128),
                                   (65, 130, 33)])
def test_qgemm_kernel_lowrank_matches_xla_reference(shape):
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    _, spec = _lowrank_spec()
    want = np.asarray(ref.ref_approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                           spec))
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1.0)


def test_qgemm_lowrank_tracks_lut_oracle_within_residual():
    """The low-rank path approximates the LUT semantic within the residual
    NMED recorded on the spec (mean-level bound, exercised at K=128)."""
    mobj, spec = _lowrank_spec(rank=8, seed=3)
    k = 128
    a, b = _rand_q((64, k)), _rand_q((k, 64))
    oracle = np.asarray(ref.lut_matmul(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(mobj.lut))).astype(np.float64)
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                      spec)).astype(np.float64)
    mean_err = np.abs(got - oracle).mean() / k
    # mean per-product error must be of the order of the recorded residual
    assert mean_err <= 16384 * (spec.residual_nmed * 8 + 1e-6), (
        mean_err, spec.residual_nmed)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,d", [(2, 128, 64), (4, 256, 128), (1, 64, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(bh, s, d, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((bh, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((bh, s, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((bh, s, d)), dtype)
    want = np.asarray(ref.ref_attention(q, k, v, causal=causal),
                      dtype=np.float32)
    got = np.asarray(ops.flash_attention(q, k, v, causal=causal,
                                         bq=64, bkv=64), dtype=np.float32)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 3)


def test_flash_attention_cross_blocks():
    """Block sizes must not change the result."""
    q = jnp.asarray(RNG.standard_normal((2, 256, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 256, 64)), jnp.float32)
    o1 = np.asarray(ops.flash_attention(q, k, v, bq=64, bkv=128))
    o2 = np.asarray(ops.flash_attention(q, k, v, bq=256, bkv=32))
    np.testing.assert_allclose(o1, o2, rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("m,k", [(8, 16), (100, 300), (256, 1024), (3, 7)])
def test_quantize_rows_kernel(m, k):
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    q1, s1 = ops.quantize_rows(x)
    q2, s2 = ref.ref_quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-7)


FUSED_PARITY_SHAPES = [(64, 96, 80), (128, 128, 128), (100, 130, 50),
                       (1, 256, 257), (33, 257, 65)]


@pytest.mark.parametrize("rank", [1, 2, 4, 8])
@pytest.mark.parametrize("shape", FUSED_PARITY_SHAPES)
def test_fused_matches_stacked_bitexact_lowrank(rank, shape):
    """The in-kernel table map must reproduce the pre-mapped stacked path
    bit-for-bit at every rank and at non-block-multiple shapes (K-tail
    masking of the mapped planes)."""
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    _, spec = _lowrank_spec(rank=rank, seed=rank)
    fused = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                        spec))
    stacked = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                          spec, fused=False))
    np.testing.assert_array_equal(fused, stacked)


@pytest.mark.parametrize("mult", ["exact", "trunc2x2", "trunc3x1"])
@pytest.mark.parametrize("shape", [(64, 96, 80), (100, 130, 50),
                                   (1, 256, 257)])
def test_fused_matches_stacked_and_xla_bitexact_int_paths(mult, shape):
    """Exact/trunc: fused == stacked == XLA reference, bit-for-bit (the
    trunc mask moves into the kernel)."""
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    spec = G.from_multiplier(mm.get_multiplier(mult))
    fused = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                        spec))
    stacked = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                          spec, fused=False))
    xla = np.asarray(ref.ref_approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                          spec))
    np.testing.assert_array_equal(fused, stacked)
    np.testing.assert_array_equal(fused, xla)


@pytest.mark.parametrize("rank", [1, 2, 4, 8])
def test_fused_lowrank_tracks_lut_oracle_within_residual(rank):
    """Fused path approximates the LUT semantic within the residual NMED
    recorded on the spec, at every rank (same bound as the stacked test)."""
    mobj, spec = _lowrank_spec(rank=rank, seed=3)
    k = 130  # non-block-multiple: exercises the in-kernel K-tail mask
    a, b = _rand_q((64, k)), _rand_q((k, 64))
    oracle = np.asarray(ref.lut_matmul(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(mobj.lut))
                        ).astype(np.float64)
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                      spec)).astype(np.float64)
    mean_err = np.abs(got - oracle).mean() / k
    assert mean_err <= 16384 * (spec.residual_nmed * 8 + 1e-6), (
        mean_err, spec.residual_nmed)


def test_fused_kernel_masks_fully_padded_k_block():
    """k_valid < K with k_valid % bk == 0 (an entire padded K block) must
    still be masked in the mapped planes — pad zeros map to tbl[0] != 0."""
    _, spec = _lowrank_spec(rank=2, seed=9)
    m = n = k_valid = 128
    a, b = _rand_q((m, k_valid)), _rand_q((k_valid, n))
    ap = np.zeros((m, 256), np.int8)
    ap[:, :k_valid] = a
    bp = np.zeros((256, n), np.int8)
    bp[:k_valid] = b
    scales = jnp.concatenate([jnp.ones((1,), jnp.float32),
                              -spec.s_r])[:, None]
    got = qk.approx_qgemm_fused(
        jnp.asarray(ap), jnp.asarray(bp), spec.fu_q, spec.fv_q, scales,
        k_valid=k_valid, bm=128, bk=128, bn=128, interpret=True)
    want = ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec,
                            fused=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("trunc", [0, 2, 4])
def test_quantize_rows_trunc_prologue(trunc):
    """Fused quantize+mask == mask-after-quantize bit-for-bit (same kernel
    both sides, so the comparison is exact and order-independent); scales
    are untouched by the mask and track the reference quantizer."""
    x = jnp.asarray(RNG.standard_normal((24, 96)), jnp.float32)
    q1, s1 = ops.quantize_rows(x, trunc=trunc)
    q0, s0 = ops.quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(q1),
                                  np.asarray(G._trunc_mask(q0, trunc)))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    _, s_ref = ref.ref_quantize_rows(x)
    # kernel vs XLA max-reduction order: within 1 f32 ULP (~1.2e-7 rel)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_ref), rtol=2e-7)


def test_padding_is_inert():
    """Padded K region must contribute exactly zero even when m(0,0) != 0."""
    mobj, spec = _lowrank_spec(rank=8, seed=5)
    # verify the premise: this multiplier has m(0,0) != 0 or at least some
    # nonzero row/col at zero operands — if not, the test is vacuous but
    # still correct.
    a, b = _rand_q((4, 130)), _rand_q((130, 4))  # K=130 pads to 256
    want = np.asarray(ref.ref_approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                           spec))
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1.0)


# ---------------------------------------------------------------------------
# skinny-M decode kernel + plane-unroll schedule knob
# ---------------------------------------------------------------------------

SKINNY_SHAPES = [(1, 256, 256), (4, 200, 256), (8, 384, 130), (32, 512, 256)]


@pytest.mark.parametrize("shape", SKINNY_SHAPES)
@pytest.mark.parametrize("mult", ["exact", "trunc2x2"])
def test_skinny_kernel_bitexact_int_paths(shape, mult):
    """Decode-shaped GEMMs through the skinny-M kernel are bit-identical
    to the LUT oracle on the pure-int paths (incl. odd-K tails)."""
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    mobj = mm.get_multiplier(mult)
    spec = G.from_multiplier(mobj)
    oracle = np.asarray(ref.lut_matmul(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(mobj.lut)))
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec,
                                      skinny=True))
    np.testing.assert_array_equal(got, oracle.astype(np.float32))


@pytest.mark.parametrize("shape", SKINNY_SHAPES)
@pytest.mark.parametrize("rank", [1, 2, 8])
def test_skinny_matches_fused_bitexact_lowrank(shape, rank):
    """skinny == fused == stacked bit-for-bit at every rank: the same
    integer planes and the same f32 flush combination, so the decode
    layout is purely a schedule change."""
    m, k, n = shape
    a, b = _rand_q((m, k)), _rand_q((k, n))
    _, spec = _lowrank_spec(rank=rank, seed=rank)
    fused = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                        spec))
    skinny = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                         spec, skinny=True))
    stacked = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                          spec, fused=False))
    np.testing.assert_array_equal(skinny, fused)
    np.testing.assert_array_equal(skinny, stacked)


@pytest.mark.parametrize("unroll", [2, 3, 8])
def test_plane_unroll_is_bit_identical(unroll):
    """Plane-unroll groups correction planes into one batched int8 dot —
    integer accumulation, so every unroll factor gives the same bits on
    both the regular fused and the skinny kernels."""
    m, k, n = 16, 200, 128  # odd K: the grouped path must keep the tail mask
    a, b = _rand_q((m, k)), _rand_q((k, n))
    _, spec = _lowrank_spec(rank=8, seed=9)
    base = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec))
    got = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec,
                                      unroll=unroll))
    np.testing.assert_array_equal(got, base)
    sk_base = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b),
                                          spec, skinny=True))
    sk = np.asarray(ops.approx_qgemm(jnp.asarray(a), jnp.asarray(b), spec,
                                     skinny=True, unroll=unroll))
    np.testing.assert_array_equal(sk, sk_base)
    np.testing.assert_array_equal(sk_base, base)


@pytest.mark.parametrize("rows,cols", [(1, 128), (3, 256), (8, 512),
                                       (16, 128)])
def test_lane_table_map_equals_take(rows, cols):
    """The Mosaic-lowerable table map (two 128-lane halves, lane gather,
    half select) is `jnp.take` on the 256-entry table, bit for bit —
    every index value, and row counts below one sublane tile."""
    rng = np.random.default_rng(rows * cols)
    tbl = rng.integers(-128, 128, 256).astype(np.int8)
    idx = np.concatenate([np.arange(256), rng.integers(0, 256, rows * cols)])
    idx = idx[:rows * cols].reshape(rows, cols).astype(np.int32)
    halves = [jnp.asarray(tbl[None, h:h + 128], jnp.int32) for h in (0, 128)]
    got = np.asarray(qk._lane_table_map(*halves, jnp.asarray(idx)))
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got, tbl[idx])


def test_skinny_vmem_scales_with_true_m():
    """The skinny working set must scale with the true row count — the
    whole point of the decode kernel is never paying the 128-row pad."""
    small = qk.skinny_vmem_bytes(1, 512, 256, 3)
    big = qk.fused_vmem_bytes(128, 512, 256, 3)
    assert small < big
    assert qk.skinny_vmem_bytes(32, 512, 256, 3) > small
