"""Pallas TPU kernels: approximate int8 GEMM as (R+1) MXU matmuls.

Computes  C[m,n] = sum_k m(a[m,k], b[k,n])  for an approximate multiplier m,
in the low-rank formulation (DESIGN.md §3):

    C = A.B - sum_r s_r * U_r(A).V_r(B)

Two kernels implement this:

`approx_qgemm_fused` (the hot path): consumes the *raw* quantized operands.
The (R, 256) int8 factor tables live in VMEM alongside the operand tiles;
each (bm, bk) / (bk, bn) tile is table-mapped in-register per correction
plane, and the truncation mask (precision-scaled multipliers) is applied
in-kernel as a bitwise AND.  HBM reads both operands exactly once —
`(R+1)x` less operand traffic than the stacked kernel, and no `(P, M, K)` /
`(P, K, N)` intermediates ever materialize.

`approx_qgemm_stacked` (reference / A-B twin): ops.py pre-maps the operands
through the tables in XLA, producing stacks  a_stack (R+1, M, K) int8  and
b_stack (R+1, K, N) int8, and the kernel is pure MXU work.  Kept for the
fused-vs-stacked parity tests and the BENCH_gemm trajectory.

Both kernels accumulate per-plane in int32 with the f32 plane scales applied
once at flush, so they are bit-identical to each other and to the XLA
reference semantics (no f32 partial-sum drift across the K loop).  K is
innermost ("arbitrary") so the accumulator lives across the K loop; M/N are
parallel.

Block shapes default to (bm, bk, bn) = (256, 512, 256): MXU-aligned
(multiples of 128 / int8 lane tiling).  `fused_vmem_bytes` /
`stacked_vmem_bytes` give the VMEM working set per grid step —
kernels/dispatch.py checks the fused budget in its auto policy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat


DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256

#: Largest M the decode-specialized skinny kernel accepts: one decode step
#: of a continuous-batching arena (m = batch).  Above this, padding to an
#: MXU tile stops being the dominant cost and the regular fused kernel wins.
SKINNY_MAX_M = 32

#: Bump when a kernel's schedule/layout changes in a way that invalidates
#: measured tile timings (kernels/autotune.py keys its cache on this).
KERNEL_VERSION = 3


def choose_blocks(m: int, k: int, n: int, bm: int | None = None,
                  bk: int | None = None, bn: int | None = None
                  ) -> tuple[int, int, int]:
    """Default block shape for an (m, k, n) GEMM: the standard blocks capped
    below by one MXU tile and above by the defaults (small operands round up
    to a single 128-multiple block instead of padding to 256/512)."""
    bm = bm or min(DEFAULT_BM, max(128, 1 << max(m - 1, 0).bit_length()))
    bk = bk or min(DEFAULT_BK, max(128, 1 << max(k - 1, 0).bit_length()))
    bn = bn or min(DEFAULT_BN, max(128, 1 << max(n - 1, 0).bit_length()))
    return bm, bk, bn


def choose_skinny_blocks(k: int, n: int, bk: int | None = None,
                         bn: int | None = None) -> tuple[int, int]:
    """Default (bk, bn) for the skinny-M decode kernel (M is never
    blocked — the whole row batch rides in every grid step)."""
    bk = bk or min(DEFAULT_BK, max(128, 1 << max(k - 1, 0).bit_length()))
    bn = bn or min(DEFAULT_BN, max(128, 1 << max(n - 1, 0).bit_length()))
    return bk, bn


def fused_vmem_bytes(bm: int, bk: int, bn: int, n_planes: int) -> int:
    """VMEM working set of one fused-kernel grid step: double-buffered raw
    int8 operand tiles (plane count does NOT multiply them — that is the
    point), the factor tables, the per-plane int32 accumulator, and the
    double-buffered f32 output tile."""
    operands = 2 * (bm * bk + bk * bn)
    tables = 2 * 2 * max(n_planes - 1, 0) * 256
    acc = n_planes * bm * bn * 4
    out = 2 * bm * bn * 4
    return operands + tables + acc + out


def stacked_vmem_bytes(bm: int, bk: int, bn: int, n_planes: int) -> int:
    """Same for the stacked kernel: operand tiles scale with the plane
    count (the pre-mapped stacks are streamed from HBM)."""
    operands = 2 * n_planes * (bm * bk + bk * bn)
    acc = n_planes * bm * bn * 4
    out = 2 * bm * bn * 4
    return operands + acc + out


def skinny_vmem_bytes(m: int, bk: int, bn: int, n_planes: int) -> int:
    """VMEM working set of one skinny-kernel grid step: the whole (un-
    padded) M dimension rides in every block, so the A tile and the
    accumulator scale with the true row count, not a 128-padded bm.
    Rank 0 still ships one dummy table row per side (a BlockSpec dim may
    not be 0), so the table term floors at one row."""
    operands = 2 * (m * bk + bk * bn)
    tables = 2 * 2 * max(n_planes - 1, 1) * 256
    acc = n_planes * m * bn * 4
    out = 2 * m * bn * 4
    return operands + tables + acc + out


def signed_trunc_mask(t: int) -> int:
    """Two's-complement signed value of the uint8 LSB-truncation mask
    0xFF & ~((1<<t)-1); -1 (all bits set) when t <= 0 (no truncation)."""
    if t <= 0:
        return -1
    return ((0xFF & ~((1 << t) - 1)) ^ 0x80) - 0x80


# ---------------------------------------------------------------------------
# stacked kernel (reference twin; operands pre-mapped in XLA by ops.py)
# ---------------------------------------------------------------------------

def _stacked_kernel(a_ref, b_ref, s_ref, out_ref, acc_ref, *, n_planes: int,
                    k_blocks: int):
    """One (i, j, k) grid step.

    a_ref: (n_planes, bm, bk) int8 VMEM
    b_ref: (n_planes, bk, bn) int8 VMEM
    s_ref: (n_planes, 1) f32 VMEM   (plane scales; s[0]=1, s[r]=-s_r)
    out_ref: (bm, bn) f32 VMEM
    acc_ref: (n_planes, bm, bn) int32 VMEM scratch
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for r in range(n_planes):  # static unroll over correction planes
        acc_ref[r] += jnp.dot(a_ref[r], b_ref[r],
                              preferred_element_type=jnp.int32)

    @pl.when(k == k_blocks - 1)
    def _flush():
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for r in range(n_planes):
            acc = acc + s_ref[r, 0] * acc_ref[r].astype(jnp.float32)
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def approx_qgemm_stacked(a_stack: jax.Array, b_stack: jax.Array,
                         scales: jax.Array, *, bm: int = DEFAULT_BM,
                         bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
                         interpret: bool = False) -> jax.Array:
    """a_stack (P, M, K) int8, b_stack (P, K, N) int8, scales (P, 1) f32
    -> (M, N) f32.  M, K, N must be multiples of the block shape (ops.py
    pads; padding is inserted *after* table mapping so padded elements
    contribute exactly zero in every plane)."""
    p, m, k = a_stack.shape
    p2, k2, n = b_stack.shape
    assert p == p2 and k == k2, (a_stack.shape, b_stack.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_stacked_kernel, n_planes=p, k_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((p, bk, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((p, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, bm, bn), jnp.int32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_stack, b_stack, scales)


# ---------------------------------------------------------------------------
# fused kernel: raw operands in, table map + trunc mask in-kernel
# ---------------------------------------------------------------------------

def _lane_table_map(lo, hi, idx):
    """Map every element of `idx` (rows, cols) int32 in [0, 256) through a
    256-entry table given as its two 128-lane halves `lo`/`hi` (1, 128)
    int32, in a form Mosaic lowers.

    Mosaic has no 1-D gather (`jnp.take` on the table is refused), but it
    does lower a 2-D lane gather: `take_along_axis(x, i, axis=1)` with x
    and i both (rows, 128).  So each half is broadcast down the rows,
    every 128-lane chunk of the index is looked up in both halves on
    `idx & 127`, and the half is picked by `idx >= 128`.  `cols` must be a
    multiple of 128 (every block shape is); fewer than 8 rows are padded
    to one sublane tile (a 1-row gather does not lower).  Exact integer
    lookups: the result equals `jnp.take` bit for bit."""
    rows, cols = idx.shape
    if rows < 8:
        pad = jnp.zeros((8 - rows, cols), idx.dtype)
        return _lane_table_map(lo, hi, jnp.concatenate([idx, pad]))[:rows]
    lo = jnp.broadcast_to(lo, (rows, 128))
    hi = jnp.broadcast_to(hi, (rows, 128))
    lane = jnp.bitwise_and(idx, 127)
    upper = idx >= 128
    chunks = []
    for c in range(0, cols, 128):
        li = lane[:, c:c + 128]
        chunks.append(jnp.where(
            upper[:, c:c + 128],
            jnp.take_along_axis(hi, li, axis=1),
            jnp.take_along_axis(lo, li, axis=1)))
    out = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return out.astype(jnp.int8)


def _table_halves(tbl_ref, r: int):
    """Row r of an (R, 256) int8 table ref as two (1, 128) int32 halves.
    Each half is loaded on its own: a lane slice at offset 128 of a loaded
    row would reach the broadcast with a layout Mosaic refuses."""
    return tuple(tbl_ref[pl.ds(r, 1), pl.ds(h, 128)].astype(jnp.int32)
                 for h in (0, 128))


def _correction_dots(a, b, fu_ref, fv_ref, acc_ref, in_k, *, n_corr: int,
                     unroll: int):
    """Table-map + matmul the `n_corr` correction planes into acc_ref[1:].

    `unroll` groups planes: each group's mapped tiles are stacked and run
    as ONE batched int8 dot_general (a single MXU dispatch per group
    instead of per plane).  Integer accumulation, so the result is
    bit-identical at every unroll factor — it is purely a schedule knob,
    which is what lets the autotuner search it.
    """
    idx_a = jnp.bitwise_and(a.astype(jnp.int32), 0xFF)
    idx_b = jnp.bitwise_and(b.astype(jnp.int32), 0xFF)
    for r0 in range(0, n_corr, unroll):
        u = min(unroll, n_corr - r0)
        uas, vbs = [], []
        for r in range(r0, r0 + u):
            ua = _lane_table_map(*_table_halves(fu_ref, r), idx_a)
            if in_k is not None:
                ua = jnp.where(in_k, ua, jnp.int8(0))
            uas.append(ua)
            vbs.append(_lane_table_map(*_table_halves(fv_ref, r), idx_b))
        if u == 1:
            acc_ref[r0 + 1] += jnp.dot(uas[0], vbs[0],
                                       preferred_element_type=jnp.int32)
        else:
            batched = jax.lax.dot_general(
                jnp.stack(uas), jnp.stack(vbs),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
            acc_ref[r0 + 1:r0 + 1 + u] += batched


def _fused_kernel(a_ref, b_ref, fu_ref, fv_ref, s_ref, out_ref, acc_ref, *,
                  n_planes: int, k_blocks: int, bk: int, k_valid: int,
                  mask_a: int, mask_b: int, unroll: int):
    """One (i, j, k) grid step over RAW operand tiles.

    a_ref: (bm, bk) int8 VMEM      raw quantized activations
    b_ref: (bk, bn) int8 VMEM      raw quantized weights
    fu_ref/fv_ref: (R, 256) int8 VMEM   per-rank factor tables (whole table
        resident; the index map is constant so it is fetched once)
    s_ref: (n_planes, 1) f32 VMEM  plane scales (s[0]=1, s[r]=-s_r)
    out_ref: (bm, bn) f32 VMEM
    acc_ref: (n_planes, bm, bn) int32 VMEM scratch

    `k_valid` is the un-padded contraction length: K-pad zeros are inert in
    plane 0 (0*0 == 0) but map through the tables to tbl[0], which is in
    general nonzero — mapped a-tiles are therefore masked past k_valid
    (zeroing one side of the product suffices).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    a0 = a if mask_a == -1 else jnp.bitwise_and(a, jnp.int8(mask_a))
    b0 = b if mask_b == -1 else jnp.bitwise_and(b, jnp.int8(mask_b))
    acc_ref[0] += jnp.dot(a0, b0, preferred_element_type=jnp.int32)

    if n_planes > 1:
        in_k = None
        if k_valid < k_blocks * bk:  # static: any K padding at all
            kpos = k * bk + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            in_k = kpos < k_valid  # all-true except past the K tail
        _correction_dots(a, b, fu_ref, fv_ref, acc_ref, in_k,
                         n_corr=n_planes - 1, unroll=unroll)

    @pl.when(k == k_blocks - 1)
    def _flush():
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for r in range(n_planes):
            acc = acc + s_ref[r, 0] * acc_ref[r].astype(jnp.float32)
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "trunc_a", "trunc_b", "k_valid", "bm", "bk", "bn", "unroll",
    "interpret"))
def approx_qgemm_fused(a_q: jax.Array, b_q: jax.Array, fu_q: jax.Array,
                       fv_q: jax.Array, scales: jax.Array, *,
                       trunc_a: int = 0, trunc_b: int = 0, k_valid: int,
                       bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                       bn: int = DEFAULT_BN, unroll: int = 1,
                       interpret: bool = False) -> jax.Array:
    """Low-rank fused path: a_q (M, K) int8, b_q (K, N) int8, fu_q/fv_q
    (R, 256) int8 tables, scales (R+1, 1) f32 -> (M, N) f32.

    M, K, N must be block multiples (ops.py zero-pads the raw operands);
    `k_valid` is the true contraction length before padding."""
    m, k = a_q.shape
    k2, n = b_q.shape
    r = fu_q.shape[0]
    assert k == k2 and fv_q.shape == fu_q.shape == (r, 256)
    assert scales.shape == (r + 1, 1), scales.shape
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    assert 0 < k_valid <= k, (k_valid, k)
    grid = (m // bm, n // bn, k // bk)
    p = r + 1

    return pl.pallas_call(
        functools.partial(
            _fused_kernel, n_planes=p, k_blocks=grid[2], bk=bk,
            k_valid=k_valid, mask_a=signed_trunc_mask(trunc_a),
            mask_b=signed_trunc_mask(trunc_b), unroll=unroll),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((r, 256), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((r, 256), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((p, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, bm, bn), jnp.int32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_q, b_q, fu_q, fv_q, scales)


def _plane0_kernel(a_ref, b_ref, out_ref, acc_ref, *, k_blocks: int,
                   mask_a: int, mask_b: int):
    """Single-plane (exact / trunc) grid step: trunc masks in-kernel."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    a0 = a if mask_a == -1 else jnp.bitwise_and(a, jnp.int8(mask_a))
    b0 = b if mask_b == -1 else jnp.bitwise_and(b, jnp.int8(mask_b))
    acc_ref[...] += jnp.dot(a0, b0, preferred_element_type=jnp.int32)

    @pl.when(k == k_blocks - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "trunc_a", "trunc_b", "bm", "bk", "bn", "interpret"))
def approx_qgemm_plane0(a_q: jax.Array, b_q: jax.Array, *, trunc_a: int = 0,
                        trunc_b: int = 0, bm: int = DEFAULT_BM,
                        bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
                        interpret: bool = False) -> jax.Array:
    """Exact / truncation-only fused path: a_q (M, K) x b_q (K, N) -> f32
    (M, N) with the LSB masks applied in-kernel.  K-pad zeros are inert
    (masked zero stays zero), so no k_valid is needed."""
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2, (a_q.shape, b_q.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_plane0_kernel, k_blocks=grid[2],
                          mask_a=signed_trunc_mask(trunc_a),
                          mask_b=signed_trunc_mask(trunc_b)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_q, b_q)


# ---------------------------------------------------------------------------
# skinny-M kernel: decode-shaped GEMMs (m = batch <= SKINNY_MAX_M)
# ---------------------------------------------------------------------------

def _skinny_kernel(a_ref, b_ref, fu_ref, fv_ref, s_ref, out_ref, acc_ref, *,
                   n_planes: int, k_blocks: int, bk: int, k_valid: int,
                   mask_a: int, mask_b: int, unroll: int):
    """One (j, k) grid step of the decode-specialized GEMV-style kernel.

    a_ref: (m, bk) int8 VMEM — the WHOLE row batch, broadcast to every
        N-block (index map constant in j, so the tile re-fetches only
        across K steps); m is the true batch, never padded to an MXU tile.
    b_ref: (bk, bn) int8 VMEM — K-major streaming of the weight.
    acc_ref: (n_planes, m, bn) int32 VMEM scratch.

    Grid is (N-blocks, K-blocks) with K innermost ("arbitrary") so the
    accumulator lives across the contraction, same discipline as the
    prefill-shaped fused kernel; there is no M grid axis at all.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    a0 = a if mask_a == -1 else jnp.bitwise_and(a, jnp.int8(mask_a))
    b0 = b if mask_b == -1 else jnp.bitwise_and(b, jnp.int8(mask_b))
    acc_ref[0] += jnp.dot(a0, b0, preferred_element_type=jnp.int32)

    if n_planes > 1:
        in_k = None
        if k_valid < k_blocks * bk:
            kpos = k * bk + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            in_k = kpos < k_valid
        _correction_dots(a, b, fu_ref, fv_ref, acc_ref, in_k,
                         n_corr=n_planes - 1, unroll=unroll)

    @pl.when(k == k_blocks - 1)
    def _flush():
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for r in range(n_planes):
            acc = acc + s_ref[r, 0] * acc_ref[r].astype(jnp.float32)
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "trunc_a", "trunc_b", "k_valid", "bk", "bn", "unroll", "interpret"))
def approx_qgemm_skinny(a_q: jax.Array, b_q: jax.Array, fu_q: jax.Array,
                        fv_q: jax.Array, scales: jax.Array, *,
                        trunc_a: int = 0, trunc_b: int = 0, k_valid: int,
                        bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
                        unroll: int = 1,
                        interpret: bool = False) -> jax.Array:
    """Decode path: a_q (m, K) int8 with m <= SKINNY_MAX_M, b_q (K, N)
    int8, fu_q/fv_q (R, 256) tables (R may be 0 for exact/trunc), scales
    (R+1, 1) f32 -> (m, N) f32.

    K, N must be block multiples (ops.py pads); m is consumed AS IS — the
    whole point is that a batch-8 decode GEMM does 8 rows of MXU work
    instead of a 128-row padded tile.  Bit-identical to the fused/stacked
    kernels and the XLA reference on every plane."""
    m, k = a_q.shape
    k2, n = b_q.shape
    r = fu_q.shape[0]
    assert k == k2 and fv_q.shape == fu_q.shape == (r, 256)
    assert scales.shape == (r + 1, 1), scales.shape
    assert 0 < m <= SKINNY_MAX_M, m
    assert k % bk == 0 and n % bn == 0, (k, n, bk, bn)
    assert 0 < k_valid <= k, (k_valid, k)
    grid = (n // bn, k // bk)
    p = r + 1
    if r == 0:
        # Exact/trunc: the kernel never touches the tables (n_planes == 1),
        # but a BlockSpec dim of 0 is illegal — ship a 1-row dummy.
        fu_q = jnp.zeros((1, 256), jnp.int8)
        fv_q = jnp.zeros((1, 256), jnp.int8)
    ru = max(r, 1)

    return pl.pallas_call(
        functools.partial(
            _skinny_kernel, n_planes=p, k_blocks=grid[1], bk=bk,
            k_valid=k_valid, mask_a=signed_trunc_mask(trunc_a),
            mask_b=signed_trunc_mask(trunc_b), unroll=unroll),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),
            pl.BlockSpec((ru, 256), lambda j, kk: (0, 0)),
            pl.BlockSpec((ru, 256), lambda j, kk: (0, 0)),
            pl.BlockSpec((p, 1), lambda j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, m, bn), jnp.int32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a_q, b_q, fu_q, fv_q, scales)
