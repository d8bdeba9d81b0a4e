"""jit'd public wrappers around the Pallas kernels.

Handles: padding to block multiples, reshaping, and the interpret-mode
switch (CPU containers run kernels with interpret=True; on real TPU the
same code compiles to Mosaic).

The approximate GEMM runs FUSED by default: raw quantized operands go
straight into the kernel, which applies the truncation mask and the
per-rank table maps in-register (kernels/approx_qgemm.py).  The legacy
stacked path — `build_stacks` pre-maps the operands in XLA into (P, M, K)
/ (P, K, N) HBM intermediates — is kept behind `fused=False` as the
reference twin for parity tests and the BENCH_gemm trajectory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.approx import gemm as gemm_mod
from repro.kernels import approx_qgemm as qk
from repro.kernels import dispatch
from repro.kernels import flash_attention as fk
from repro.kernels import quantize as qz


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def build_stacks(a_q: jax.Array, b_q: jax.Array, spec: gemm_mod.MultSpec
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Build (P, M, K) / (P, K, N) int8 operand stacks + (P, 1) f32 scales.

    Plane 0 carries the raw (or truncation-masked) operands with scale +1;
    planes 1..R carry the table-mapped correction operands with scale -s_r.
    """
    if spec.mode == "trunc":
        a0 = gemm_mod._trunc_mask(a_q, spec.trunc_a)
        b0 = gemm_mod._trunc_mask(b_q, spec.trunc_b)
        return (a0[None], b0[None], jnp.ones((1, 1), jnp.float32))
    planes_a = [a_q]
    planes_b = [b_q]
    scales = [jnp.ones((), jnp.float32)]
    for r in range(spec.rank):
        planes_a.append(gemm_mod._table_map(spec.fu_q[r], a_q))
        planes_b.append(gemm_mod._table_map(spec.fv_q[r], b_q))
        scales.append(-spec.s_r[r])
    return (jnp.stack(planes_a), jnp.stack(planes_b),
            jnp.stack(scales)[:, None])


def _spec_kernel_args(spec: gemm_mod.MultSpec):
    """(trunc_a, trunc_b, rank) as the kernels consume them."""
    trunc_a = spec.trunc_a if spec.mode == "trunc" else 0
    trunc_b = spec.trunc_b if spec.mode == "trunc" else 0
    rank = spec.rank if spec.mode == "lowrank" else 0
    return trunc_a, trunc_b, rank


def approx_qgemm(a_q: jax.Array, b_q: jax.Array, spec: gemm_mod.MultSpec,
                 *, bm: int | None = None, bk: int | None = None,
                 bn: int | None = None, fused: bool = True,
                 skinny: bool = False, unroll: int = 1) -> jax.Array:
    """int8 (m, k) x int8 (k, n) -> f32 (m, n) via the Pallas kernels.

    `fused=True` (default) streams the raw operands once and maps/masks
    them in-kernel; `fused=False` runs the stacked reference twin (XLA
    pre-maps `(R+1)x` operand copies through HBM).  `skinny=True` routes
    a decode-shaped GEMM (m <= SKINNY_MAX_M) to the skinny-M kernel: the
    row batch is consumed unpadded, so `bm` is ignored.  `unroll` is the
    plane-unroll schedule knob (bit-identical at every value)."""
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2
    interpret = dispatch.interpret_mode()
    trunc_a, trunc_b, rank = _spec_kernel_args(spec)
    if fused and skinny:
        assert m <= qk.SKINNY_MAX_M, (m, qk.SKINNY_MAX_M)
        bk, bn = qk.choose_skinny_blocks(k, n, bk, bn)
        ap = _pad_to(a_q, 1, bk)
        bp = _pad_to(_pad_to(b_q, 0, bk), 1, bn)
        scales = jnp.concatenate(
            [jnp.ones((1,), jnp.float32), -spec.s_r])[:, None] if rank \
            else jnp.ones((1, 1), jnp.float32)
        out = qk.approx_qgemm_skinny(
            ap, bp, spec.fu_q[:rank], spec.fv_q[:rank], scales,
            trunc_a=trunc_a, trunc_b=trunc_b, k_valid=k, bk=bk, bn=bn,
            unroll=unroll, interpret=interpret)
        return out[:, :n]
    bm, bk, bn = qk.choose_blocks(m, k, n, bm, bk, bn)
    if not fused:
        a_s, b_s, s = build_stacks(a_q, b_q, spec)
        a_s = _pad_to(_pad_to(a_s, 1, bm), 2, bk)
        b_s = _pad_to(_pad_to(b_s, 1, bk), 2, bn)
        out = qk.approx_qgemm_stacked(a_s, b_s, s, bm=bm, bk=bk, bn=bn,
                                      interpret=interpret)
        return out[:m, :n]
    ap = _pad_to(_pad_to(a_q, 0, bm), 1, bk)
    bp = _pad_to(_pad_to(b_q, 0, bk), 1, bn)
    if rank:
        scales = jnp.concatenate(
            [jnp.ones((1,), jnp.float32), -spec.s_r])[:, None]
        out = qk.approx_qgemm_fused(
            ap, bp, spec.fu_q, spec.fv_q, scales, trunc_a=trunc_a,
            trunc_b=trunc_b, k_valid=k, bm=bm, bk=bk, bn=bn, unroll=unroll,
            interpret=interpret)
    else:
        out = qk.approx_qgemm_plane0(ap, bp, trunc_a=trunc_a,
                                     trunc_b=trunc_b, bm=bm, bk=bk, bn=bn,
                                     interpret=interpret)
    return out[:m, :n]


def approx_qgemm_planned(a_q: jax.Array, b_q: jax.Array,
                         spec: gemm_mod.MultSpec,
                         plan: dispatch.GemmPlan) -> jax.Array:
    """Execute a GEMM per a `dispatch.choose_gemm_path` plan (Pallas
    paths; the XLA path belongs to approx/gemm.py, which knows about
    prepared weights)."""
    assert plan.path in ("fused", "stacked"), plan
    if plan.path == "stacked":
        return approx_qgemm(a_q, b_q, spec, fused=False)
    if plan.skinny:
        return approx_qgemm(a_q, b_q, spec, bk=plan.bk, bn=plan.bn,
                            skinny=True, unroll=plan.unroll)
    return approx_qgemm(a_q, b_q, spec, bm=plan.bm, bk=plan.bk, bn=plan.bn,
                        unroll=plan.unroll)


def approx_qgemm_tp(a_q: jax.Array, b_q: jax.Array,
                    spec: gemm_mod.MultSpec, mesh, *,
                    axis: str = "model", fused: bool = True) -> jax.Array:
    """Column-parallel tensor-parallel fused GEMM: the weight is sharded
    on its output dim over the mesh's `axis`, activations are replicated,
    and each shard runs the SAME fused Pallas kernel on its shard-local
    (m, k, n/tp) slice — the (R, 256) LUT factor tables ride into every
    shard's VMEM (they are spec constants, replicated by closure).  A
    full-K contraction per shard means no cross-shard reduction, so the
    result is bit-identical to the single-device kernel.

    Inside jit, the shard_map in_specs double as sharding constraints:
    weights prepared/committed with sharding/rules.py (col-parallel on
    "model") flow in without movement; anything else is resharded once by
    GSPMD."""
    from jax.sharding import PartitionSpec as P

    n = b_q.shape[1]
    tp = dispatch.tp_degree(mesh)
    assert tp > 1 and n % tp == 0, (n, tp)

    def per_shard(a, b):
        return approx_qgemm(a, b, spec, fused=fused)

    run = jax.shard_map(per_shard, mesh=mesh,
                        in_specs=(P(), P(None, axis)),
                        out_specs=P(None, axis), check_vma=False)
    return run(a_q, b_q)


def approx_qgemm_replicated(a_q: jax.Array, b_q: jax.Array,
                            spec: gemm_mod.MultSpec, mesh, *,
                            fused: bool = True) -> jax.Array:
    """Fully-replicated shard_map wrapper: every device runs the whole
    fused kernel.  The escape hatch for a pallas-pinned policy on a
    multi-device mesh when the output dim does not divide the model axis
    (pallas_call is opaque to GSPMD, so it must run under manual
    partitioning either way)."""
    from jax.sharding import PartitionSpec as P

    run = jax.shard_map(
        lambda a, b: approx_qgemm(a, b, spec, fused=fused), mesh=mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False)
    return run(a_q, b_q)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int | None = None,
                    bkv: int | None = None) -> jax.Array:
    """q (bh, sq, d), k/v (bh, skv, d) -> (bh, sq, d)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = bq or min(fk.DEFAULT_BQ, sq)
    bkv = bkv or min(fk.DEFAULT_BKV, skv)
    assert sq % bq == 0 and skv % bkv == 0, \
        "pad sequence to block multiples before calling"
    return fk.flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv,
                              interpret=dispatch.interpret_mode())


def quantize_rows(x: jax.Array, *, bm: int | None = None, trunc: int = 0
                  ) -> tuple[jax.Array, jax.Array]:
    """(M, K) float -> int8 rows + scales via the fused kernel.

    `trunc` > 0 additionally masks the bottom LSBs of the quantized rows
    in the same VMEM pass — the prologue fusion for trunc-mode GEMMs
    (saves the separate XLA mask pass on the activation side)."""
    m, k = x.shape
    bm = bm or min(qz.DEFAULT_BM, max(8, 1 << (m - 1).bit_length()))
    xp = _pad_to(x, 0, bm)
    q, s = qz.quantize_rows(xp, bm=bm, trunc=trunc,
                            interpret=dispatch.interpret_mode())
    return q[:m], s[:m]
