"""Model-facing approximate compute layers.

Every matmul in every model in this framework routes through `dense` /
`conv2d` / `gemm` here, so any architecture can be evaluated under any
candidate approximate multiplier (the accuracy-constraint substrate of the
paper's GA).  With `spec=None` or an exact spec the layer is a plain bf16/f32
matmul — that is the dry-run / roofline baseline mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.approx import gemm as gemm_mod


def _as_weight(w, dtype):
    """Accepts a plain array, an int8-serving {"q","s"} dict leaf, or a
    serving `PreparedWeight` (degrades to its original float weight)."""
    from repro.approx import quant
    if gemm_mod.is_prepared(w):
        return w.w
    if quant.is_qweight(w):
        return quant.dequantize_weight(w, dtype)
    return w


def gemm(x: jax.Array, w,
         spec: gemm_mod.MultSpec | None = None,
         policy: str | None = None) -> jax.Array:
    """x (..., k) @ w (k, n), approximate if spec says so.

    `w` may be a raw array, an int8-serving {"q","s"} dict leaf, or a
    `PreparedWeight` (the serving weight-plane cache, api.prepare_params):
    prepared weights skip the per-call weight quantize/table-map entirely
    and are bit-identical to the fresh path.

    `policy` overrides the spec-carried kernel-dispatch policy for this
    call ("auto" | "pallas" | "xla"); None keeps `spec.policy`.
    """
    if spec is None or spec.is_exact:
        # f32 accumulation out of the dot, rounded once: a row-parallel
        # (K-sharded) weight then all-reduces f32 partial sums instead of
        # bf16-rounded ones, so tensor-parallel serving matches one chip
        return jnp.einsum("...k,kn->...n", x, _as_weight(w, x.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)
    if policy is not None:
        spec = spec.with_policy(policy)
    if gemm_mod.is_prepared(w):
        return gemm_mod.approx_matmul_prepared(x, w, spec)
    return gemm_mod.approx_matmul(x, _as_weight(w, x.dtype), spec)


def dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
          spec: gemm_mod.MultSpec | None = None,
          policy: str | None = None) -> jax.Array:
    """Linear layer.  The bias add stays exact (the paper approximates the
    MAC multipliers; accumulators/adders are exact)."""
    y = gemm(x, w, spec, policy)
    if b is not None:
        y = y + b
    return y


def _im2col(x: jax.Array, r: int, s: int, stride: int, padding: int
            ) -> tuple[jax.Array, int, int]:
    """x (n, h, w, c) -> patches (n, ho, wo, r*s*c)."""
    n, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    ho = (h + 2 * padding - r) // stride + 1
    wo = (w + 2 * padding - s) // stride + 1
    idx_h = stride * jnp.arange(ho)[:, None] + jnp.arange(r)[None, :]  # ho,r
    idx_w = stride * jnp.arange(wo)[:, None] + jnp.arange(s)[None, :]  # wo,s
    # gather rows then cols
    patches = xp[:, idx_h]              # (n, ho, r, w+2p, c)
    patches = patches[:, :, :, idx_w]   # (n, ho, r, wo, s, c)
    patches = patches.transpose(0, 1, 3, 2, 4, 5)  # (n, ho, wo, r, s, c)
    return patches.reshape(n, ho, wo, r * s * c), ho, wo


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1, padding: int = 1,
           spec: gemm_mod.MultSpec | None = None,
           policy: str | None = None) -> jax.Array:
    """NHWC conv via im2col + (approximate) GEMM.

    x (n, h, w, c_in), w (r, s, c_in, c_out).  im2col is exactly how the
    NVDLA-style accelerator maps conv onto its MAC array, so simulated
    approximation composes correctly per-MAC.
    """
    w = _as_weight(w, x.dtype)
    r, s, c_in, c_out = w.shape
    if spec is None or spec.is_exact:
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(padding, padding), (padding, padding)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    patches, ho, wo = _im2col(x, r, s, stride, padding)
    w2 = w.reshape(r * s * c_in, c_out)
    y = gemm(patches, w2, spec, policy)
    return y.reshape(x.shape[0], ho, wo, c_out)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """Embedding lookups are reads, not MACs — always exact."""
    return jnp.take(table, tokens, axis=0)
