"""The weight GEMM of the plain references, in float32 or, for the control,
in a precision below the configurations' bfloat16.

`quant=None` multiplies in float32 at the highest precision.  `"int8"`
and `"fp8"` (float8 e4m3) scale each row of the activations and each
column of the weights to the format's largest value, round both operands
into it, and accumulate the products exactly (int32, float32): the W8A8
GEMM a later change might be tempted to serve with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: control precision -> (operand type, largest value, accumulator type)
FORMATS = {"int8": (jnp.int8, 127.0, jnp.int32),
           "fp8": (jnp.float8_e4m3fn, 448.0, F32)}


def _to(x, scale, fmt):
    y = x / scale
    if jnp.issubdtype(fmt, jnp.integer):
        y = jnp.round(y)
    return y.astype(fmt)


def matmul(x, w, quant=None):
    """x (M, K) float32 by w (K, N) -> (M, N) float32."""
    w = w.astype(F32)
    if quant is None:
        return jnp.dot(x, w, precision=HIGHEST)
    if quant not in FORMATS:
        raise ValueError(f"unknown control precision {quant!r}")
    fmt, top, acc_t = FORMATS[quant]
    sa = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / top
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / top
    acc = jnp.dot(_to(x, sa, fmt), _to(w, sw, fmt),
                  preferred_element_type=acc_t)
    return acc.astype(F32) * sa * sw
