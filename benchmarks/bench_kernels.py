"""Pallas kernel microbenchmarks (interpret mode on CPU: correctness-scale
timings; the BlockSpec/VMEM structure is the TPU artifact).

Each GEMM cell runs the full float-in/float-out `approx_matmul` path twice —
once per kernel-dispatch policy ("pallas" vs "xla", kernels/dispatch.py) —
so the benchmark exercises exactly the dispatch models/serving use, plus
the direct int8 kernel for the raw MXU-path number.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx import gemm as G
from repro.core import multipliers as mm
from repro.kernels import dispatch, ops


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main() -> list[str]:
    rng = np.random.default_rng(0)
    lines = []
    lines.append(f"kernel_dispatch_info,0.0,"
                 f"interpret={dispatch.interpret_mode()};"
                 f"default_policy={dispatch.default_policy()}")

    a = jnp.asarray(rng.integers(-128, 128, (256, 512)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (512, 256)), jnp.int8)
    x = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    for name in ("exact", "trunc2x2"):
        spec = G.spec_from_name(name)
        us = _time(lambda p, q, s=spec: ops.approx_qgemm(p, q, s), a, b)
        flops = 2 * 256 * 512 * 256 * spec.n_planes
        lines.append(f"kernel_qgemm_{name},{us:.1f},"
                     f"gflops_equiv={flops / us / 1e3:.2f}")
        # end-to-end dispatch path (quantize + GEMM + dequant) per policy
        for policy in ("pallas", "xla"):
            sp = spec.with_policy(policy)
            us = _time(lambda p, q, s=sp: G.approx_matmul(p, q, s), x, w)
            lines.append(f"approx_matmul_{name}_{policy},{us:.1f},"
                         f"m=256;k=512;n=256")

    q = jnp.asarray(rng.standard_normal((4, 512, 64)), jnp.float32)
    us = _time(lambda t: ops.flash_attention(t, t, t, causal=True,
                                             bq=128, bkv=128), q)
    lines.append(f"kernel_flash_attention,{us:.1f},bh=4;s=512;d=64")

    xq = jnp.asarray(rng.standard_normal((512, 1024)), jnp.float32)
    us = _time(lambda v: ops.quantize_rows(v), xq)
    lines.append(f"kernel_quantize_rows,{us:.1f},m=512;k=1024")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
