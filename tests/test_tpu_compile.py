"""Compile rehearsal: the main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported ops inside a kernel (a 1-D gather, say), slices not aligned to
the tiling, or more VMEM than a kernel may use.  Here each kernel is
lowered and compiled by the TPU compiler for a *described* v5e chip, at
tinyllama-1.1b's own GEMM widths, without needing the chip.  Nothing runs:
a pass says the compiler accepts the kernel, not that it is fast or right.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import approx_qgemm as qk
from repro.kernels import quantize as qz

#: tinyllama-1.1b's GEMM (K, N): attention q/o, k/v projections, FFN up,
#: FFN down.
WIDTHS = [(2048, 2048), (2048, 256), (2048, 5632), (5632, 2048)]
#: prefill-shaped row count (one 256-row block)
PREFILL_M = 256
RANK = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_args(sh, m, k, n, rank):
    return (_sds(sh, (m, k), jnp.int8), _sds(sh, (k, n), jnp.int8),
            _sds(sh, (rank, 256), jnp.int8), _sds(sh, (rank, 256), jnp.int8),
            _sds(sh, (rank + 1, 1), jnp.float32))


@pytest.mark.parametrize("k", sorted({k for k, _ in WIDTHS}))
def test_quantize_rows_compiles(one_chip, k):
    x = _sds(one_chip, (PREFILL_M, k), jnp.bfloat16)
    _compile(qz.quantize_rows, x, bm=PREFILL_M, trunc=2, interpret=False)


@pytest.mark.parametrize("k,n", WIDTHS)
def test_plane0_compiles(one_chip, k, n):
    bm, bk, bn = qk.choose_blocks(PREFILL_M, k, n)
    a, b = _kernel_args(one_chip, PREFILL_M, k, n, 0)[:2]
    _compile(qk.approx_qgemm_plane0, a, b, trunc_a=2, trunc_b=2, bm=bm,
             bk=bk, bn=bn, interpret=False)


@pytest.mark.parametrize("unroll", [1, 2])
@pytest.mark.parametrize("k,n", WIDTHS)
def test_fused_lowrank_compiles(one_chip, k, n, unroll):
    bm, bk, bn = qk.choose_blocks(PREFILL_M, k, n)
    _compile(qk.approx_qgemm_fused,
             *_kernel_args(one_chip, PREFILL_M, k, n, RANK), k_valid=k,
             bm=bm, bk=bk, bn=bn, unroll=unroll, interpret=False)


@pytest.mark.parametrize("rank", [0, RANK])
@pytest.mark.parametrize("m", [1, 8, 32])
@pytest.mark.parametrize("k,n", WIDTHS)
def test_skinny_compiles(one_chip, k, n, m, rank):
    bk, bn = qk.choose_skinny_blocks(k, n)
    _compile(qk.approx_qgemm_skinny, *_kernel_args(one_chip, m, k, n, rank),
             trunc_a=0 if rank else 2, trunc_b=0 if rank else 2, k_valid=k,
             bk=bk, bn=bn, interpret=False)


@pytest.mark.parametrize("k,n", WIDTHS)
def test_stacked_compiles(one_chip, k, n):
    bm, bk, bn = qk.choose_blocks(PREFILL_M, k, n)
    p = RANK + 1
    _compile(qk.approx_qgemm_stacked,
             _sds(one_chip, (p, PREFILL_M, k), jnp.int8),
             _sds(one_chip, (p, k, n), jnp.int8),
             _sds(one_chip, (p, 1), jnp.float32), bm=bm, bk=bk, bn=bn,
             interpret=False)
