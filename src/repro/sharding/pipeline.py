"""Pipeline parallelism: GPipe-style microbatch streaming over a "stage"
mesh axis with lax.ppermute activation transfer (shard_map).

This is the optional third parallelism dimension (the production meshes in
launch/mesh.py use data x model; PP composes by adding a leading "stage"
axis).  The schedule below is the classic fill-drain pipeline: M microbatches
over S stages in M + S - 1 ticks, bubble fraction (S-1)/(M+S-1).  Tested on
forced multi-device CPU in tests/test_distributed.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_mb: jax.Array,
                   mesh: Mesh, axis: str = "stage") -> jax.Array:
    """Run `stage_fn(params_i, x)` as a pipeline over mesh axis `axis`.

    stage_params: leading dim S (sharded over `axis`), one slice per stage.
    x_mb: (M, mb, d) microbatched input (replicated).
    Returns (M, mb, d) outputs (replicated).
    """
    s = mesh.shape[axis]
    m = x_mb.shape[0]
    steps = m + s - 1

    pspec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspec_params, P()), out_specs=P(),
        check_vma=False)
    def run(params, xs):
        idx = jax.lax.axis_index(axis)
        local_params = jax.tree_util.tree_map(lambda p: p[0], params)
        perm = [(i, i + 1) for i in range(s - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t; downstream stages consume buf
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(idx == 0, xs[mb_idx], buf)
            y = stage_fn(local_params, x_in)
            # the last stage's y for tick t is microbatch t-(s-1)
            out_idx = t - (s - 1)
            valid = (idx == s - 1) & (out_idx >= 0) & (out_idx < m)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, m - 1), 0),
                lambda o: o, outs)
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(steps))
        # only the last stage holds real outputs; broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run(stage_params, x_mb)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
