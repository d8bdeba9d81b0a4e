"""Mesh construction with the axis types this codebase is written for.

On the pinned JAX, `jax.make_mesh` defaults to `Explicit` axis types: array
types then carry their shardings (`float32[512@model,128]`), and ops whose
output sharding is ambiguous — a `jnp.take` on a vocab-sharded embedding,
for one — raise `ShardingTypeError` even on a 1x1 mesh.  The model,
serving and training code is written for GSPMD-propagated (`Auto`) axes,
with shardings supplied by sharding/rules.py, so every mesh is built here
with `Auto` axes.

Everything here is callable-only (no module-level device probes): importing
this module never initializes JAX device state.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def _axes(axis_shapes: Sequence[int], axis_names: Sequence[str]
          ) -> tuple[tuple[int, ...], tuple[str, ...]]:
    sizes = tuple(int(s) for s in axis_shapes)
    names = tuple(str(n) for n in axis_names)
    if len(sizes) != len(names):
        raise ValueError(f"{len(sizes)} axis sizes vs {len(names)} names")
    return sizes, names


def make_abstract_mesh(axis_shapes: Sequence[int],
                       axis_names: Sequence[str]) -> AbstractMesh:
    """Device-free mesh (Auto axes) for sharding-rule evaluation."""
    sizes, names = _axes(axis_shapes, axis_names)
    return AbstractMesh(sizes, names, (AxisType.Auto,) * len(sizes))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Sequence | None = None) -> Mesh:
    """`jax.make_mesh` with `Auto` axis types.

    With `devices=None` JAX picks a contiguous, locality-aware device
    order; an explicit `devices` list is used from its head (the first
    prod(axis_shapes) entries), so callers can pin e.g. one chip of four."""
    sizes, names = _axes(axis_shapes, axis_names)
    n = math.prod(sizes)
    if devices is not None:
        devices = list(devices)
        if len(devices) < n:
            raise ValueError(f"mesh {dict(zip(names, sizes))} needs {n} "
                             f"devices, have {len(devices)}")
        devices = devices[:n]
    return jax.make_mesh(sizes, names, (AxisType.Auto,) * len(sizes),
                         devices=devices)
