"""Backend probe for the installed JAX."""

from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def backend() -> str:
    """Default JAX backend name ("cpu" / "gpu" / "tpu").

    Cached: calling this initializes JAX's backends, so keep it out of
    module import paths (the dry-run must set XLA_FLAGS before any jax
    device-state touch — same rule as launch/mesh.py).
    """
    return jax.default_backend()


def is_tpu_backend() -> bool:
    """True when the default backend is a real TPU (Pallas compiles through
    Mosaic); False means Pallas TPU kernels must run with interpret=True."""
    return backend() == "tpu"
