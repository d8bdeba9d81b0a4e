"""JAX persistent compilation cache for the entry points.

A cold process compiles every jitted step; on a TPU that can be a large
share of a short run.  `enable()` turns JAX's persistent cache on:

* where `$JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
  module sets no directory of its own;
* otherwise the cache lives at the fixed path `<repo>/.cache/jax`
  (gitignored).  The path is part of what makes a later process hit, so
  it never holds a temp dir, a pid or a time.

Called first by the serve/train CLIs, the benchmark mains and
`chip_smoke.py`.  Tests do not call it.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".cache" / "jax"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR, "").strip()
    if env:
        return env
    DEFAULT_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
