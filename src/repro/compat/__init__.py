"""Single home of the JAX spellings this codebase wraps.

The repository is pinned to one JAX release (requirements.txt).  What
stays here is what call sites must not spell themselves:

* meshes, built with `Auto` axis types (`make_mesh`, `make_abstract_mesh`);
* the Pallas TPU compiler-params class (`tpu_compiler_params`);
* the backend probe that decides Mosaic vs interpret mode.

No module outside `repro.compat` may reference `pltpu.*CompilerParams` or
construct `jax.sharding.AbstractMesh` directly (enforced by
tests/test_compat.py).  Importing this package never initializes JAX
device state.
"""

from repro.compat.mesh import make_abstract_mesh, make_mesh
from repro.compat.pallas import (normalize_dimension_semantics,
                                 tpu_compiler_params)
from repro.compat.version import backend, is_tpu_backend

__all__ = [
    "backend",
    "is_tpu_backend",
    "make_abstract_mesh",
    "make_mesh",
    "normalize_dimension_semantics",
    "tpu_compiler_params",
]
