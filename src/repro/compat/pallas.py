"""Pallas TPU compiler-params spelling.

Kernel modules do not spell `pltpu.CompilerParams` directly — the class
has been renamed before (`TPUCompilerParams`) — they call
`tpu_compiler_params(...)`, so a future rename is absorbed here.

Dimension-semantics strings are normalized too: the Mosaic vocabulary is
("parallel", "arbitrary"); "sequential" is accepted as an alias for
"arbitrary" since some external kernel code uses that spelling.
"""

from __future__ import annotations

from typing import Any, Sequence

from jax.experimental.pallas import tpu as pltpu

_DIM_SEMANTICS_ALIASES = {
    "parallel": "parallel",
    "arbitrary": "arbitrary",
    "sequential": "arbitrary",
}


def normalize_dimension_semantics(sem: Sequence[str]) -> tuple[str, ...]:
    """Map each grid-dimension semantic onto the Mosaic vocabulary."""
    out = []
    for s in sem:
        canon = _DIM_SEMANTICS_ALIASES.get(str(s).lower())
        if canon is None:
            raise ValueError(
                f"unknown dimension semantic {s!r}; expected one of "
                f"{sorted(_DIM_SEMANTICS_ALIASES)}")
        out.append(canon)
    return tuple(out)


def tpu_compiler_params(*, dimension_semantics: Sequence[str] | None = None,
                        **kwargs: Any) -> pltpu.CompilerParams:
    """Build the `compiler_params=` argument for a TPU `pl.pallas_call`."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = \
            normalize_dimension_semantics(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)
