"""Kernel-dispatch policy: Pallas kernels vs the XLA reference path.

Replaces the old `use_kernel: bool` threaded through approx/gemm.py with a
named policy resolved per GEMM at trace time:

  "xla"    — never use the Pallas kernels (pure jnp/lax path);
  "pallas" — always use them (interpret mode off-TPU, Mosaic on TPU);
  "auto"   — use them when they plausibly win: real TPU backend, operand
             dims at least one MXU tile (padding a tiny GEMM to 128-multiples
             costs more than it saves), and the fused kernel's VMEM working
             set within budget.  Off-TPU, auto picks XLA — interpret-mode
             Pallas is a correctness vehicle, not a fast path.

The fused GEMM data path changed the auto accounting: operand HBM traffic
no longer scales with the plane count (raw operands are read once and
table-mapped in-register), so plane count only costs VMEM accumulator
space.  `fused_vmem_bytes` is checked against VMEM_BUDGET_BYTES directly —
rank-8 multipliers (9 planes) now pass at the default block shape, where
the old stacked-traffic cap (MAX_PLANES=8) rejected them.

The policy rides on `MultSpec.policy` (a static/meta pytree field, so a
policy change is a new jit cache key — no stale-trace footgun), is settable
per model via `ModelConfig.kernel_policy`, per run via the `--kernel-policy`
flag on launch/train.py and launch/serve.py, and process-wide via the
`REPRO_KERNEL_POLICY` environment variable.
"""

from __future__ import annotations

import dataclasses
import os

from repro import compat

POLICIES = ("auto", "pallas", "xla")
GEMM_PATHS = ("fused", "stacked", "xla")

#: Below one MXU tile on any operand dim, block padding dominates.
MIN_DIM = 128
#: Per-grid-step VMEM working-set budget for the fused kernel: ~16 MiB/core
#: minus compiler headroom.  Override per process with $REPRO_VMEM_BUDGET
#: (bytes, decimal or 0x-hex) for parts with different VMEM — the override
#: feeds every budget consumer (auto dispatch and the repro.analysis Pallas
#: contract checker) through `vmem_budget_bytes()`.
VMEM_BUDGET_BYTES = 14 << 20

_ENV_VAR = "REPRO_KERNEL_POLICY"
_VMEM_ENV_VAR = "REPRO_VMEM_BUDGET"


def vmem_budget_bytes() -> int:
    """Effective fused-kernel VMEM budget: $REPRO_VMEM_BUDGET (positive
    integer bytes; "0x..." hex accepted) or VMEM_BUDGET_BYTES."""
    raw = os.environ.get(_VMEM_ENV_VAR, "").strip()
    if not raw:
        return VMEM_BUDGET_BYTES
    try:
        val = int(raw, 0)
    except ValueError:
        raise ValueError(
            f"${_VMEM_ENV_VAR}={raw!r} is not an integer byte count")
    if val <= 0:
        raise ValueError(f"${_VMEM_ENV_VAR}={raw!r} must be positive")
    return val


def default_policy() -> str:
    """Process-wide default: $REPRO_KERNEL_POLICY or "auto"."""
    p = os.environ.get(_ENV_VAR, "auto").strip().lower()
    return p if p in POLICIES else "auto"


def resolve(policy: str | None) -> str:
    """Normalize a user-supplied policy.

    None/"" and "auto" both resolve through the process default, so
    $REPRO_KERNEL_POLICY can pin "pallas"/"xla" process-wide for any run
    that didn't explicitly choose a non-auto policy.
    """
    p = "auto" if policy in (None, "") else str(policy).lower()
    if p not in POLICIES:
        raise ValueError(f"unknown kernel policy {policy!r}; "
                         f"expected one of {POLICIES}")
    return default_policy() if p == "auto" else p


def interpret_mode() -> bool:
    """Pallas TPU kernels must run interpret=True off-TPU (CPU containers);
    on a real TPU the same pallas_call lowers through Mosaic."""
    return not compat.is_tpu_backend()


def tp_degree(mesh) -> int:
    """Model-axis size of a mesh (1 when absent / no mesh): the tensor-
    parallel fan-out a GEMM's output dimension is split across."""
    if mesh is None:
        return 1
    try:
        return int(mesh.shape.get("model", 1))
    except AttributeError:
        return 1


def tp_split(n: int, tp: int) -> int:
    """Shard-local output dimension under `tp`-way column parallelism
    (the whole dim when it does not divide — that GEMM stays unsplit)."""
    return n // tp if tp > 1 and n % tp == 0 else n


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Trace-time execution plan for one approximate GEMM.

    `path` is the three-way choice ("fused" / "stacked" Pallas kernels, or
    the "xla" reference); `bm/bk/bn/unroll` are the fused tile (tuned or
    default); `skinny=True` routes a decode-shaped GEMM (m <= SKINNY_MAX_M)
    to the skinny-M kernel, in which case bm is the true row count.
    `source` records why: "policy" (pinned), "tuned" (autotune cache hit),
    "roofline" (cost-model prediction), "default" (static fallback)."""
    path: str
    bm: int
    bk: int
    bn: int
    unroll: int = 1
    skinny: bool = False
    source: str = "default"

    @property
    def use_pallas(self) -> bool:
        return self.path != "xla"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _fused_admissible(m: int, k: int, n: int, n_planes: int, *,
                      skinny: bool, bm: int, bk: int, bn: int) -> bool:
    from repro.kernels import approx_qgemm as qk
    if skinny:
        return (m <= qk.SKINNY_MAX_M and min(k, n) >= MIN_DIM and
                qk.skinny_vmem_bytes(m, bk, bn, n_planes)
                <= vmem_budget_bytes())
    return (min(m, k, n) >= MIN_DIM and
            qk.fused_vmem_bytes(bm, bk, bn, n_planes)
            <= vmem_budget_bytes())


def choose_gemm_path(policy: str | None, *, m: int, k: int, n: int,
                     mode: str = "exact", rank: int = 0,
                     n_planes: int | None = None, tp: int = 1,
                     multi_device: bool = False) -> GemmPlan:
    """The three-way GEMM dispatch: fused / stacked / xla, with tiles.

    Resolution order under "auto" (single-device):

      1. the autotune cache — a MEASURED winner for this (backend,
         shape-bucket, mode, rank, VMEM budget) cell wins outright, tiles
         included;
      2. the roofline cost model (on TPU) — predicted-winner across the
         three paths at default tiles, with the skinny-M kernel standing
         in for fused on decode-shaped GEMMs.  `auto` therefore never
         picks fused where the model predicts stacked/XLA wins — the
         exact-mode regression BENCH_gemm used to show;
      3. off-TPU with no cache entry: XLA (interpret-mode Pallas is a
         correctness vehicle, not a fast path).

    Under tensor parallelism the plan applies to the SHARD-LOCAL shape
    (m, k, n/tp); stacked/skinny are not offered there (the shard_map
    wrappers run the regular fused kernel), so TP keeps the PR5-era
    binary fused/xla choice."""
    from repro.kernels import approx_qgemm as qk

    p = resolve(policy)
    n_planes = n_planes if n_planes is not None else 1 + rank
    n_local = tp_split(n, tp)
    bm, bk, bn = qk.choose_blocks(m, k, n_local)
    if p == "xla":
        return GemmPlan("xla", bm, bk, bn, source="policy")
    sharded = tp > 1 or multi_device
    if p == "pallas":
        plan = None if sharded else _tuned_plan(m, k, n_local, mode, rank,
                                                n_planes)
        if plan is not None and plan.path == "fused":
            return plan
        if not sharded and m <= qk.SKINNY_MAX_M:
            sbk, sbn = qk.choose_skinny_blocks(k, n_local)
            return GemmPlan("fused", m, sbk, sbn, skinny=True,
                            source="policy")
        return GemmPlan("fused", bm, bk, bn, source="policy")
    # auto
    if sharded:
        if (compat.is_tpu_backend()
                and _fused_admissible(m, k, n_local, n_planes, skinny=False,
                                      bm=bm, bk=bk, bn=bn)):
            return GemmPlan("fused", bm, bk, bn, source="roofline")
        return GemmPlan("xla", bm, bk, bn, source="default")
    plan = _tuned_plan(m, k, n_local, mode, rank, n_planes)
    if plan is not None:
        return plan
    if not compat.is_tpu_backend():
        return GemmPlan("xla", bm, bk, bn, source="default")
    return _roofline_plan(m, k, n_local, n_planes, bm, bk, bn)


def _tuned_plan(m: int, k: int, n: int, mode: str, rank: int,
                n_planes: int) -> GemmPlan | None:
    """Autotune-cache hit -> GemmPlan, re-validated against the CURRENT
    admission model (a tuned fused entry that no longer fits the budget —
    e.g. after a kernel edit — is ignored, not trusted)."""
    import jax

    from repro.kernels import autotune

    hit = autotune.lookup(m, k, n, mode, rank,
                          backend=jax.default_backend(),
                          vmem_budget=vmem_budget_bytes())
    if hit is None:
        return None
    if hit.path == "fused":
        bm = m if hit.skinny else hit.bm
        if not _fused_admissible(m, k, n, n_planes, skinny=hit.skinny,
                                 bm=bm, bk=hit.bk, bn=hit.bn):
            return None
        return GemmPlan("fused", bm, hit.bk, hit.bn, hit.unroll,
                        hit.skinny, source="tuned")
    from repro.kernels import approx_qgemm as qk
    bm, bk, bn = qk.choose_blocks(m, k, n)
    return GemmPlan(hit.path, bm, bk, bn, source="tuned")


def _roofline_plan(m: int, k: int, n: int, n_planes: int,
                   bm: int, bk: int, bn: int) -> GemmPlan:
    """On-TPU, no measurement: the roofline model's predicted winner at
    the local chip's published peaks (a chip kind without an entry in
    `roofline.analysis.CHIP_PEAKS` raises)."""
    import jax

    from repro.kernels import approx_qgemm as qk
    from repro.roofline import analysis as rfa

    peaks = rfa.chip_peaks(jax.devices()[0].device_kind)

    skinny = m <= qk.SKINNY_MAX_M
    if skinny:
        sbk, sbn = qk.choose_skinny_blocks(k, n)
        fbm, fbk, fbn = m, sbk, sbn
    else:
        fbm, fbk, fbn = bm, bk, bn
    if not _fused_admissible(m, k, n, n_planes, skinny=skinny,
                             bm=fbm, bk=fbk, bn=fbn):
        return GemmPlan("xla", bm, bk, bn, source="roofline")
    winner, _ = rfa.predicted_gemm_winner(m, k, n, n_planes, peaks=peaks,
                                          bm=fbm, bk=fbk, bn=fbn,
                                          skinny=skinny)
    if winner == "fused":
        return GemmPlan("fused", fbm, fbk, fbn, skinny=skinny,
                        source="roofline")
    return GemmPlan(winner, bm, bk, bn, source="roofline")


def use_pallas_gemm(policy: str | None, *, m: int, k: int, n: int,
                    n_planes: int = 1, tp: int = 1) -> bool:
    """Should this (m, k, n) approximate GEMM with `n_planes` operand planes
    run on a Pallas kernel?  Back-compat boolean view of the three-way
    `choose_gemm_path` plan (fused OR stacked -> True).

    Under `tp`-way tensor parallelism the kernel runs per shard (via
    shard_map, kernels/ops.approx_qgemm_tp), so both the minimum-tile
    check and the VMEM budget apply to the SHARD-LOCAL shape
    (m, k, n/tp) — a GEMM whose fused working set busts VMEM globally can
    still run fused when each die's slice fits; one that doesn't falls
    back to XLA per-shard."""
    rank = max(n_planes - 1, 0)
    mode = "lowrank" if rank else "exact"
    return choose_gemm_path(policy, m=m, k=k, n=n, mode=mode, rank=rank,
                            n_planes=n_planes, tp=tp).use_pallas


def use_pallas_attention(policy: str | None, *, seq: int,
                         head_dim: int) -> bool:
    """Same decision for flash attention (kv-blocked kernel vs the XLA
    blockwise custom-VJP twin in models/attention.py)."""
    p = resolve(policy)
    if p == "xla":
        return False
    if p == "pallas":
        return True
    return compat.is_tpu_backend() and seq >= MIN_DIM and head_dim >= 64
