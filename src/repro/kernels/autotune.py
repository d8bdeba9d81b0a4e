"""Roofline-guided tile autotuner for the approximate-GEMM kernels.

`kernels/dispatch.py` used to pick the fused Pallas path from a hand-made
VMEM admission check alone, at one hand-picked prefill-shaped tile —
BENCH_gemm.json showed that losing to XLA/stacked in exact and lowrank-r2
modes despite the fused path's 2-4x HBM reduction.  This module closes the
loop the way the delay model itself is closed (core/calibrate.py anchors
analytical FPS to measured serving): tile choice and path choice come from
MEASUREMENT, with the roofline model pruning the search so only plausibly-
winning candidates are ever timed.

Three pieces:

* **candidate generation** — `candidate_plans` enumerates (bm, bk, bn,
  plane-unroll) tiles for the fused kernel (plus the skinny-M decode
  kernel when m <= SKINNY_MAX_M), filters them through the same
  `fused_vmem_bytes`/`skinny_vmem_bytes` admission dispatch enforces, ranks
  them by the roofline cost model (`roofline/analysis.gemm_path_cost`:
  tiled operand re-reads vs MXU/VPU work per grid step), and keeps the top
  few — the measurement budget goes where the model says it matters.

* **measurement** — `tune_gemm` times each surviving candidate (untimed
  warm-up rep, median of reps) plus the stacked and XLA paths, and records
  the winner.  The measurement function is injectable, so tests drive the
  tuner with a seeded deterministic stub and CI never depends on timer
  noise.

* **a versioned on-disk cache** — winners persist to a JSON file
  (`$REPRO_TUNING_CACHE`, default ./TUNING_gemm.json) keyed by
  (backend, shape-bucket, mode, rank, VMEM budget) and stamped with the
  cache schema and `approx_qgemm.KERNEL_VERSION`.  Any mismatch —
  different backend, budget, kernel schedule, or a corrupt file — makes
  an entry invisible, so dispatch silently falls back to its static
  roofline prediction rather than trusting stale timings.  Writes are
  atomic (tmp + os.replace) and reads tolerate concurrent writers.

`dispatch.choose_gemm_path` consults `lookup()` per GEMM at trace time
(memoized per file mtime — no JSON parse on the hot path), which is what
turns the `auto` policy into a measured three-way fused/stacked/xla
predicted-winner choice.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time

from repro.kernels import approx_qgemm as qk

#: Bump when the cache layout changes (entries under old schemas are
#: discarded wholesale).
CACHE_SCHEMA = 1

_ENV_VAR = "REPRO_TUNING_CACHE"
DEFAULT_CACHE_FILENAME = "TUNING_gemm.json"

PATHS = ("fused", "stacked", "xla")

#: Tile-search axes.  Kept small on purpose: the roofline pruner ranks the
#: cross product, and only MAX_MEASURED_CANDIDATES are ever timed.
BM_CANDIDATES = (128, 256)
BK_CANDIDATES = (128, 256, 512)
BN_CANDIDATES = (128, 256)
UNROLL_CANDIDATES = (1, 2)
MAX_MEASURED_CANDIDATES = 4


def cache_path() -> str:
    """Active tuning-cache path: $REPRO_TUNING_CACHE or ./TUNING_gemm.json."""
    return os.environ.get(_ENV_VAR, "").strip() or DEFAULT_CACHE_FILENAME


def _pow2_ceil(x: int, cap: int) -> int:
    return min(cap, max(1, 1 << max(x - 1, 0).bit_length()))


def shape_bucket(m: int, k: int, n: int) -> str:
    """Shape-bucket key: pow2-ceiling per dim.  Decode GEMMs (m <= 32) get
    per-pow2 m buckets — m=1 and m=32 decode steps genuinely want
    different plans — while K/N bucket coarsely (cap 8192)."""
    return f"m{_pow2_ceil(m, 8192)}_k{_pow2_ceil(k, 8192)}" \
           f"_n{_pow2_ceil(n, 8192)}"


def entry_key(backend: str, bucket: str, mode: str, rank: int,
              vmem_budget: int) -> str:
    return f"{backend}|{bucket}|{mode}|r{rank}|vmem{vmem_budget}"


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One cache entry: the measured winner for a (backend, bucket, mode,
    rank, budget) cell, plus the per-path medians that elected it."""
    path: str                 # "fused" | "stacked" | "xla"
    bm: int                   # fused tile (ignored for path="xla")
    bk: int
    bn: int
    unroll: int = 1
    skinny: bool = False      # fused path ran the skinny-M decode kernel
    us: dict = dataclasses.field(default_factory=dict)  # per-path medians
    source: str = "measured"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunedPlan":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


# ---------------------------------------------------------------------------
# cache persistence
# ---------------------------------------------------------------------------

def _empty_cache() -> dict:
    return {"schema": CACHE_SCHEMA,
            "kernel_version": qk.KERNEL_VERSION, "entries": {}}


def load_cache(path: str | None = None) -> dict:
    """Read the tuning cache; corrupt/missing/stale files yield an empty
    cache (defaults win — never an exception on the dispatch path)."""
    path = path or cache_path()
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return _empty_cache()
    if not isinstance(raw, dict) \
            or raw.get("schema") != CACHE_SCHEMA \
            or raw.get("kernel_version") != qk.KERNEL_VERSION \
            or not isinstance(raw.get("entries"), dict):
        return _empty_cache()
    return raw


def save_cache(cache: dict, path: str | None = None) -> str:
    """Atomic write (tmp + rename): concurrent readers see either the old
    or the new file, never a torn one."""
    path = path or cache_path()
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".tuning.", suffix=".json", dir=d)
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _MEMO.pop(os.path.abspath(path), None)
    return path


def put(plan: TunedPlan, m: int, k: int, n: int, mode: str, rank: int, *,
        backend: str, vmem_budget: int, path: str | None = None) -> str:
    """Merge one winner into the on-disk cache (read-modify-replace)."""
    path = path or cache_path()
    cache = load_cache(path)
    key = entry_key(backend, shape_bucket(m, k, n), mode, rank, vmem_budget)
    cache["entries"][key] = plan.as_dict()
    return save_cache(cache, path)


#: path -> (mtime_ns, entries) — dispatch consults the cache at trace time,
#: so the JSON parse must not be on the per-GEMM path.
_MEMO: dict[str, tuple[int, dict]] = {}


def _entries(path: str) -> dict:
    apath = os.path.abspath(path)
    try:
        mtime = os.stat(apath).st_mtime_ns
    except OSError:
        _MEMO.pop(apath, None)
        return {}
    hit = _MEMO.get(apath)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    entries = load_cache(apath)["entries"]
    _MEMO[apath] = (mtime, entries)
    return entries


def lookup(m: int, k: int, n: int, mode: str, rank: int, *, backend: str,
           vmem_budget: int, path: str | None = None) -> TunedPlan | None:
    """Cache hit for this GEMM's bucket, or None (dispatch falls back to
    the roofline prediction)."""
    entries = _entries(path or cache_path())
    if not entries:
        return None
    key = entry_key(backend, shape_bucket(m, k, n), mode, rank, vmem_budget)
    d = entries.get(key)
    if not isinstance(d, dict) or d.get("path") not in PATHS:
        return None
    try:
        return TunedPlan.from_dict(d)
    except TypeError:
        return None


# ---------------------------------------------------------------------------
# candidate generation (roofline-pruned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    bm: int
    bk: int
    bn: int
    unroll: int = 1
    skinny: bool = False


def _ranking_peaks():
    """Chip peaks the candidate ranking uses: the local chip's on a TPU
    (an unknown kind raises), the v5e target's elsewhere — off-TPU the
    kernels run in interpret mode and the ranking only prunes the search
    for the chip the repository targets."""
    import jax

    from repro.roofline import analysis as rfa
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else rfa.V5E_KIND
    return rfa.chip_peaks(kind)


def candidate_plans(m: int, k: int, n: int, n_planes: int, *,
                    vmem_budget: int,
                    max_candidates: int = MAX_MEASURED_CANDIDATES
                    ) -> list[Candidate]:
    """Fused-kernel tile candidates for an (m, k, n) GEMM, VMEM-admitted
    and ranked by the roofline cost model (best predicted first).

    Plane-unroll only enters the space when there are >= 2 correction
    planes to group; the skinny kernel only when m is decode-shaped."""
    from repro.roofline import analysis as rfa

    peaks = _ranking_peaks()

    unrolls = [u for u in UNROLL_CANDIDATES if u <= max(n_planes - 1, 1)]
    seen: set[Candidate] = set()
    scored: list[tuple[float, Candidate]] = []

    def consider(c: Candidate) -> None:
        if c in seen:
            return
        seen.add(c)
        if c.skinny:
            vmem = qk.skinny_vmem_bytes(m, c.bk, c.bn, n_planes)
        else:
            vmem = qk.fused_vmem_bytes(c.bm, c.bk, c.bn, n_planes)
        if vmem > vmem_budget:
            return
        cost = rfa.gemm_path_cost("fused", m, k, n, n_planes, peaks=peaks,
                                  bm=c.bm, bk=c.bk, bn=c.bn,
                                  skinny=c.skinny)
        scored.append((cost.time_s, c))

    kb = [b for b in BK_CANDIDATES if b < 2 * k] or [BK_CANDIDATES[0]]
    nb = [b for b in BN_CANDIDATES if b < 2 * n] or [BN_CANDIDATES[0]]
    if m <= qk.SKINNY_MAX_M:
        for bk in kb:
            for bn in nb:
                for u in unrolls:
                    consider(Candidate(m, bk, bn, u, skinny=True))
    mb = [b for b in BM_CANDIDATES if b < 2 * m] or [BM_CANDIDATES[0]]
    for bm in mb:
        for bk in kb:
            for bn in nb:
                for u in unrolls:
                    consider(Candidate(bm, bk, bn, u))
    # default blocks always compete (the pre-autotuner behavior is never
    # pruned away, so tuning can only tie or win)
    consider(Candidate(*qk.choose_blocks(m, k, n)))
    scored.sort(key=lambda t: (t[0], dataclasses.astuple(t[1])))
    return [c for _, c in scored[:max_candidates]]


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _median(xs: list[float]) -> float:
    ys = sorted(xs)
    h = len(ys) // 2
    return ys[h] if len(ys) % 2 else 0.5 * (ys[h - 1] + ys[h])


def measure_real(spec, *, reps: int = 3, seed: int = 0):
    """Build the default measurement fn for a MultSpec: times the actual
    kernels (one untimed warm-up/compile rep, then median of `reps`).
    Returns seconds.  The signature is the stub contract for tests:
    measure(path, m, k, n, bm, bk, bn, unroll, skinny) -> float."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.approx import gemm as gemm_mod
    from repro.kernels import ops

    def measure(path: str, m: int, k: int, n: int, bm: int, bk: int,
                bn: int, unroll: int, skinny: bool) -> float:
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        if path == "xla":
            fn = jax.jit(lambda x, y: gemm_mod.approx_qgemm(x, y, spec))
        elif path == "stacked":
            fn = jax.jit(
                lambda x, y: ops.approx_qgemm(x, y, spec, fused=False))
        else:
            fn = jax.jit(lambda x, y: ops.approx_qgemm(
                x, y, spec, bm=bm, bk=bk, bn=bn, unroll=unroll,
                skinny=skinny))
        jax.block_until_ready(fn(a, b))  # warm-up: compile + first touch
        samples = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(a, b))
            samples.append(time.perf_counter() - t0)
        return _median(samples)

    return measure


def tune_gemm(m: int, k: int, n: int, spec=None, *, mode: str | None = None,
              rank: int | None = None, measure=None, reps: int = 3,
              seed: int = 0, backend: str | None = None,
              vmem_budget: int | None = None,
              persist: bool = True, path: str | None = None) -> TunedPlan:
    """Tune one (shape, spec) cell: roofline-pruned fused candidates plus
    the stacked and XLA paths, measured, winner persisted.

    Pass `spec` (a MultSpec) for real measurement, or `mode`/`rank` plus a
    `measure` stub for deterministic testing."""
    from repro.kernels import dispatch

    if spec is not None:
        mode, rank = spec.mode, spec.rank
        n_planes = spec.n_planes
    else:
        assert mode is not None and rank is not None and measure is not None
        n_planes = 1 + rank
    backend = backend or _default_backend()
    vmem_budget = vmem_budget or dispatch.vmem_budget_bytes()
    measure = measure or measure_real(spec, reps=reps, seed=seed)

    cands = candidate_plans(m, k, n, n_planes, vmem_budget=vmem_budget)
    best_fused: tuple[float, Candidate] | None = None
    for c in cands:
        t = measure("fused", m, k, n, c.bm, c.bk, c.bn, c.unroll, c.skinny)
        if best_fused is None or t < best_fused[0]:
            best_fused = (t, c)
    dbm, dbk, dbn = qk.choose_blocks(m, k, n)
    us = {}
    if best_fused is not None:
        us["fused"] = best_fused[0] * 1e6
    us["stacked"] = measure("stacked", m, k, n, dbm, dbk, dbn, 1,
                            False) * 1e6
    us["xla"] = measure("xla", m, k, n, dbm, dbk, dbn, 1, False) * 1e6

    winner = min(us, key=lambda p: (us[p], PATHS.index(p)))
    if winner == "fused":
        c = best_fused[1]
        plan = TunedPlan("fused", c.bm, c.bk, c.bn, c.unroll, c.skinny, us)
    else:
        plan = TunedPlan(winner, dbm, dbk, dbn, 1, False, us)
    if persist:
        put(plan, m, k, n, mode, rank, backend=backend,
            vmem_budget=vmem_budget, path=path)
    return plan


def record_winner(m: int, k: int, n: int, mode: str, rank: int,
                  us: dict, *, fused_plan: Candidate | None = None,
                  backend: str | None = None,
                  vmem_budget: int | None = None,
                  path: str | None = None) -> TunedPlan:
    """Elect + persist a winner from EXTERNALLY measured per-path medians
    (e.g. bench_gemm's own timing loop) — the cache accepts any
    measurement source, it only insists the entry be measurement-backed."""
    from repro.kernels import dispatch

    backend = backend or _default_backend()
    vmem_budget = vmem_budget or dispatch.vmem_budget_bytes()
    winner = min(us, key=lambda p: (us[p], PATHS.index(p)))
    if winner == "fused" and fused_plan is not None:
        c = fused_plan
        plan = TunedPlan("fused", c.bm, c.bk, c.bn, c.unroll, c.skinny,
                         dict(us))
    else:
        bm, bk, bn = qk.choose_blocks(m, k, n)
        plan = TunedPlan(winner, bm, bk, bn, 1, False, dict(us))
    put(plan, m, k, n, mode, rank, backend=backend,
        vmem_budget=vmem_budget, path=path)
    return plan


def _default_backend() -> str:
    import jax
    return jax.default_backend()
