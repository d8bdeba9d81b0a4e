"""Roofline-term derivation from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(); collective bytes
are parsed out of the post-SPMD optimized HLO text (operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute).
The peaks come from one table keyed by `jax.Device.device_kind`; a device
that is not in it is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import re


# --- published per-chip peaks, keyed by device_kind ---------------------------

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peak rates of one chip."""
    bf16_flops: float     # FLOP/s, bf16 MXU
    int8_ops: float       # OP/s, int8 MXU
    hbm_bw: float         # bytes/s
    ici_link_bw: float    # bytes/s per inter-chip link


#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip
#: interconnect over 4 links (50 GB/s each).
V5E_KIND = "TPU v5 lite"
CHIP_PEAKS = {
    V5E_KIND: ChipPeaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                        ici_link_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip JAX reports as `device_kind`; raises for a kind
    with no published entry in CHIP_PEAKS."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

# --- approximate-GEMM kernel-path model (consumed by kernels/autotune) -------
#: VPU table-gather throughput (elements/s): the fused kernel's per-plane
#: (256,)-table maps run on the VPU, 8x128 lanes at ~940 MHz.
GATHER_ELEMS_PER_S = 0.9e12
#: Fixed cost per grid step (pipeline bubble + index bookkeeping).
GRID_STEP_OVERHEAD_S = 1.5e-6
#: Fixed per-call launch overhead (dispatch + output touch).
LAUNCH_OVERHEAD_S = {"fused": 5e-6, "stacked": 5e-6, "xla": 2e-6}

GEMM_PATHS = ("fused", "stacked", "xla")


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class GemmPathCost:
    """Roofline terms for one execution path of one approximate GEMM.

    All byte/flop counts follow the tiled-GEMM re-read model: with output
    tiling (bm, bn), the A operand streams from HBM once per N-block column
    and B once per M-block row — the quantity the tile autotuner actually
    trades against VMEM footprint.
    """
    path: str                 # "fused" | "stacked" | "xla"
    mac_ops: float            # int8 MACs across all planes (padded shape)
    hbm_bytes: float          # operand + intermediate + output traffic
    gather_elems: float       # in-kernel VPU table-map element count
    grid_steps: int           # pallas grid size (0 for the XLA path)
    peaks: ChipPeaks

    @property
    def compute_s(self) -> float:
        mxu = 2.0 * self.mac_ops / self.peaks.int8_ops
        vpu = self.gather_elems / GATHER_ELEMS_PER_S
        return mxu + vpu

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def time_s(self) -> float:
        """Roofline time: overlapped compute/memory + fixed overheads."""
        return (max(self.compute_s, self.memory_s)
                + self.grid_steps * GRID_STEP_OVERHEAD_S
                + LAUNCH_OVERHEAD_S[self.path])

    def as_dict(self) -> dict:
        return {"path": self.path, "mac_ops": self.mac_ops,
                "hbm_bytes": self.hbm_bytes,
                "gather_elems": self.gather_elems,
                "grid_steps": self.grid_steps, "compute_s": self.compute_s,
                "memory_s": self.memory_s, "time_s": self.time_s}


def gemm_path_cost(path: str, m: int, k: int, n: int, n_planes: int, *,
                   peaks: ChipPeaks, bm: int = 256, bk: int = 512,
                   bn: int = 256, skinny: bool = False) -> GemmPathCost:
    """Roofline terms for an (m, k, n) approximate GEMM with `n_planes`
    operand planes on `path` at tile (bm, bk, bn), on a chip with `peaks`.

    `skinny=True` models the decode-specialized kernel: the whole (un-
    padded) M rides in every grid step, so a batch-of-8 decode GEMM does
    8 rows of MXU work instead of a 128-row padded tile."""
    assert path in GEMM_PATHS, path
    r = max(n_planes - 1, 0)
    if path == "xla":
        # XLA runs the plane matmuls from HBM-resident mapped operands:
        # the table-map pass reads the raw operands and writes R mapped
        # copies, each plane matmul re-reads its operands, and the f32
        # accumulator is updated per correction plane.
        mapped = r * (m * k + k * n)
        traffic = (m * k + k * n) + 2 * mapped + n_planes * (m * k + k * n) \
            + (1 + 2 * r) * 4 * m * n
        return GemmPathCost(path, m * k * n * n_planes, traffic, 0.0, 0,
                            peaks)
    kp, np_ = _ceil_to(k, bk), _ceil_to(n, bn)
    if skinny:
        mp, grid_m = m, 1
    else:
        mp = _ceil_to(m, bm)
        grid_m = mp // bm
    grid = grid_m * (np_ // bn) * (kp // bk)
    mac = float(mp) * kp * np_ * n_planes
    # tiled re-reads: A once per N-block column, B once per M-block row
    a_reads = mp * kp * (np_ // bn)
    b_reads = kp * np_ * grid_m
    out = 4 * mp * np_
    if path == "fused":
        tables = 2 * 256 * r
        gathers = float(r) * grid * (mp // grid_m * bk + bk * bn)
        return GemmPathCost(path, mac, a_reads + b_reads + tables + out,
                            gathers, grid, peaks)
    # stacked: ops.build_stacks writes (and the kernel re-reads) per-plane
    # operand copies through HBM
    stack_build = n_planes * (m * k + k * n) + (m * k + k * n)
    return GemmPathCost(path, mac,
                        stack_build + n_planes * (a_reads + b_reads) + out,
                        0.0, grid, peaks)


def predicted_gemm_winner(m: int, k: int, n: int, n_planes: int, *,
                          peaks: ChipPeaks, bm: int = 256, bk: int = 512,
                          bn: int = 256, skinny: bool = False
                          ) -> tuple[str, dict]:
    """(winner path, per-path predicted seconds) for an approximate GEMM
    on a chip with `peaks`."""
    costs = {p: gemm_path_cost(p, m, k, n, n_planes, peaks=peaks, bm=bm,
                               bk=bk, bn=bn,
                               skinny=skinny and p == "fused").time_s
             for p in GEMM_PATHS}
    return min(costs, key=costs.get), costs

# matches e.g.  f32[16,4096,128]{2,1,0}  or  bf16[]  (scalars)
_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# an HLO instruction line:  %name = TYPE kind(args...)
_INSTR_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^)]*)\)")


def _type_bytes(dtype: str, dims: str) -> int:
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    numel = 1
    if dims:
        for d in dims.split(","):
            numel *= int(d)
    return numel * nbytes


def parse_collectives(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes per collective kind from optimized HLO text."""
    out: dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    for m in _INSTR_RE.finditer(hlo_text):
        kind, args = m.group(1), m.group(2)
        total = 0
        for tm in _TYPE_RE.finditer(args):
            total += _type_bytes(tm.group(1), tm.group(2))
        out[kind] += total
    return out


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    flops: float                   # whole-program HLO flops
    hbm_bytes: float               # whole-program bytes accessed
    collective_bytes: float        # summed collective operand bytes
    collectives: dict              # per-kind bytes
    chips: int
    model_flops: float             # 6*N*D (or inference analogue)
    peaks: ChipPeaks               # the chip the terms are modelled for
    # Pallas-kernel deployment model: traffic of vmem_kernel-tagged scopes
    # (materialized by the XLA-CPU lowering, VMEM-resident in the Mosaic
    # kernel) and the kernel's true HBM I/O to swap in instead.
    tagged_bytes: float = 0.0
    kernel_io_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * self.peaks.bf16_flops)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def hbm_bytes_kernel_adj(self) -> float:
        """HBM bytes with tagged scopes replaced by Pallas-kernel I/O."""
        return max(self.hbm_bytes - self.tagged_bytes, 0.0) + \
            self.kernel_io_bytes

    @property
    def memory_kernel_adj_s(self) -> float:
        return self.hbm_bytes_kernel_adj / (self.chips * self.peaks.hbm_bw)

    @property
    def roofline_fraction_kernel_adj(self) -> float:
        ideal = self.model_flops / (self.chips * self.peaks.bf16_flops)
        worst = max(self.compute_s, self.memory_kernel_adj_s,
                    self.collective_s)
        return ideal / worst if worst > 0 else 0.0

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * self.peaks.ici_link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs: <1 means remat/overhead; >1 means the
        compiler sees fewer flops than the analytic model (e.g. int8)."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the step's roofline-limited time:
        model_flops/(chips*peak) / max(term)."""
        ideal = self.model_flops / (self.chips * self.peaks.bf16_flops)
        worst = max(self.compute_s, self.memory_s, self.collective_s)
        return ideal / worst if worst > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "collectives": dict(self.collectives), "chips": self.chips,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "tagged_bytes": self.tagged_bytes,
            "kernel_io_bytes": self.kernel_io_bytes,
            "memory_kernel_adj_s": self.memory_kernel_adj_s,
            "roofline_fraction_kernel_adj":
                self.roofline_fraction_kernel_adj,
        }


def model_flops_for_cell(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for one step of a cell.

    train:   6 * N_active * tokens          (fwd+bwd)
    prefill: 2 * N_active * tokens
    decode:  2 * N_active * batch           (one token per sequence)
    """
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def kernel_io_bytes_for_cell(cfg, shape) -> float:
    """Analytic HBM I/O of the Pallas attention kernels for one step
    (q/k/v or cache reads + out writes, x passes: fwd / remat / bwd)."""
    if cfg.family == "ssm":
        return 0.0
    b, s = shape.global_batch, shape.seq_len
    hd = cfg.hd
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // 3
        s_kv = min(s, cfg.window)
    else:
        n_attn = cfg.n_layers + cfg.n_enc_layers
        s_kv = s
    if shape.kind == "decode":
        # fused decode attention streams the KV cache once per layer
        cache = 2 * b * s_kv * cfg.n_kv_heads * hd * 2
        return n_attn * cache
    qo = b * s * cfg.n_heads * hd * 2
    kv = 2 * b * s * cfg.n_kv_heads * hd * 2
    passes = 4.0 if shape.kind == "train" else 2.0
    return n_attn * passes * (2 * qo + kv)


def terms_from_compiled(compiled, cfg, shape, chips: int,
                        peaks: ChipPeaks) -> RooflineTerms:
    """Preferred path: the while-aware HLO module analyzer (hlo_parse.py).
    XLA's cost_analysis undercounts scanned layers (bodies counted once) —
    it is recorded in the dry-run JSON for cross-checking only."""
    from repro.roofline import hlo_parse
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    stats = hlo_parse.analyze_module(hlo)
    # the SPMD-partitioned module is per-device; the roofline formulas want
    # whole-program totals (they divide by `chips` again)
    return RooflineTerms(
        flops=stats.flops * chips, hbm_bytes=stats.traffic_bytes * chips,
        collective_bytes=stats.collective_bytes * chips,
        collectives={k: v * chips for k, v in stats.collectives.items()},
        chips=chips, model_flops=model_flops_for_cell(cfg, shape),
        peaks=peaks, tagged_bytes=stats.tagged_traffic_bytes * chips,
        kernel_io_bytes=kernel_io_bytes_for_cell(cfg, shape))


def analytic_memory_per_device(cfg, shape, mesh_shape: dict,
                               accum: int = 1, fsdp: bool | None = None,
                               moment_bytes: float = 8.0) -> dict:
    """TPU-side per-device memory estimate (bytes).

    The CPU-backend compile inflates temp memory by materializing f32 copies
    of bf16 layer-stacked saves (XLA-CPU computes bf16 in f32 and hoists the
    converts); TPUs have native bf16, so this analytic model is the honest
    HBM estimate that accompanies the raw memory_analysis() numbers.
    """
    model_par = mesh_shape.get("model", 1)
    dp = 1
    for ax in ("pod", "data"):
        dp *= mesh_shape.get(ax, 1)
    n = cfg.param_count()
    if fsdp is None:
        fsdp = n >= 20e9
    wshard = model_par * (dp if fsdp else 1)
    params = 2.0 * n / wshard
    grads = 2.0 * n / wshard
    moments = moment_bytes * n / wshard
    out = {"params": params, "grads": 0.0, "opt": 0.0, "activations": 0.0,
           "cache": 0.0, "logits": 0.0}
    if shape.kind == "train":
        mb_local = max(shape.global_batch // dp // accum, 1)
        out["grads"] = grads
        out["opt"] = moments
        out["activations"] = (cfg.n_layers * mb_local * shape.seq_len
                              * cfg.d_model * 2.0)
        out["logits"] = (mb_local * shape.seq_len
                         * max(cfg.vocab // model_par, 1) * 4.0)
    elif shape.kind == "prefill":
        b_local = max(shape.global_batch // dp, 1)
        out["activations"] = (b_local * shape.seq_len * cfg.d_model * 2.0
                              * 4)
        out["cache"] = (cfg.n_layers * b_local * shape.seq_len
                        * cfg.n_kv_heads * cfg.hd * 2 * 2.0)
    else:  # decode
        b_local = max(shape.global_batch // dp, 1)
        if cfg.family == "ssm":
            d_in = cfg.ssm_expand * cfg.d_model
            h = cfg.ssm_heads or 32
            p = d_in // h
            out["cache"] = cfg.n_layers * b_local * (
                h * p * cfg.ssm_state * 4.0 + 3 * (d_in + 2 * cfg.ssm_state))
        elif cfg.family == "hybrid":
            n_attn = cfg.n_layers // 3
            w = cfg.lru_width or cfg.d_model
            out["cache"] = (n_attn * b_local * cfg.window * cfg.n_kv_heads
                            * cfg.hd * 2 * 2.0
                            + cfg.n_layers * b_local * w * 6.0)
        else:
            kvshard = model_par if (cfg.n_kv_heads * cfg.hd) % model_par \
                == 0 else 1
            out["cache"] = (cfg.n_layers * b_local * shape.seq_len
                            * cfg.n_kv_heads * cfg.hd * 2 * 2.0 / kvshard)
    out["total"] = sum(v for k, v in out.items())
    return out


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        val = getattr(ma, attr, None)
        if val is not None:
            out[attr] = int(val)
    return out
