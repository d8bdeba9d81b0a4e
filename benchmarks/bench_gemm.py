"""GEMM data-path benchmark: fused vs stacked vs XLA approximate GEMM, plus
the serving weight-plane cache — emits a structured `BENCH_gemm.json` so the
GEMM perf trajectory rides alongside `BENCH_serving.json`.

  PYTHONPATH=src python benchmarks/bench_gemm.py            # full shapes
  PYTHONPATH=src python benchmarks/bench_gemm.py --smoke    # CI
  PYTHONPATH=src python benchmarks/bench_gemm.py --autotune # + tile tuning

`--autotune` tile-tunes the fused path (kernels/autotune.py candidates)
and records this bench's own per-path medians into the tuning cache, so
the `dispatch` decision stamped per mode is the measured argmin and the
check_schema.py `chosen_us <= 1.05x best-of-three` gate is deterministic.
A decode-shaped sweep (m = 1..32) times the skinny-M kernel against the
prefill-shaped fused tile and XLA at every decode batch size.

CPU (interpret-mode) timings are indicative only; the load-bearing numbers
are the STRUCTURAL ones, which hold on any backend:

  * est_hbm_bytes — operand bytes each path materializes through HBM.  The
    stacked path writes+reads `(R+1)x` operand copies (`build_stacks`); the
    fused kernel reads the raw operands once and maps them in-register.
  * builds_stacks — jaxpr inspection: the fused path must contain NO
    (P, M, K)-shaped int8 intermediate for P > 1.
  * weight_cache — per-call µs of the fresh-quantize forward vs the
    prepared-weights forward (the serving engine's decode configuration).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx import gemm as G
from repro.core import multipliers as mm, netlist as nl
from repro.kernels import approx_qgemm as qk
from repro.kernels import autotune, dispatch, ops, ref


def _time(fn, *args, reps: int) -> float:
    """Per-call µs: compile rep, one untimed warm-up rep (first post-compile
    call still pays allocator/first-touch costs), then median of `reps`."""
    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))  # warm-up
    samples = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    h = len(samples) // 2
    return samples[h] if len(samples) % 2 else \
        0.5 * (samples[h - 1] + samples[h])


def est_hbm_bytes(m: int, k: int, n: int, planes: int, fused: bool) -> int:
    """Operand bytes materialized through HBM for one (m, k, n) GEMM.

    stacked: build_stacks reads the raw operands once and WRITES planes x
    (MK + KN) int8 stacks; the kernel then READS them all back, and writes
    the f32 output.  fused: the kernel reads the raw operands and the
    (R, 256) tables once, and writes the output."""
    operands = m * k + k * n
    out = 4 * m * n
    if fused:
        tables = 2 * 256 * max(planes - 1, 0)
        return operands + tables + out
    return operands + 2 * planes * operands + out


def _jaxpr_builds_stacks(fn, a, b, planes: int) -> bool:
    """Does the traced computation materialize a (P, ~M, ~K) int8 stack?"""
    if planes <= 1:
        return False
    jaxpr = jax.make_jaxpr(fn)(a, b)

    def scan(jx) -> bool:
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                if (aval.dtype == jnp.int8 and len(aval.shape) == 3
                        and aval.shape[0] == planes):
                    return True
            for sub in eqn.params.values():
                for j in jax.tree_util.tree_leaves(
                        sub, is_leaf=lambda x: hasattr(x, "jaxpr")):
                    if hasattr(j, "jaxpr") and scan(j.jaxpr):
                        return True
        return False

    return scan(jaxpr.jaxpr)


def _tune_fused(spec, m: int, k: int, n: int, reps: int,
                a, b) -> tuple[float, autotune.Candidate]:
    """Time the roofline-pruned fused tile candidates with the bench's own
    timer; (best µs, best candidate)."""
    cands = autotune.candidate_plans(
        m, k, n, spec.n_planes, vmem_budget=dispatch.vmem_budget_bytes())
    if not cands:
        cands = [autotune.Candidate(*qk.choose_blocks(m, k, n))]
    best = None
    for c in cands:
        f = jax.jit(lambda x, y, s=spec, c=c: ops.approx_qgemm(
            x, y, s, bm=None if c.skinny else c.bm, bk=c.bk, bn=c.bn,
            unroll=c.unroll, skinny=c.skinny))
        us = _time(f, a, b, reps=reps)
        if best is None or us < best[0]:
            best = (us, c)
    return best


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--k", type=int, default=512)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_gemm.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / single rep (CI); explicit "
                         "--m/--k/--n/--reps still win")
    ap.add_argument("--autotune", action="store_true",
                    help="tile-tune the fused path per mode and feed this "
                         "bench's own medians into the autotune cache "
                         "($REPRO_TUNING_CACHE), so the recorded dispatch "
                         "decision is the measured argmin")
    args = ap.parse_args(argv)
    if args.smoke:
        defaults = {"m": 256, "k": 512, "n": 256, "reps": 3}
        smoke = {"m": 128, "k": 160, "n": 128, "reps": 1}  # odd K: tail
        for name, val in smoke.items():
            if getattr(args, name) == defaults[name]:
                setattr(args, name, val)

    m, k, n = args.m, args.k, args.n
    rng = np.random.default_rng(args.seed)
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    mask = rng.random(len(nl.bw8().prunable_gates())) < 0.03
    pruned = mm.pruned(mask, name="bench_pruned")

    cases = [
        ("exact", G.from_multiplier(mm.exact_multiplier())),
        ("trunc2x2", G.from_multiplier(mm.truncated(2, 2))),
        ("lowrank_r1", G.from_multiplier(pruned, rank=1)),
        ("lowrank_r2", G.from_multiplier(pruned, rank=2)),
        ("lowrank_r4", G.from_multiplier(pruned, rank=4)),
        ("lowrank_r8", G.from_multiplier(pruned, rank=8)),
    ]

    us_oracle = _time(
        jax.jit(lambda x, y: ref.lut_matmul(x, y, jnp.asarray(pruned.lut))),
        a, b, reps=args.reps)

    default_blocks = dict(zip(("bm", "bk", "bn"), qk.choose_blocks(m, k, n)))
    modes = []
    builds_fused = []
    builds_stacked = []
    for name, spec in cases:
        planes = spec.n_planes
        rank = spec.rank if spec.mode == "lowrank" else 0
        f_fused = jax.jit(lambda x, y, s=spec: ops.approx_qgemm(x, y, s))
        f_stack = jax.jit(
            lambda x, y, s=spec: ops.approx_qgemm(x, y, s, fused=False))
        f_xla = jax.jit(lambda x, y, s=spec: G.approx_qgemm(x, y, s))
        us_fused = _time(f_fused, a, b, reps=args.reps)
        us_stacked = _time(f_stack, a, b, reps=args.reps)
        us_xla = _time(f_xla, a, b, reps=args.reps)
        tuned = None
        if args.autotune:
            us_tuned, cand = _tune_fused(spec, m, k, n, args.reps, a, b)
            us_fused = min(us_fused, us_tuned)
            tuned = {"blocks": {"bm": cand.bm, "bk": cand.bk, "bn": cand.bn,
                                "unroll": cand.unroll,
                                "skinny": cand.skinny},
                     "default_blocks": default_blocks,
                     "us_tuned": us_tuned}
        us = {"fused": us_fused, "stacked": us_stacked, "xla": us_xla}
        if args.autotune:
            # The cache entry's per-path medians ARE this bench's numbers,
            # so the dispatch decision below is the measured argmin by
            # construction (the <= 1.05x best-of-three gate in
            # check_schema.py cannot flake on a noisy runner).
            autotune.record_winner(m, k, n, spec.mode, rank, us,
                                   fused_plan=cand)
        plan = dispatch.choose_gemm_path(spec.policy, m=m, k=k, n=n,
                                         mode=spec.mode, rank=rank,
                                         n_planes=planes)
        bytes_fused = est_hbm_bytes(m, k, n, planes, fused=True)
        bytes_stacked = est_hbm_bytes(m, k, n, planes, fused=False)
        if planes > 1:
            builds_fused.append(_jaxpr_builds_stacks(f_fused, a, b, planes))
            builds_stacked.append(_jaxpr_builds_stacks(f_stack, a, b, planes))
        modes.append({
            "name": name,
            "mode": spec.mode,
            "rank": spec.rank,
            "planes": planes,
            "residual_nmed": float(spec.residual_nmed),
            "us": us,
            "dispatch": plan.as_dict(),
            "chosen_us": us.get(plan.path, us["xla"]),
            "tuned": tuned,
            "est_hbm_bytes": {"fused": bytes_fused, "stacked": bytes_stacked},
            "hbm_reduction": bytes_stacked / bytes_fused,
            "fused_vs_stacked_speedup": us_stacked / max(us_fused, 1e-9),
        })

    # --- weight-plane cache: fresh-quantize vs prepared forward ----------
    spec_wc = G.from_multiplier(pruned, rank=4)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    pw = jax.tree_util.tree_map(
        jax.block_until_ready, G.prepare_weight(w, spec_wc))
    us_fresh = _time(
        jax.jit(lambda xx, ww: G.approx_matmul(xx, ww, spec_wc)),
        x, w, reps=args.reps)
    us_prep = _time(
        jax.jit(lambda xx, ww: G.approx_matmul_prepared(xx, ww, spec_wc)),
        x, pw, reps=args.reps)

    # --- decode-shaped sweep: skinny-M vs prefill-shaped fused vs XLA ----
    spec_dec = G.from_multiplier(pruned, rank=2)
    dec_points = []
    for m_dec in (1, 2, 4, 8, 16, 32):
        a_dec = jnp.asarray(rng.integers(-128, 128, (m_dec, k)), jnp.int8)
        us_skinny = _time(
            jax.jit(lambda x, y, s=spec_dec: ops.approx_qgemm(
                x, y, s, skinny=True)), a_dec, b, reps=args.reps)
        us_padded = _time(
            jax.jit(lambda x, y, s=spec_dec: ops.approx_qgemm(x, y, s)),
            a_dec, b, reps=args.reps)
        us_xla_dec = _time(
            jax.jit(lambda x, y, s=spec_dec: G.approx_qgemm(x, y, s)),
            a_dec, b, reps=args.reps)
        if args.autotune:
            sbk, sbn = qk.choose_skinny_blocks(k, n)
            best_fused = min(us_skinny, us_padded)
            cand_dec = autotune.Candidate(m_dec, sbk, sbn, 1, True) \
                if us_skinny <= us_padded \
                else autotune.Candidate(*qk.choose_blocks(m_dec, k, n))
            autotune.record_winner(
                m_dec, k, n, spec_dec.mode, spec_dec.rank,
                {"fused": best_fused, "xla": us_xla_dec},
                fused_plan=cand_dec)
        dec_points.append({
            "m": m_dec,
            "us": {"skinny": us_skinny, "fused_padded": us_padded,
                   "xla": us_xla_dec},
            "skinny_speedup_vs_fused": us_padded / max(us_skinny, 1e-9),
        })

    tuning_cache = autotune.load_cache()
    report = {
        "bench": "gemm",
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "shape": {"m": m, "k": k, "n": n},
        "reps": args.reps,
        "lut_oracle_us": us_oracle,
        "modes": modes,
        "decode_sweep": {
            "mult": spec_dec.name,
            "mode": spec_dec.mode,
            "rank": spec_dec.rank,
            "k": k,
            "n": n,
            "points": dec_points,
        },
        "tuning": {
            "autotuned": args.autotune,
            "cache_path": autotune.cache_path(),
            "kernel_version": qk.KERNEL_VERSION,
            "entries": len(tuning_cache.get("entries", {})),
        },
        "structural": {
            "fused_builds_stacks": any(builds_fused),
            "stacked_builds_stacks": all(builds_stacked),
        },
        "weight_cache": {
            "mult": spec_wc.name,
            "rank": spec_wc.rank,
            "us_fresh": us_fresh,
            "us_prepared": us_prep,
            "hit_speedup": us_fresh / max(us_prep, 1e-9),
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    for mo in modes:
        print(f"[bench_gemm] {mo['name']:<11} planes={mo['planes']} "
              f"fused {mo['us']['fused']:9.1f}us  "
              f"stacked {mo['us']['stacked']:9.1f}us  "
              f"xla {mo['us']['xla']:9.1f}us  "
              f"-> {mo['dispatch']['path']} ({mo['dispatch']['source']})  "
              f"hbm x{mo['hbm_reduction']:.2f} less")
    for pt in dec_points:
        print(f"[bench_gemm] decode m={pt['m']:<3} "
              f"skinny {pt['us']['skinny']:9.1f}us  "
              f"padded-fused {pt['us']['fused_padded']:9.1f}us  "
              f"xla {pt['us']['xla']:9.1f}us  "
              f"(skinny x{pt['skinny_speedup_vs_fused']:.2f})")
    wc = report["weight_cache"]
    print(f"[bench_gemm] weight-cache ({wc['mult']} r{wc['rank']}): "
          f"fresh {wc['us_fresh']:.1f}us -> prepared {wc['us_prepared']:.1f}us "
          f"({wc['hit_speedup']:.2f}x) -> {args.out}")
    return report


def csv_main() -> list[str]:
    """benchmarks/run.py entry: smoke shapes to a temp file (the cwd
    BENCH_gemm.json artifact is the CLI's, not the suite's), report as
    CSV lines."""
    import os
    import tempfile
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        r = main(["--smoke", "--out", path])
    finally:
        os.unlink(path)
    lines = []
    for mo in r["modes"]:
        lines.append(
            f"gemm_{mo['name']}_fused,{mo['us']['fused']:.1f},"
            f"planes={mo['planes']};hbm_reduction={mo['hbm_reduction']:.2f}")
        lines.append(f"gemm_{mo['name']}_stacked,{mo['us']['stacked']:.1f},"
                     f"planes={mo['planes']}")
    wc = r["weight_cache"]
    lines.append(f"gemm_weight_cache_prepared,{wc['us_prepared']:.1f},"
                 f"hit_speedup={wc['hit_speedup']:.2f}x")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
