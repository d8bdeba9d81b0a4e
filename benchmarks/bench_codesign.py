"""Co-design engine benchmark: population-parallel (JAX-batched) GA vs the
sequential numpy reference, plus scenario sweeps with serving-calibrated
delay — emits a structured `BENCH_codesign.json` so the search itself
rides the bench trajectory alongside `BENCH_gemm.json` /
`BENCH_serving.json`.

  PYTHONPATH=src python benchmarks/bench_codesign.py            # full grid
  PYTHONPATH=src python benchmarks/bench_codesign.py --smoke    # CI

Sections of the report:

  * parity    — the batched engine and the numpy twin must select the SAME
                best-CDP design at fixed seeds (per workload).
  * population_eval — wall time to evaluate one `--pop`-genome population
                through each engine (steady state: jit compiled, caches
                warm).  The acceptance bar is a >=10x batched speedup at
                4096 genomes.
  * ga        — end-to-end batched GA wall time at that population size.
  * calibration — measured-vs-analytical throughput anchor
                (`core/calibrate.py`): serving engine trace or fused-GEMM
                kernel timing.
  * scenarios — (node x fab carbon intensity x workload) sweep, each point
                solved by the batched GA, with analytical and calibrated
                CDP, plus the (carbon, delay) frontier of the final GA
                population.
  * total_carbon — the fleet loop closed into co-design: CDP winner vs
                the amortized-embodied + operational winner under an
                `repro.fleet.total.OperationalModel`, per scenario, with
                at least one point where pricing operational carbon
                changes the chosen design.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibrate as calmod
from repro.core import carbon as carbonmod
from repro.core import codesign
from repro.core import ga
from repro.core import ga_batched as gb
from repro.core import multipliers as mm


def _parity_mults() -> list[mm.ApproxMultiplier]:
    return [mm.exact_multiplier(), mm.truncated(1, 1), mm.truncated(2, 2),
            mm.truncated(3, 3)]


def parity_check(workloads: list[str], node_nm: int, seed: int) -> list[dict]:
    out = []
    for wk in workloads:
        mults = _parity_mults()
        rb = gb.run_ga_batched(
            wk, node_nm, 30.0, 2.0, mults=mults,
            cfg=gb.BatchedGAConfig(pop_size=2048, generations=8, seed=seed))
        rn = ga.run_ga(wk, node_nm, 30.0, 2.0, mults=mults,
                       cfg=ga.GAConfig(pop_size=32, generations=16,
                                       seed=seed))
        out.append({
            "workload": wk, "node_nm": node_nm, "seed": seed,
            # the config dataclass does not carry the die gene — compare
            # it explicitly or a (X, 4-die) vs (X, 1-die) split would
            # still read as a MATCH
            "match": (rb.best.config == rn.best.config
                      and rb.best.n_dies == rn.best.n_dies),
            "batched": {"config": str(rb.best.config),
                        "n_dies": rb.best.n_dies, "cdp": rb.best.cdp,
                        "fitness": rb.best.fitness},
            "numpy": {"config": str(rn.best.config),
                      "n_dies": rn.best.n_dies, "cdp": rn.best.cdp,
                      "fitness": rn.best.fitness},
        })
    return out


def population_eval_timing(workload: str, node_nm: int, pop_size: int,
                           seed: int, reps: int) -> dict:
    """Steady-state wall time for one whole-population CDP evaluation."""
    mults = _parity_mults()
    space = gb.build_space(workload, node_nm, 30.0, 2.0, mults=mults)
    rng = np.random.default_rng(seed)
    pop = np.stack([rng.integers(0, n, pop_size)
                    for n in space.gene_sizes], axis=1).astype(np.int32)
    # mask the mult and die genes to the feasible set (what the GA
    # guarantees): infeasible genomes score +inf on both engines, which
    # would turn the relative-error check into inf - inf
    allowed_idx = np.flatnonzero(space.mult_allowed)
    pop[:, gb.MULT_GENE] = allowed_idx[pop[:, gb.MULT_GENE]
                                       % len(allowed_idx)]
    die_ok = space.die_ok[pop[:, 0], pop[:, 1], pop[:, gb.DIE_GENE]]
    pop[:, gb.DIE_GENE] = np.where(die_ok, pop[:, gb.DIE_GENE], 0)

    # numpy reference: warm the workload_perf lru cache, then time
    gcfg = ga.GAConfig()
    def numpy_pass():
        return [ga.evaluate(space.decode(row), workload, node_nm,
                            list(space.mults), 30.0, gcfg) for row in pop]
    numpy_pass()
    t0 = time.perf_counter()
    for _ in range(reps):
        evs = numpy_pass()
    numpy_s = (time.perf_counter() - t0) / reps

    # batched engine: compile, then time
    tables = space.tables()
    jpop = jnp.asarray(pop)
    met = jax.block_until_ready(
        gb.evaluate_population(jpop, tables, node_nm))
    t0 = time.perf_counter()
    for _ in range(reps):
        met = jax.block_until_ready(
            gb.evaluate_population(jpop, tables, node_nm))
    batched_s = (time.perf_counter() - t0) / reps

    # the two evaluators must agree on every genome, not just the argmin
    fit_np = np.array([e.fitness for e in evs])
    rel = np.abs(np.asarray(met["fitness"]) - fit_np) / np.abs(fit_np)
    return {
        "workload": workload, "node_nm": node_nm, "pop_size": pop_size,
        "reps": reps,
        "numpy_s": numpy_s, "batched_s": batched_s,
        "speedup": numpy_s / max(batched_s, 1e-12),
        "max_rel_fitness_err": float(rel.max()),
    }


def ga_timing(workload: str, node_nm: int, pop_size: int, generations: int,
              seed: int) -> dict:
    mults = _parity_mults()
    cfg = gb.BatchedGAConfig(pop_size=pop_size, generations=generations,
                             seed=seed)
    t0 = time.perf_counter()
    res = gb.run_ga_batched(workload, node_nm, 30.0, 2.0, mults=mults,
                            cfg=cfg)
    wall = time.perf_counter() - t0
    return {"workload": workload, "pop_size": pop_size,
            "generations": generations, "wall_s": wall,
            "best_cdp": res.best.cdp,
            "best_config": str(res.best.config),
            "history": res.history}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pop", type=int, default=4096)
    ap.add_argument("--generations", type=int, default=12)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--node", type=int, default=7, choices=(7, 14, 28))
    ap.add_argument("--calibration", default="",
                    choices=["", "none", "serving", "gemm"],
                    help="delay anchor (default: serving; smoke: serving)")
    ap.add_argument("--calibration-mesh", default="",
                    help="serve the calibration trace tensor-parallel, "
                         "e.g. 'model=4' (serving source only; needs that "
                         "many devices)")
    ap.add_argument("--out", default="BENCH_codesign.json")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scenario grid + small GA (CI); the "
                         "4096-genome population timing is kept as-is")
    args = ap.parse_args(argv)

    parity_workloads = ["vgg16", "resnet50"]
    if args.smoke:
        scen = codesign.scenario_grid(
            workloads=("vgg16", "lm_serving"), nodes=(7, 28),
            ci_fabs=(50.0, carbonmod.CI_FAB_G_PER_KWH))
        ga_gens = min(args.generations, 6)
    else:
        scen = codesign.scenario_grid()
        ga_gens = args.generations
    # multi-die pressure points: FPS floors above monolithic (one DRAM
    # channel) reach — where the GA must trade die partitioning against
    # packaging carbon and D2D delay
    scen += codesign.multi_die_scenarios()

    parity = parity_check(parity_workloads, args.node, args.seed)
    pop_eval = population_eval_timing("vgg16", args.node, args.pop,
                                      args.seed, args.reps)
    ga_wall = ga_timing("vgg16", args.node, args.pop, ga_gens, args.seed)

    cal_kwargs = {}
    if args.calibration_mesh and (args.calibration or "serving") == \
            "serving":
        cal_kwargs["mesh_spec"] = args.calibration_mesh
    calib = calmod.get_calibration(args.calibration or "serving",
                                   node_nm=args.node, **cal_kwargs)
    results = codesign.run_scenarios(
        scen, mults=_parity_mults(),
        cfg=gb.BatchedGAConfig(pop_size=512 if args.smoke else args.pop,
                               generations=ga_gens, seed=args.seed),
        calibration=calib)

    scenario_dicts = [r.to_dict() for r in results]
    # multi-die wins: scenarios where the GA selected >1 die AND beat the
    # best monolithic design on the constrained-CDP fitness
    multi_wins = [
        {"scenario": s["scenario"], "n_dies": s["best"]["n_dies"],
         "cdp_constrained": s["best"]["cdp_constrained"],
         "mono_cdp_constrained": s["best_monolithic"]["cdp_constrained"],
         "die_yield": s["best"]["die_yield"],
         "packaging_g": s["best"]["packaging_g"]}
        for s in scenario_dicts
        if s["best"]["n_dies"] > 1 and s["best_monolithic"] is not None
        and s["best"]["cdp_constrained"] <
        s["best_monolithic"]["cdp_constrained"]]

    # total-carbon axis: same pressure-point scenarios, winners compared
    # under a deployment's operational model (grid CI, lifetime, D2D
    # link power) — ground-truth exhaustive search, cheap at this space
    from repro.fleet.total import OperationalModel
    total_carbon = codesign.run_total_carbon(
        codesign.multi_die_scenarios(), OperationalModel(),
        mults=_parity_mults())

    report = {
        "bench": "codesign",
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "seed": args.seed,
        "parity": parity,
        "population_eval": pop_eval,
        "ga": ga_wall,
        "calibration": calib.to_dict(),
        "scenarios": scenario_dicts,
        "multi_die_wins": multi_wins,
        "total_carbon": total_carbon,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)

    for p in parity:
        print(f"[bench_codesign] parity {p['workload']}: "
              f"{'MATCH' if p['match'] else 'MISMATCH'} "
              f"(cdp {p['batched']['cdp']:.4g})")
    print(f"[bench_codesign] population eval P={pop_eval['pop_size']}: "
          f"numpy {pop_eval['numpy_s'] * 1e3:.1f}ms -> batched "
          f"{pop_eval['batched_s'] * 1e3:.2f}ms "
          f"({pop_eval['speedup']:.1f}x)")
    print(f"[bench_codesign] calibration ({calib.source}): scale "
          f"{calib.scale:.3e} ({calib.measured:.3g} measured vs "
          f"{calib.analytical:.3g} analytical {calib.unit})")
    for r in results:
        cal = (f" cdp_cal {r.cdp_calibrated:.3g}"
               if r.cdp_calibrated is not None else "")
        dies = f" x{r.best.n_dies}die" if r.best.n_dies > 1 else ""
        print(f"[bench_codesign] {r.scenario.name}: "
              f"{r.best.config.num_pes} PEs{dies} "
              f"mult={r.best.config.multiplier} "
              f"carbon {-100 * r.ga_reduction:+.1f}% "
              f"cdp {r.best.cdp:.3g}{cal} ({r.wall_s:.1f}s)")
    for w in multi_wins:
        sc = w["scenario"]
        print(f"[bench_codesign] multi-die win: {sc['workload']}@"
              f"{sc['node_nm']}nm fps>={sc['fps_min']:.0f}: "
              f"{w['n_dies']} dies (yield {w['die_yield']:.3f}, "
              f"pkg {w['packaging_g']:.1f} g) cdp* "
              f"{w['cdp_constrained']:.3g} vs mono "
              f"{w['mono_cdp_constrained']:.3g}")
    for s in total_carbon:
        sc = s["scenario"]
        tag = "DIFFERS" if s["differs"] else "same"
        print(f"[bench_codesign] total-carbon {sc['workload']}@"
              f"{sc['node_nm']}nm fps>={sc['fps_min']:.0f} "
              f"ci_use={s['op']['ci_use_g_per_kwh']:.0f}: {tag}; "
              f"total {s['total_winner']['total_g_per_inf']:.3e} vs "
              f"cdp-design {s['cdp_winner']['total_g_per_inf']:.3e} g/inf "
              f"({100 * s['total_reduction']:+.2f}%)")
    print(f"[bench_codesign] -> {args.out}")
    return report


def csv_main() -> list[str]:
    """benchmarks/run.py entry: smoke run to a temp file, report as CSV."""
    import os
    import tempfile
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        r = main(["--smoke", "--calibration", "gemm", "--out", path])
    finally:
        os.unlink(path)
    pe = r["population_eval"]
    lines = [
        f"codesign_pop_eval_numpy,{pe['numpy_s'] * 1e6:.0f},"
        f"pop={pe['pop_size']}",
        f"codesign_pop_eval_batched,{pe['batched_s'] * 1e6:.0f},"
        f"speedup={pe['speedup']:.1f}x",
        f"codesign_ga_batched,{r['ga']['wall_s'] * 1e6:.0f},"
        f"pop={r['ga']['pop_size']};gens={r['ga']['generations']}",
    ]
    for s in r["scenarios"]:
        sc = s["scenario"]
        lines.append(
            f"codesign_{sc['workload']}_{sc['node_nm']}nm_"
            f"ci{sc['ci_fab_g_per_kwh']:.0f},{s['wall_s'] * 1e6:.0f},"
            f"reduction={100 * s['ga_reduction']:.1f}%")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
