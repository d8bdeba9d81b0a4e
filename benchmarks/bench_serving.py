"""Serving-engine benchmark: replay a Poisson-ish synthetic arrival trace
through `repro.serving.Engine` and measure throughput + per-request
latency percentiles.

  PYTHONPATH=src python benchmarks/bench_serving.py --smoke
  PYTHONPATH=src python benchmarks/bench_serving.py --arch mamba2-370m \
      --requests 32 --rate 0.25 --capacity 4

Arrivals are exponential inter-arrival times in engine ticks (one decode
step = one tick), so traces are deterministic and replayable; wall-clock
metrics come from the engine's per-request timestamps.  Writes a JSON
report (default BENCH_serving.json) for the bench trajectory; `--smoke`
runs a tiny trace on the reduced config — wired into CI so the engine's
hot path is exercised on every PR.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro import configs
from repro.serving import Engine, Request, SamplingParams


def build_trace(cfg, n_requests: int, rate: float, prompt_lo: int,
                prompt_hi: int, gen_lo: int, gen_hi: int, seed: int,
                mixed_sampling: bool) -> list[Request]:
    """Heterogeneous prompt lengths, arrivals, and sampling params."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / max(rate, 1e-9)))
        n = int(rng.integers(prompt_lo, prompt_hi + 1))
        gen = int(rng.integers(gen_lo, gen_hi + 1))
        if mixed_sampling and i % 3 == 1:
            sp = SamplingParams(temperature=0.8, top_k=16,
                                max_new_tokens=gen, seed=1000 + i)
        elif mixed_sampling and i % 3 == 2:
            sp = SamplingParams(temperature=1.2, max_new_tokens=gen,
                                seed=2000 + i)
        else:
            sp = SamplingParams(max_new_tokens=gen)      # greedy
        reqs.append(Request(f"req{i:03d}",
                            rng.integers(0, cfg.vocab, (n,)).tolist(),
                            sp, arrival=t))
    return reqs


def build_named_trace(name: str, cfg, args) -> list[Request]:
    """Deterministic request sets for the slot-vs-paged comparison."""
    if name == "standard":
        return build_trace(cfg, args.requests, args.rate, args.prompt_min,
                           args.prompt_max, args.gen_min, args.gen_max,
                           args.seed, not args.uniform_sampling)
    rng = np.random.default_rng(args.seed + {"long-prompt": 101,
                                             "shared-prefix": 202,
                                             "burst": 303}[name])
    n = args.requests
    reqs: list[Request] = []

    def sp(i, gen):
        if not args.uniform_sampling and i % 3 == 1:
            return SamplingParams(temperature=0.8, top_k=16,
                                  max_new_tokens=gen, seed=1000 + i)
        return SamplingParams(max_new_tokens=gen)      # greedy

    def prompt(k):
        return rng.integers(0, cfg.vocab, (k,)).tolist()

    def gen():
        return int(rng.integers(args.gen_min, args.gen_max + 1))

    if name == "long-prompt":
        # a few near-max prompts with LONG generations hog slots while a
        # stream of short requests arrives behind them: the whole-slot
        # engine reserves a full max_len row per request, the paged
        # engine admits by actual page need, so shorts queue far less
        long_gen = min(2 * args.gen_max, args.max_len // 4)
        long_lens = [args.max_len - long_gen - 2,
                     args.max_len // 2 - 2]
        t = 0.0
        for i in range(n):
            if i < max(2, n // 4):
                k, g = long_lens[i % len(long_lens)], long_gen
            else:
                k = int(rng.integers(args.prompt_min, args.prompt_min + 3))
                g = gen()
            reqs.append(Request(f"lp{i:03d}", prompt(k), sp(i, g),
                                arrival=t))
            t += float(rng.exponential(1.0 / max(args.rate, 1e-9)))
    elif name == "shared-prefix":
        # request groups share a long system prefix: the paged engine
        # serves the shared pages from the prefix cache (tail-only
        # prefill); the whole-slot engine re-prefills every time
        shared = prompt(args.max_len // 2)
        for i in range(n):
            tail = prompt(int(rng.integers(2, 6)))
            reqs.append(Request(f"sp{i:03d}", shared + tail, sp(i, gen()),
                                arrival=float(i) * 0.5))
    elif name == "burst":
        # everything lands at tick 0: pure admission-queue pressure
        # (mid-length prompts, so the paged pool fits its extra lanes),
        # drained faster by speculation
        for i in range(n):
            k = int(rng.integers(args.prompt_min,
                                 args.max_len // 2 - 2))
            reqs.append(Request(f"bu{i:03d}", prompt(k), sp(i, gen()),
                                arrival=0.0))
    else:
        raise ValueError(f"unknown trace {name!r}")
    return reqs


def run_comparison(cfg, args, trace_names, mesh):
    """Slot engine vs paged+chunked+speculative engine on shared params
    and identical traces, at EQUAL KV MEMORY: the slot engine reserves
    `capacity` full max_len rows; the paged engine gets the same pool of
    KV tokens as pages and twice the decode lanes, admitting by actual
    page need.  Per-trace latency metrics (wall + deterministic
    tick-space TTFT), token identity (asserted — the differential
    invariant rides in the bench), and aggregate speculation counters."""
    import jax

    from repro.models import api
    from repro.serving import PagedEngine

    params = api.init_params(cfg, jax.random.key(0))
    kv_pool_tokens = args.capacity * args.max_len
    paged_capacity = 2 * args.capacity
    paged_kw = dict(page_size=args.page_size,
                    n_pages=kv_pool_tokens // args.page_size + 1,
                    prefill_chunk=args.prefill_chunk,
                    chunk_budget=(args.chunk_budget
                                  or max(1, args.max_len
                                         // args.prefill_chunk)),
                    spec_k=args.spec_k,
                    draft_tier=args.draft_tier or None)
    out = {"page_size": args.page_size,
           "prefill_chunk": args.prefill_chunk,
           "spec_k": args.spec_k,
           "draft_tier": args.draft_tier or None,
           "slot_capacity": args.capacity,
           "paged_capacity": paged_capacity,
           "kv_pool_tokens": kv_pool_tokens,
           "traces": {}}
    spec_tot = {"proposed": 0, "accepted": 0, "corrections": 0}
    spec_steps = 0
    retrace_ok = True
    for name in trace_names:
        reqs = build_named_trace(name, cfg, args)
        rows, toks = {}, {}
        entry: dict = {"requests": len(reqs)}
        for kind in ("slot", "paged"):
            if kind == "slot":
                eng = Engine(cfg, params, capacity=args.capacity,
                             max_len=args.max_len, seed=args.seed,
                             mesh=mesh)
            else:
                eng = PagedEngine(cfg, params, capacity=paged_capacity,
                                  max_len=args.max_len, seed=args.seed,
                                  mesh=mesh, **paged_kw)
            sanitizer = None
            if args.sanitize_retrace:
                from repro.analysis.retrace import instrument_engine
                sanitizer = instrument_engine(eng)
            # identical warmup protocol for both engines: one multi-chunk
            # greedy request (warms prefill/chunk/draft/verify) plus one
            # sampled request (warms the non-speculative decode path)
            wl = max(args.prompt_min, args.prefill_chunk + 2)
            eng.submit(Request("_warm_g", [1] * wl,
                               SamplingParams(max_new_tokens=2)))
            eng.submit(Request("_warm_s", [1] * args.prompt_min,
                               SamplingParams(temperature=0.8, top_k=8,
                                              max_new_tokens=2, seed=7)))
            eng.run_until_complete()
            base_decode_s = eng.stats()["decode_s"]
            t0 = time.perf_counter()
            start = eng.tick
            for r in reqs:
                eng.submit(dataclasses.replace(
                    r, arrival=r.arrival + start))
            done = [c for c in eng.run_until_complete()
                    if not c.request_id.startswith("_warm")]
            wall = time.perf_counter() - t0
            toks[kind] = {c.request_id: c.tokens for c in done}
            ttft = np.asarray([c.ttft_s for c in done])
            ticks = np.asarray([c.ttft_ticks for c in done])
            lat = np.asarray([c.latency_s for c in done])
            st = eng.stats()
            decode_toks = sum(len(c.tokens) - 1 for c in done)
            rows[kind] = {
                "wall_s": wall,
                "ttft_p50_s": float(np.percentile(ttft, 50)),
                "ttft_p95_s": float(np.percentile(ttft, 95)),
                "ttft_p50_ticks": float(np.percentile(ticks, 50)),
                "ttft_p95_ticks": float(np.percentile(ticks, 95)),
                "latency_p95_s": float(np.percentile(lat, 95)),
                "decode_tokens_per_s": decode_toks / max(
                    st["decode_s"] - base_decode_s, 1e-9),
            }
            if sanitizer is not None:
                finds = sanitizer.findings()
                entry[f"{kind}_retrace_ok"] = not finds
                retrace_ok &= not finds
                for f_ in finds:
                    print(f"[bench_serving]   {name}/{kind}: "
                          f"{f_.render()}")
            if kind == "paged":
                pst = st["paged"]
                entry["alloc"] = {k: pst[k] for k in
                                  ("n_pages", "pages_live", "prefix_hits",
                                   "prefix_hit_tokens", "cow_copies",
                                   "alloc_failures")}
                entry["chunks"] = pst["chunked"]["chunks"]
                if "spec" in st:
                    entry["spec"] = st["spec"]
                    for k in spec_tot:
                        spec_tot[k] += st["spec"][k]
                    spec_steps += st["spec"]["steps"]
        entry["tokens_match"] = toks["slot"] == toks["paged"]
        assert entry["tokens_match"], (
            name, {r: (toks["slot"][r], toks["paged"].get(r))
                   for r in toks["slot"]
                   if toks["slot"][r] != toks["paged"].get(r)})
        entry["slot"], entry["paged"] = rows["slot"], rows["paged"]
        entry["ttft_p95_improvement"] = (
            rows["slot"]["ttft_p95_s"]
            / max(rows["paged"]["ttft_p95_s"], 1e-9))
        entry["ttft_p95_ticks_improvement"] = (
            rows["slot"]["ttft_p95_ticks"]
            / max(rows["paged"]["ttft_p95_ticks"], 1e-9))
        out["traces"][name] = entry
        print(f"[bench_serving] trace {name}: tokens MATCH, ttft p95 "
              f"slot {rows['slot']['ttft_p95_ticks']:.1f} vs paged "
              f"{rows['paged']['ttft_p95_ticks']:.1f} ticks "
              f"({entry['ttft_p95_ticks_improvement']:.1f}x; wall "
              f"{entry['ttft_p95_improvement']:.1f}x), decode "
              f"{rows['slot']['decode_tokens_per_s']:.0f} vs "
              f"{rows['paged']['decode_tokens_per_s']:.0f} tok/s")
    spec = None
    if args.draft_tier:
        spec = {"draft_tier": args.draft_tier, "k": args.spec_k,
                "steps": spec_steps, **spec_tot,
                "acceptance_rate": (spec_tot["accepted"]
                                    / spec_tot["proposed"]
                                    if spec_tot["proposed"] else 0.0)}
    return out, spec, retrace_ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mult", default="")
    ap.add_argument("--kernel-policy", default="",
                    choices=["", "auto", "pallas", "xla"])
    ap.add_argument("--mesh", default="",
                    help="device mesh spec, e.g. 'model=4,data=2' "
                         "(default: $REPRO_MESH, then the host mesh)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per engine tick")
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=48)
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=16)
    ap.add_argument("--uniform-sampling", action="store_true",
                    help="all-greedy trace (default mixes sampling params)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--meter", action="store_true",
                    help="serve with a repro.fleet EnergyMeter attached: "
                         "adds metrics.energy_j / co2e_g / "
                         "co2e_g_per_token and per-request carbon")
    ap.add_argument("--region", default="us-east",
                    help="grid region for --meter intensity")
    ap.add_argument("--trace", action="append", default=None,
                    choices=["standard", "long-prompt", "shared-prefix",
                             "burst"],
                    help="run a slot-vs-paged differential comparison on "
                         "this named trace (repeatable); populates "
                         "report['paged'] / report['spec']")
    ap.add_argument("--paged", action="store_true",
                    help="shorthand for --trace standard")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size (tokens) for the paged engine")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked-prefill chunk length for the paged "
                         "engine")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft length for speculative decoding")
    ap.add_argument("--chunk-budget", type=int, default=0,
                    help="prefill chunks per engine tick (0 = enough "
                         "for one full max_len prompt per tick)")
    ap.add_argument("--draft-tier", default="exact",
                    help="draft tier for speculative decoding in the "
                         "paged comparison ('' disables; a mult name "
                         "like trunc4x4 drafts approximately and "
                         "verifies exactly)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace on the reduced config (CI)")
    ap.add_argument("--sanitize-retrace", action="store_true",
                    help="watch the engine's jitted phases under the "
                         "repro.analysis compile budgets (decode compiles "
                         "once, prefill once per bucket) and fail the "
                         "bench on any violation")
    args = ap.parse_args(argv)

    if args.smoke:
        args.reduced = True
        args.requests = min(args.requests, 8)
        args.capacity = 3
        args.max_len = 64
        args.prompt_min, args.prompt_max = 6, 24
        args.gen_min, args.gen_max = 3, 6
        args.page_size, args.prefill_chunk, args.spec_k = 8, 8, 3

    cfg = configs.apply_overrides(configs.get_config(args.arch),
                                  reduced=args.reduced, mult=args.mult,
                                  kernel_policy=args.kernel_policy)
    reqs = build_trace(cfg, args.requests, args.rate, args.prompt_min,
                       args.prompt_max, args.gen_min, args.gen_max,
                       args.seed, not args.uniform_sampling)

    from repro.launch.mesh import make_mesh_from_spec
    meter = None
    if args.meter:
        from repro.fleet import DevicePowerModel, EnergyMeter, StaticGrid
        meter = EnergyMeter(power=DevicePowerModel(),
                            grid=StaticGrid(args.region))
    mesh = make_mesh_from_spec(args.mesh)
    eng = Engine(cfg, capacity=args.capacity, max_len=args.max_len,
                 seed=args.seed, mesh=mesh, meter=meter)
    sanitizer = None
    if args.sanitize_retrace:
        # budgets count from here, so the warmup compiles are the ONLY
        # compiles allowed: decode exactly once, prefill once per bucket
        from repro.analysis.retrace import instrument_engine
        sanitizer = instrument_engine(eng)
    # warm the jitted prefill/insert/decode once so the trace's latency
    # percentiles measure steady-state serving, not compile time
    eng.submit(Request("_warmup", [1] * args.prompt_min,
                       SamplingParams(max_new_tokens=2)))
    eng.run_until_complete()
    base = eng.stats()

    t0 = time.perf_counter()
    start_tick = eng.tick
    for r in reqs:
        # trace arrivals are relative to the start of the measured run
        eng.submit(dataclasses.replace(r, arrival=r.arrival + start_tick))
    done = [c for c in eng.run_until_complete()
            if c.request_id != "_warmup"]
    wall_s = time.perf_counter() - t0

    assert len(done) == args.requests, (len(done), args.requests)
    stats = eng.stats()
    stats["prefill_s"] -= base["prefill_s"]
    stats["decode_s"] -= base["decode_s"]
    stats["completed"] -= base["completed"]
    stats["queue_wait_ticks_total"] -= base["queue_wait_ticks_total"]
    stats["queue_wait_ticks_mean"] = (
        stats["queue_wait_ticks_total"] / max(stats["completed"], 1))
    stats["evictions"] = {k: v - base["evictions"].get(k, 0)
                          for k, v in stats["evictions"].items()}
    lat = np.asarray([c.latency_s for c in done])
    ttft = np.asarray([c.ttft_s for c in done])
    total_toks = sum(len(c.tokens) for c in done)
    decode_toks = sum(len(c.tokens) - 1 for c in done)
    report = {
        "bench": "serving",
        "arch": cfg.name,
        "family": cfg.family,
        "mult": cfg.mult or "exact",
        "reduced": args.reduced,
        "trace": {
            "requests": args.requests, "rate_per_tick": args.rate,
            "capacity": args.capacity, "max_len": args.max_len,
            "prompt_len": [args.prompt_min, args.prompt_max],
            "gen_len": [args.gen_min, args.gen_max],
            "mixed_sampling": not args.uniform_sampling,
            "seed": args.seed,
        },
        "mesh": stats["mesh"],
        "metrics": {
            "wall_s": wall_s,
            "total_tokens": total_toks,
            "tokens_per_s": total_toks / max(wall_s, 1e-9),
            "decode_tokens_per_s":
                decode_toks / max(stats["decode_s"], 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95)),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "ttft_mean_s": float(np.mean(ttft)),
            "mean_queue_ticks": float(np.mean(
                [c.admitted_tick - c.arrival for c in done])),
        },
        "engine": stats,
    }
    if meter is not None:
        # per-request attribution over the measured trace (the engine's
        # cumulative counters in stats["carbon"] also include warmup)
        energy_j = sum(c.carbon.energy_j for c in done)
        co2e_g = sum(c.carbon.co2e_g for c in done)
        report["metrics"]["energy_j"] = energy_j
        report["metrics"]["co2e_g"] = co2e_g
        report["metrics"]["co2e_g_per_token"] = co2e_g / max(total_toks, 1)
        report["metrics"]["energy_j_per_token"] = (
            energy_j / max(total_toks, 1))
        report["carbon"] = {"region": meter.region,
                            "g_per_kwh": meter.g_per_kwh_now(),
                            "power": stats["carbon"]["power"]}
    trace_names = list(dict.fromkeys(
        (["standard"] if args.paged else []) + (args.trace or [])))
    cmp_retrace_ok = True
    if trace_names:
        paged_rep, spec_rep, cmp_retrace_ok = run_comparison(
            cfg, args, trace_names, mesh)
        report["paged"] = paged_rep
        if spec_rep is not None:
            report["spec"] = spec_rep
    retrace_findings = []
    if sanitizer is not None:
        retrace_findings = sanitizer.findings()
        report["retrace"] = {
            "ok": not retrace_findings,
            "findings": [f.render() for f in retrace_findings],
            "watches": sanitizer.report(),
        }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    m = report["metrics"]
    mesh_str = ",".join(f"{k}={v}" for k, v in report["mesh"].items())
    print(f"[bench_serving] {cfg.name} ({cfg.mult or 'exact'}, "
          f"mesh {mesh_str}): {args.requests} reqs in {wall_s:.2f}s, "
          f"{m['tokens_per_s']:.1f} tok/s "
          f"(decode {m['decode_tokens_per_s']:.1f}), "
          f"latency p50 {m['latency_p50_s'] * 1e3:.0f}ms "
          f"p95 {m['latency_p95_s'] * 1e3:.0f}ms, "
          f"ttft p50 {m['ttft_p50_s'] * 1e3:.0f}ms "
          f"p95 {m['ttft_p95_s'] * 1e3:.0f}ms -> {args.out}")
    if meter is not None:
        print(f"[bench_serving] carbon ({meter.region}): "
              f"{m['energy_j']:.2f} J, {m['co2e_g']:.3e} gCO2e, "
              f"{m['co2e_g_per_token']:.3e} g/token")
    if sanitizer is not None:
        compiles = {n: w["compiles"]
                    for n, w in sanitizer.report().items()}
        print(f"[bench_serving] retrace sanitizer: "
              f"{'OK' if not retrace_findings else 'FAIL'} {compiles}")
        for f_ in retrace_findings:
            print(f"[bench_serving]   {f_.render()}")
        if retrace_findings:
            return 1
    if "spec" in report:
        s = report["spec"]
        print(f"[bench_serving] spec (draft {s['draft_tier']}, "
              f"k={s['k']}): {s['proposed']} proposed, "
              f"{s['accepted']} accepted "
              f"({s['acceptance_rate']:.2f}), "
              f"{s['corrections']} corrections")
    if not cmp_retrace_ok:
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
