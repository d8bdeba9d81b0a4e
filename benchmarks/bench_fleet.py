"""Fleet benchmark: carbon-aware routing + failover under a time-varying
grid, with metering on — the operational half of the total-carbon story.

  PYTHONPATH=src python benchmarks/bench_fleet.py --smoke
  PYTHONPATH=src python benchmarks/bench_fleet.py --requests 24 \
      --regions us-west,eu-west --kill 6
  PYTHONPATH=src python benchmarks/bench_fleet.py --smoke --chaos

Replays a Poisson trace through a 2+ replica `repro.fleet` router
(diurnal per-region grid traces by default), kills one replica mid-trace
(`--kill`, on by default — the failover invariants are part of the
schema), and writes BENCH_fleet.json: per-replica energy/CO2e, routed
shares, the low-carbon routing share, SLO attainment, and the zero-lost
failover accounting.  `--sanitize-retrace` watches every replica
engine's jitted phases under the repro.analysis compile budgets.

`--chaos` additionally runs two deterministic chaos campaigns on
tier-laddered fleets (`--tiers`) and records a `chaos` section:

  * a seeded `ChaosSchedule.random(--chaos-seed)` campaign (transient
    crashes with recovery, submission-boundary deaths, stragglers, grid
    spikes, bursts) whose invariant checkers — zero lost, exactly-once,
    meter conservation, deadline accounting, monotone tiers — must all
    pass;
  * a burst-overload A/B: the same flood with and without the
    `DegradationController`, showing brownout holding p95 TTFT within
    the (tight) `--brownout-slo-ticks` by shifting tokens onto approx
    tiers, then restoring exact after the burst drains.
"""

from __future__ import annotations

import argparse
import dataclasses
import json

from repro import configs
from repro.launch.fleet import build_fleet, poisson_requests, ttft_ticks


def _run_chaos(cfg, args, regions, max_len) -> tuple[dict, bool]:
    """Two deterministic campaigns on tier-laddered fleets; returns the
    `chaos` report section and whether every gate passed."""
    import random

    from repro.fleet.chaos import ChaosCampaign, ChaosSchedule, _p95
    from repro.fleet.router import DegradationConfig, FleetConfig
    from repro.serving import Request, SamplingParams

    tiers = tuple(t.strip() for t in args.tiers.split(",") if t.strip())

    def fresh(slo_ticks, degradation):
        return build_fleet(
            cfg, regions=regions, trace=args.trace, capacity=args.capacity,
            max_len=max_len, seed=args.seed,
            seconds_per_tick=args.seconds_per_tick, tiers=tiers,
            fleet_cfg=FleetConfig(ttft_slo_ticks=slo_ticks,
                                  degradation=degradation))

    # ---- seeded fault campaign: the invariant gauntlet -------------------
    fleet = fresh(args.slo_ticks, DegradationConfig(patience=1))
    trace = [dataclasses.replace(r,
                                 ttft_deadline_ticks=4.0 * args.slo_ticks,
                                 deadline_ticks=8.0 * args.slo_ticks)
             for r in poisson_requests(args.requests, args.prompt_len,
                                       args.gen, cfg.vocab, seed=args.seed)]
    schedule = ChaosSchedule.random(args.chaos_seed,
                                    [r.name for r in fleet.replicas])
    campaign = ChaosCampaign(fleet, trace, schedule).run()

    # ---- brownout A/B: same burst flood with/without the controller ------
    bslo = args.brownout_slo_ticks
    rng = random.Random(args.chaos_seed)
    flood = [Request(request_id=f"burst{i}",
                     tokens=[rng.randrange(1, cfg.vocab)
                             for _ in range(args.prompt_len)],
                     sampling=SamplingParams(max_new_tokens=args.gen),
                     arrival=2.0)
             for i in range(args.brownout_requests)]

    def run_flood(degradation):
        f = fresh(bslo, degradation)
        for r in flood:
            f.submit(r)
        f.run_until_complete()
        for _ in range(48):     # cooldown: let the controller restore exact
            f.step()
        rb = f.stats()["robustness"]
        return {
            # wall-clock TTFT (fleet ticks): degraded tiers run several
            # engine ticks per fleet tick, so only the wall metric can
            # show the brownout holding the SLO
            "ttft_p95_ticks": _p95(list(f.wall_ttft_ticks().values())),
            "tier_occupancy": f.tier_occupancy(),
            "degradation_events": len(rb["degradation_events"]),
            "final_tiers": {r.name: r.engine.tier for r in f.replicas},
        }

    with_ctl = run_flood(DegradationConfig(patience=1))
    without_ctl = run_flood(None)
    brownout = {
        "requests": args.brownout_requests,
        "slo_ticks": bslo,
        "with_controller": with_ctl,
        "without_controller": without_ctl,
        "holds_slo": with_ctl["ttft_p95_ticks"] <= bslo,
        "improves_p95": (with_ctl["ttft_p95_ticks"]
                         < without_ctl["ttft_p95_ticks"]),
        "restored_exact": all(t == tiers[0]
                              for t in with_ctl["final_tiers"].values()),
    }
    section = {
        "seed": args.chaos_seed,
        "tiers": list(tiers),
        "campaign": campaign.to_dict(),
        "brownout": brownout,
    }
    ok = (campaign.ok and brownout["holds_slo"]
          and brownout["improves_p95"] and brownout["restored_exact"])
    return section, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--regions", default="us-west,eu-west")
    ap.add_argument("--trace", default="diurnal",
                    choices=["static", "diurnal"])
    ap.add_argument("--capacity", type=int, default=2)
    ap.add_argument("--slo-ticks", type=float, default=32.0)
    ap.add_argument("--seconds-per-tick", type=float, default=1800.0)
    ap.add_argument("--kill", type=int, default=5,
                    help="inject a replica-0 fault after this many of its "
                         "steps (-1 disables; the schema's failover "
                         "checks need a kill)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace on the reduced config (CI)")
    ap.add_argument("--sanitize-retrace", action="store_true",
                    help="watch every replica engine's jitted phases "
                         "under the repro.analysis compile budgets")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the seeded chaos campaign + brownout "
                         "A/B on tier-laddered fleets and record a "
                         "'chaos' report section")
    ap.add_argument("--chaos-seed", type=int, default=7)
    ap.add_argument("--tiers", default="exact,trunc2x2,trunc4x4",
                    help="comma-separated multiplier tier ladder for the "
                         "chaos fleets (index 0 = most accurate)")
    ap.add_argument("--brownout-requests", type=int, default=24)
    ap.add_argument("--brownout-slo-ticks", type=float, default=24.0,
                    help="tight TTFT SLO for the burst-overload A/B "
                         "(chosen so only the degraded ladder holds it)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.reduced = True
        args.requests = min(args.requests, 12)
        args.gen = min(args.gen, 6)

    cfg = configs.apply_overrides(configs.get_config(args.arch),
                                  reduced=args.reduced)
    regions = tuple(args.regions.split(","))
    max_len = args.prompt_len + args.gen + 8
    fleet = build_fleet(cfg, regions=regions, trace=args.trace,
                        capacity=args.capacity, max_len=max_len,
                        seed=args.seed, ttft_slo_ticks=args.slo_ticks,
                        seconds_per_tick=args.seconds_per_tick)

    sanitizers = {}
    if args.sanitize_retrace:
        # one sanitizer per engine: watch names are per-engine-phase, so
        # replicas must not share a sanitizer
        from repro.analysis.retrace import instrument_engine
        for rep in fleet.replicas:
            sanitizers[rep.name] = instrument_engine(rep.engine)

    for r in poisson_requests(args.requests, args.prompt_len, args.gen,
                              cfg.vocab, seed=args.seed):
        fleet.submit(r)
    killed = []
    if args.kill >= 0:
        fleet.replicas[0].inject_fault(at_step=args.kill)
        killed.append(fleet.replicas[0].name)
    comps = fleet.run_until_complete()
    s = fleet.stats()

    tt = sorted(ttft_ticks(c) for c in comps)
    p95 = tt[min(int(0.95 * len(tt)), len(tt) - 1)] if tt else 0
    routed_share = {name: n / max(s["submitted"] + s["requeued"], 1)
                    for name, n in s["routed"].items()}
    report = {
        "bench": "fleet",
        "arch": cfg.name,
        "reduced": args.reduced,
        "trace": {
            "requests": args.requests, "regions": list(regions),
            "grid": args.trace, "capacity": args.capacity,
            "prompt_len": args.prompt_len, "gen": args.gen,
            "seconds_per_tick": args.seconds_per_tick,
            "seed": args.seed, "ticks": s["ticks"],
        },
        "replicas": s["replicas"],
        "routing": {
            "low_carbon_share": s["low_carbon_share"],
            "routed": s["routed"],
            "routed_share": routed_share,
        },
        "failover": {
            "killed": killed,
            "kill_at_step": args.kill,
            "requeued": s["requeued"],
            "requeue_events": s["requeue_events"],
            "lost": len(s["lost"]),
        },
        "slo": {
            "ttft_slo_ticks": args.slo_ticks,
            "ttft_p50_ticks": tt[len(tt) // 2] if tt else 0,
            "ttft_p95_ticks": p95,
            "ok": p95 <= args.slo_ticks,
        },
        "totals": {
            "submitted": s["submitted"], "completed": s["completed"],
            **s["totals"],
        },
    }
    chaos_ok = True
    if args.chaos:
        report["chaos"], chaos_ok = _run_chaos(cfg, args, regions, max_len)
    if sanitizers:
        findings = [f for sz in sanitizers.values() for f in sz.findings()]
        report["retrace"] = {
            "ok": not findings,
            "findings": [f.render() for f in findings],
            "watches": {f"{name}/{w}": v
                        for name, sz in sanitizers.items()
                        for w, v in sz.report().items()},
        }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)

    t = report["totals"]
    print(f"[bench_fleet] {len(regions)} replicas ({args.trace} grid), "
          f"{s['submitted']} reqs, kill={killed or 'off'}: "
          f"requeued={s['requeued']} lost={len(s['lost'])}, "
          f"low-carbon share {s['low_carbon_share']:.2f}, "
          f"ttft p95 {p95} ticks (slo {args.slo_ticks:.0f})")
    print(f"[bench_fleet] {t['energy_j']:.2f} J, {t['co2e_g']:.3e} gCO2e, "
          f"{t['co2e_g_per_token']:.3e} g/token -> {args.out}")
    if args.chaos:
        c = report["chaos"]
        camp, brn = c["campaign"], c["brownout"]
        print(f"[bench_fleet] chaos campaign (seed {c['seed']}): "
              f"{'OK' if camp['ok'] else 'VIOLATED'} — "
              f"faults={camp['faults_by_kind']} "
              f"recoveries={camp['recoveries']} "
              f"max_attempt={camp['max_attempt']} lost={camp['lost']}")
        for v in camp["violations"]:
            print(f"[bench_fleet]   violation: {v}")
        wc, wo = brn["with_controller"], brn["without_controller"]
        print(f"[bench_fleet] brownout A/B (slo {brn['slo_ticks']:.0f}): "
              f"p95 {wc['ttft_p95_ticks']:.0f} w/ controller vs "
              f"{wo['ttft_p95_ticks']:.0f} without — "
              f"holds_slo={brn['holds_slo']} "
              f"restored_exact={brn['restored_exact']} "
              f"occupancy={wc['tier_occupancy']}")
    if sanitizers:
        print(f"[bench_fleet] retrace sanitizer: "
              f"{'OK' if report['retrace']['ok'] else 'FAIL'}")
        for msg in report["retrace"]["findings"]:
            print(f"[bench_fleet]   {msg}")
        if not report["retrace"]["ok"]:
            return 1
    if not chaos_ok:
        return 1
    return 0 if not s["lost"] else 1


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
