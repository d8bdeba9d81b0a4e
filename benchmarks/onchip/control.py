#!/usr/bin/env python3
"""Readings for the limit of a cell's correctness check, on the chip.

    python3 benchmarks/onchip/control.py --workload <name> \
        --seeds 1 2 3 --seconds 51 [--quant int8 fp8]

For each seed, in one process: serve the cell's traffic for a window of
`--seconds` as a run does and draw the same sample of finished greedy
requests.  Then judge it as a run does (`check.judge`, against the cell's
limit), once with the served tokens (the program, whose widest gap over
a dozen seeds is the limit's lower reading) and once for each `--quant`,
with the tokens that the reference computed in that precision puts first
(the controls, whose smallest gap is the upper reading, and which must
come out not correct).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--quant", nargs="+", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    from onchip_bench import spec
    bench = spec.benchmark()
    wl = spec.workload(bench, args.workload)
    conf = spec.config(bench, wl)
    cell = spec.cell(wl["name"])
    mix = spec.mix(wl["traffic"])
    ref_mod = spec.reference(conf)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    if jax.devices()[0].platform == "cpu":
        run.log("control readings are taken on the chip")
        return 2
    from onchip_bench import check, serve, traffic
    limit = cell["limits"]["logit_gap"]
    for seed in args.seeds:
        params, rec, engine = serve.prepare(conf, cell, mix, ref_mod, seed)
        items = traffic.schedule(mix, seed, cell["warmup_s"],
                                 conf["config"]["vocab"])
        win = serve.serve(engine, rec, items, warmup_s=cell["warmup_s"],
                          seconds=args.seconds)
        _, finished = serve.window_requests(win)
        sample = check.sample(finished, seed, cell["check_requests"])
        del engine
        gc.collect()
        ref = ref_mod.Reference(conf["config"], cell["max_len"])
        line = {"workload": wl["name"], "seed": seed, "limit": limit,
                "requests": len(sample),
                "tokens": sum(len(r.tokens) for r in sample)}
        for name in [None, *args.quant]:
            gap, correct = check.judge(ref, params, sample, limit, name)
            line[name or "served"] = {"gap": gap, "correct": correct}
        print(json.dumps(line), flush=True)
        del params, rec, sample
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
