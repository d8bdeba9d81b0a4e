#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration and traffic;
the files `configs/<config>.json`, `cells/<workload>.json`,
`mixes/<traffic>.json` and `metrics/<metric>.py` beside this script say
the rest.  With `--trace 0` the last line of standard output carries the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read
from a profiler trace of the window's first `TRACE_S` seconds.  Every run
ends with the correctness check (`onchip_bench/check.py`), whose numbers
are the last lines of standard error and the last key of the result.
Without an accelerator, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

#: seconds of the window the traced run records
TRACE_S = 8.0


def log(msg: str) -> None:
    print(f"[onchip] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def main(argv=None, *, root: pathlib.Path = ROOT, here: pathlib.Path = HERE,
         require_chip: bool = True) -> int:
    args = parse(argv)
    from onchip_bench import spec
    bench = spec.benchmark(root)
    wl = spec.workload(bench, args.workload)
    conf = spec.config(bench, wl, root)
    cell = spec.cell(wl["name"], here)
    mix = spec.mix(wl["traffic"], here)
    ref_mod = spec.reference(conf, here)
    wanted = spec.metrics_for(bench, wl, bool(args.trace))
    readers = {m["name"]: spec.reader(m["name"], here) for m in wanted}

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < wl["chips"]):
        log(f"needs {wl['chips']} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    peaks = spec.load_json(here / "peaks.json")
    kind = devices[0].device_kind
    if require_chip and kind not in peaks:
        log(f"no peaks for device kind {kind!r} in peaks.json")
        return 2

    from onchip_bench import check, serve, stats, traffic, xtrace
    sizes = conf["config"]
    params, rec, engine = serve.prepare(conf, cell, mix, ref_mod, args.seed)
    items = traffic.schedule(mix, args.seed, cell["warmup_s"],
                             sizes["vocab"])

    tdir = tempfile.mkdtemp(prefix="onchip-trace-") if args.trace else None
    tr = {"on": False, "start": None, "stop": None}

    def start_trace():
        if tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            tr.update(on=True, start=time.perf_counter())

    def stop_trace():
        if tr["on"]:
            tr.update(on=False, stop=time.perf_counter())
            jax.profiler.stop_trace()

    def maybe_stop():
        if tr["on"] and time.perf_counter() >= tr["start"] + TRACE_S:
            stop_trace()

    win = serve.serve(engine, rec, items, warmup_s=cell["warmup_s"],
                      seconds=args.seconds, on_window_start=start_trace,
                      on_step=maybe_stop)
    stop_trace()
    t0, t_end = win["t0"], win["t_end"]
    setup_s = t0 - T_PROCESS
    dev = device_info(jax, wl["chips"])

    # --- what the window served
    all_times = [t for r in rec.reqs.values() for t in r.times]
    served, finished = serve.window_requests(win)
    # the queue outlasts the window by design: a request still being
    # served at the close has not failed
    attempted, failed = len(served), 0
    late = [r.submitted - r.due for r in served if r.submitted]
    if late:
        log(f"generator lateness over {len(late)} requests: "
            f"p50 {stats.percentile(late, 50) * 1e3:.3f} ms, "
            f"p95 {stats.percentile(late, 95) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms")
    log(f"compiles in the window: {rec.compiles['backend']} "
        f"(traces {rec.compiles['trace']})")
    in_win = [s.end - s.start for s in rec.steps if t0 <= s.start < t_end]
    log(f"steps in the window: {len(in_win)}, longest "
        f"{max(in_win, default=0) * 1e3:.1f} ms; python gc: "
        f"{len(rec.gc_pauses)} collections, longest "
        f"{max(rec.gc_pauses, default=0) * 1e3:.1f} ms")
    log(f"window {args.seconds} s: {attempted} requests, "
        f"{len(finished)} finished, "
        f"{stats.tokens_in(all_times, t0, t_end)} tokens")

    record = {"setup_s": setup_s, "window_s": t_end - t0,
              "tokens_in_window": stats.tokens_in(all_times, t0, t_end),
              "peaks": peaks.get(kind), "trace": None, "traced_steps": None}
    if tdir:
        import glob
        files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        record["trace"] = xtrace.reduce(files[0])
        shutil.rmtree(tdir, ignore_errors=True)
        steps = [s for s in rec.steps
                 if s.start >= tr["start"] and s.end <= tr["stop"]]
        record["traced_steps"] = {
            "flops": sum(s.flops for s in steps),
            "decode_bytes": sum(s.decode_bytes for s in steps)}

    # --- correctness, once the program's state is freed
    sample = check.sample(finished, args.seed, cell["check_requests"])
    del engine
    gc.collect()
    limit = cell["limits"]["logit_gap"]
    gap, correct = check.judge(ref_mod.Reference(sizes, cell["max_len"]),
                               params, sample, limit)
    n_tok = sum(len(r.tokens) for r in sample)
    log(f"check: {len(sample)} greedy requests, {n_tok} served tokens "
        f"against the float32 reference")

    metrics = {}
    for m in wanted:
        value = readers[m["name"]](record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if record["trace"] is not None:
        result["device"]["busy_s"] = record["trace"]["busy_s"]
        result["device"]["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                               "idle_gaps": record["trace"]["idle_gaps"]}
    result["check"] = {"logit_gap": {"value": gap, "limit": limit}}
    log(f"logit_gap {gap!r} limit {limit!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
