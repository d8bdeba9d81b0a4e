"""A copy of the benchmark's tree at a tiny size, for the CPU tests: the
same files, with configurations, cells and mixes small enough for the CPU,
run through `run.main` without the chip."""

from __future__ import annotations

import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent

TINY_CONFIGS = {
    "tiny-lm": {"model": "starcoder2-7b", "reference": "gqa_rope_gelu",
                "config": {"family": "lm", "n_layers": 2, "d_model": 64,
                           "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                           "d_ff": 128, "vocab": 97, "rope_theta": 1e6,
                           "mlp_style": "gelu", "qkv_bias": True,
                           "dtype": "bfloat16", "mult": "exact",
                           "kernel_policy": "auto", "attn_chunk": 16}},
    "tiny-ssm": {"model": "mamba2-370m", "reference": "ssd",
                 "config": {"family": "ssm", "n_layers": 2, "d_model": 64,
                            "n_heads": 1, "n_kv_heads": 1, "d_ff": 0,
                            "vocab": 97, "ssm_state": 16, "ssm_heads": 4,
                            "ssm_head_dim": 32, "ssm_expand": 2,
                            "conv_width": 4, "ssd_chunk": 16,
                            "dtype": "bfloat16", "mult": "exact",
                            "kernel_policy": "auto"}},
}

TINY_CELL = {"capacity": 4, "max_len": 48, "prefill_buckets": [16, 32],
             "warmup_s": 0.5, "check_requests": 3,
             "limits": {"logit_gap": 0.05}}

TINY_MIXES = {
    "tiny_offline": {"arrival": "closed_queue", "queue": 400,
                     "prompt": {"median": 12, "sigma": 0.5, "min": 4,
                                "max": 32},
                     "output": {"median": 6, "sigma": 0.5, "min": 2,
                                "max": 16},
                     "sampled_share": 0.0},
    # a third of the requests sampled: the check judges the greedy rest
    "tiny_mixed": {"arrival": "closed_queue", "queue": 400,
                   "prompt": {"median": 10, "sigma": 0.6, "min": 2,
                              "max": 32},
                   "output": {"median": 5, "sigma": 0.5, "min": 2,
                              "max": 12},
                   "sampled_share": 0.3333, "temperature": 0.8, "top_k": 5},
}


def make_tiny_root(root: pathlib.Path) -> pathlib.Path:
    """A checkout holding the benchmark's files and tiny cells."""
    here = root / "benchmarks" / "onchip"
    for sub in ("metrics", "configs"):
        shutil.copytree(HERE / sub, here / sub)
    shutil.copy(HERE / "peaks.json", here / "peaks.json")
    (here / "cells").mkdir()
    (here / "mixes").mkdir()
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = []
    bench["workloads"] = []
    for name, conf in TINY_CONFIGS.items():
        (here / "configs" / f"{name}.json").write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmarks/onchip/configs/"
                                         f"{name}.json",
                                 "reduced": [], "why": "test"})
    for name, mix in TINY_MIXES.items():
        (here / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    for conf in TINY_CONFIGS:
        for mix in TINY_MIXES:
            wl = f"{conf}.{mix}"
            (here / "cells" / f"{wl}.json").write_text(json.dumps(TINY_CELL))
            bench["workloads"].append({"name": wl, "config": conf,
                                       "traffic": mix, "chips": 1,
                                       "why": "test"})
    wls = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(wls)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return here


def run_cell(root, here, workload: str, seed: int = 7, seconds: float = 2.0,
             trace: int = 0, capsys=None) -> dict:
    """Run a cell through `run.main` on the CPU; returns the result."""
    import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, here=here, require_chip=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
