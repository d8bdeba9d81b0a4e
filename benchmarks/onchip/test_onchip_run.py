"""Whole runs of tiny cells on the CPU through `run.main`: the result's
keys, a mix and a metric added as files alone and found by name, and the
refusals (no chip; a checkout holding only the benchmark)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from tinykit import HERE, run_cell


@pytest.mark.parametrize("workload", ["tiny-lm.tiny_offline",
                                      "tiny-ssm.tiny_mixed"])
def test_tiny_cell_runs_and_is_correct(tiny_root, capsys, workload):
    root, here = tiny_root
    res = run_cell(root, here, workload, seed=2**31 + 9, capsys=capsys)
    assert list(res)[-1] == "check"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    gap = res["check"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert set(res["metrics"]) == {"setup_s", "output_tok_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert res["device"]["platform"] == "cpu"


def test_a_mix_and_a_metric_added_as_files(tiny_root, capsys):
    root, here = tiny_root
    mix = json.loads((here / "mixes" / "tiny_offline.json").read_text())
    mix["queue"] = 9
    (here / "mixes" / "tiny_short_queue.json").write_text(json.dumps(mix))
    (here / "metrics" / "window_tokens.py").write_text(
        "def read(record):\n"
        "    return record['tokens_in_window']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-ssm.short", "config": "tiny-ssm",
                               "traffic": "tiny_short_queue", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "window_tokens", "unit": "tokens",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny-ssm.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = json.loads((here / "cells" / "tiny-ssm.tiny_offline.json"
                       ).read_text())
    (here / "cells" / "tiny-ssm.short.json").write_text(
        json.dumps(dict(cell, warmup_s=0)))
    res = run_cell(root, here, "tiny-ssm.short", capsys=capsys)
    assert set(res["metrics"]) == {"setup_s", "window_tokens"}
    # the short queue was used: at most its nine requests, each at most
    # 16 tokens long
    assert 0 < res["attempted"] <= 9
    assert 0 < res["metrics"]["window_tokens"]["value"] <= 9 * 16


def test_no_chip_means_no_result(tiny_root, capsys):
    import run
    root, here = tiny_root
    rc = run.main(["--workload", "tiny-lm.tiny_offline", "--seed", "1",
                   "--seconds", "1"], root=root, here=here)
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(HERE.parents[1] / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "mamba2-370m.exact.offline", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
