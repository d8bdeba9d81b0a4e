"""Find a cell's pieces by name: its entry in BENCHMARK.json, its
configuration file, its cell file, its traffic mix and the readers of its
metrics.  Adding a cell, a mix or a metric is adding files and entries."""

from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]     # benchmarks/onchip
ROOT = HERE.parents[1]                                 # the checkout


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "onchip_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, wl: dict, root: pathlib.Path = ROOT) -> dict:
    entry = _by_name(bench["configs"], wl["config"], "config")
    return load_json(root / entry["file"])


def cell(name: str, here: pathlib.Path = HERE) -> dict:
    return load_json(here / "cells" / f"{name}.json")


def mix(name: str, here: pathlib.Path = HERE) -> dict:
    return load_json(here / "mixes" / f"{name}.json")


def reference(conf: dict, here: pathlib.Path = HERE):
    return load_module(here / "configs" / f"{conf['reference']}.py")


def reader(metric: str, here: pathlib.Path = HERE):
    """The `read(record)` function of a metric's reader file."""
    return load_module(here / "metrics" / f"{metric}.py").read


def metrics_for(bench: dict, wl: dict, trace: bool) -> list[dict]:
    """The end-to-end metrics a cell reports (trace 0) or its per-layer
    metrics (trace 1)."""
    e2e = [m for m in bench["end_to_end"]
           if wl["name"] in m.get("workloads", [wl["name"]])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if wl["name"] in m["workloads"] if "workloads" in m
            ] + [m for m in bench["per_layer"]
                 if "workloads" not in m and m["moves"] in moved]
