"""One generator for every traffic mix.

A mix is a JSON file of parameters (`mixes/<name>.json`).  One arrival
kind exists, ``"closed_queue"``: `queue` requests, all due at the start
of the warm-up, an offline batch job whose queue outlasts the window.

Lengths are the distribution's quantiles at evenly spaced levels (a
log-normal with the given median and sigma, clipped), so every seed
serves the same set of sizes; the seed only orders them and draws token
ids and the sampled share (`sampled_share`, at `temperature` and
`top_k`).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of a schedule; `due_s` is relative to the window start
    (negative: due during the warm-up)."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    temperature: float
    top_k: int
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per (seed, stream); any non-negative int seed works."""
    words = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) & (2**64 - 1), *words])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at levels (i + 0.5) / n of a clipped log-normal."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = np.array([math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
                    for i in range(n)])
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def arrivals(mix: dict, warmup_s: float) -> np.ndarray:
    """Sorted due times (s, relative to the window start)."""
    if mix["arrival"] != "closed_queue":
        raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
    return np.full(mix["queue"], -float(warmup_s))


def schedule(mix: dict, seed: int, warmup_s: float, vocab: int
             ) -> list[Item]:
    """The whole schedule of a run, in due order."""
    due = arrivals(mix, warmup_s)
    n = len(due)
    rng = rng_for(seed, "requests")
    prompts = quantile_lengths(mix["prompt"], n)[rng.permutation(n)]
    outs = quantile_lengths(mix["output"], n)[rng.permutation(n)]
    n_sampled = int(round(mix.get("sampled_share", 0.0) * n))
    sampled = np.zeros(n, bool)
    sampled[rng.permutation(n)[:n_sampled]] = True
    items = []
    for i in range(n):
        items.append(Item(
            index=i, due_s=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompts[i]), dtype=np.int64
                                ).astype(np.int32),
            max_new=int(outs[i]),
            temperature=float(mix["temperature"]) if sampled[i] else 0.0,
            top_k=int(mix.get("top_k", 0)) if sampled[i] else 0,
            seed=int(rng.integers(0, 2**31 - 1))))
    return items
