"""Paper Fig. 2 embedded table: approx-only carbon-footprint reduction (%)
— average and peak over the 64..2048-PE sweep — per technology node
(7/14/28 nm) x accuracy-drop budget (0.5/1.0/2.0 %).

Paper's claimed bands: avg 2.83-8.44 %, peak 4.60-12.75 %.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import codesign, multipliers as mm, pareto

PAPER = {  # (node, drop) -> (avg, peak) from the paper's table
    (7, 0.5): (2.83, 5.78), (7, 1.0): (4.49, 9.18), (7, 2.0): (5.17, 10.56),
    (14, 0.5): (5.58, 8.87), (14, 1.0): (6.90, 10.98),
    (14, 2.0): (8.02, 12.75),
    (28, 0.5): (3.33, 4.60), (28, 1.0): (5.71, 7.87), (28, 2.0): (8.44, 11.65),
}


def rows() -> list[dict]:
    mults = pareto.default_front() + list(mm.static_library().values())
    out = []
    for node in (7, 14, 28):
        exact = codesign.sweep_exact_configs("vgg16", node)
        for drop in (0.5, 1.0, 2.0):
            appx = codesign.approx_only_sweep("vgg16", node, drop, mults)
            reds = [100.0 * (1 - a.carbon_g / e.carbon_g)
                    for a, e in zip(appx, exact)]
            pa, pp = PAPER[(node, drop)]
            out.append({
                "node_nm": node, "drop_pct": drop,
                "avg_reduction_pct": round(float(np.mean(reds)), 2),
                "peak_reduction_pct": round(float(np.max(reds)), 2),
                "paper_avg": pa, "paper_peak": pp,
            })
    return out


def main() -> list[str]:
    t0 = time.time()
    rs = rows()
    us = (time.time() - t0) * 1e6 / max(len(rs), 1)
    return [
        "fig2_table_reduction,{:.1f},{}".format(
            us, ";".join(f"{k}={v}" for k, v in r.items()))
        for r in rs
    ]


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
