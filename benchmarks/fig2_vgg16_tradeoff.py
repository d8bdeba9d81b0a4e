"""Paper Fig. 2: embodied carbon vs performance for VGG16 at 7 nm.

Reproduces the three curve families:
  * exact baseline accelerators (64..2048 PEs, NVDLA scaling),
  * approx-only variants (same architecture, Pareto multiplier within
    0.5 / 1.0 / 2.0 % accuracy-drop budgets),
  * GA-CDP designs at 30 / 40 / 50 FPS thresholds.
"""

from __future__ import annotations

import time

from repro.core import codesign, ga, multipliers as mm, pareto


def rows() -> list[dict]:
    out = []
    mults = pareto.default_front() + list(mm.static_library().values())
    for e in codesign.sweep_exact_configs("vgg16", 7):
        out.append({"series": "exact", "pes": e.config.num_pes,
                    "fps": round(e.fps, 2), "carbon_g": round(e.carbon_g, 3),
                    "mult": "exact"})
    for drop in (0.5, 1.0, 2.0):
        sweep = codesign.approx_only_sweep("vgg16", 7, drop, mults)
        exact = codesign.sweep_exact_configs("vgg16", 7)
        for e, x in zip(sweep, exact):
            out.append({"series": f"appx_{drop}", "pes": e.config.num_pes,
                        "fps": round(x.fps, 2),
                        "carbon_g": round(e.carbon_g, 3),
                        "mult": e.config.multiplier})
    for fps_min in (30.0, 40.0, 50.0):
        rep = codesign.run_codesign(
            "vgg16", 7, fps_min, 2.0, mults=mults,
            ga_cfg=ga.GAConfig(pop_size=24, generations=12, seed=0))
        out.append({"series": f"ga_cdp_{fps_min:.0f}fps",
                    "pes": rep.ga_cdp.config.num_pes,
                    "fps": round(rep.ga_cdp.fps, 2),
                    "carbon_g": round(rep.ga_cdp.carbon_g, 3),
                    "mult": rep.ga_cdp.config.multiplier,
                    "reduction_vs_exact_pct":
                        round(100 * rep.ga_reduction, 2)})
    return out


def main() -> list[str]:
    t0 = time.time()
    rs = rows()
    us = (time.time() - t0) * 1e6 / max(len(rs), 1)
    lines = []
    for r in rs:
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        lines.append(f"fig2_vgg16_tradeoff,{us:.1f},{derived}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
