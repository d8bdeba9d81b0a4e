"""Paper Fig. 3: embodied carbon across DNN models (VGG16/19, ResNet50/152)
x technology nodes, three designs each (normalized to the exact baseline):
  exact @ 30 FPS   |   approx-only (<=2 % drop)   |   GA-CDP.

Paper's claim: GA-CDP saves up to 65 % (VGG16) and 30-70 % across models.
"""

from __future__ import annotations

import time

from repro.core import codesign, ga, multipliers as mm, pareto

MODELS = ("vgg16", "vgg19", "resnet50", "resnet152")


def rows() -> list[dict]:
    mults = pareto.default_front() + list(mm.static_library().values())
    out = []
    for model in MODELS:
        for node in (7, 14, 28):
            rep = codesign.run_codesign(
                model, node, 30.0, 2.0, mults=mults,
                ga_cfg=ga.GAConfig(pop_size=24, generations=12, seed=0))
            base = rep.exact.carbon_g
            out.append({
                "model": model, "node_nm": node,
                "exact_norm": 1.0,
                "approx_norm": round(rep.approx_only.carbon_g / base, 4),
                "ga_cdp_norm": round(rep.ga_cdp.carbon_g / base, 4),
                "ga_saving_pct": round(100 * rep.ga_reduction, 2),
                "exact_pes": rep.exact.config.num_pes,
                "ga_pes": rep.ga_cdp.config.num_pes,
                "ga_mult": rep.ga_cdp.config.multiplier,
                "ga_fps": round(rep.ga_cdp.fps, 1),
            })
    return out


def main() -> list[str]:
    t0 = time.time()
    rs = rows()
    us = (time.time() - t0) * 1e6 / max(len(rs), 1)
    return [
        "fig3_cross_models,{:.1f},{}".format(
            us, ";".join(f"{k}={v}" for k, v in r.items()))
        for r in rs
    ]


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
