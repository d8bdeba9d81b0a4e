"""Beyond-paper: the paper's carbon-aware co-design applied to a
transformer (LM) edge workload instead of CNNs.

The dataflow model maps GEMM layers onto the same NVDLA-style loop nest
(core/workloads.py::transformer_block_gemms), so the identical
GA-CDP machinery sizes an edge accelerator for token generation under a
sequences/second constraint.  This is the bridge between the paper's
methodology and the 10 assigned LM architectures: the same co-design loop,
with the JAX framework supplying the accuracy constraint at LM scale."""

from __future__ import annotations

import time

from repro.core import codesign, ga, multipliers as mm, pareto


def rows() -> list[dict]:
    mults = pareto.default_front() + list(mm.static_library().values())
    out = []
    for node in (7, 14, 28):
        # "fps" = sequences (128 tokens) per second for the tiny LM
        rep = codesign.run_codesign(
            "tiny_lm", node, fps_min=50.0, max_accuracy_drop=2.0,
            mults=mults,
            ga_cfg=ga.GAConfig(pop_size=20, generations=10, seed=0))
        out.append({
            "workload": "tiny_lm", "node_nm": node,
            "exact_carbon_g": round(rep.exact.carbon_g, 2),
            "ga_carbon_g": round(rep.ga_cdp.carbon_g, 2),
            "saving_pct": round(100 * rep.ga_reduction, 2),
            "ga_pes": rep.ga_cdp.config.num_pes,
            "ga_mult": rep.ga_cdp.config.multiplier,
            "ga_seq_per_s": round(rep.ga_cdp.fps, 1),
        })
    return out


def main() -> list[str]:
    t0 = time.time()
    rs = rows()
    us = (time.time() - t0) * 1e6 / max(len(rs), 1)
    return [
        "beyond_lm_codesign,{:.1f},{}".format(
            us, ";".join(f"{k}={v}" for k, v in r.items()))
        for r in rs
    ]


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    print("\n".join(main()))
