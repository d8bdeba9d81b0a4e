"""Benchmark harness: one module per paper table/figure + substrate
microbenches.  Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig2       # filter by prefix
"""

from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (bench_accuracy, bench_codesign, bench_gemm,
                        bench_kernels, beyond_lm_codesign,
                        fig2_table_reduction, fig2_vgg16_tradeoff,
                        fig3_cross_models)

SUITES = [
    ("fig2_vgg16_tradeoff", fig2_vgg16_tradeoff.main),
    ("fig2_table_reduction", fig2_table_reduction.main),
    ("fig3_cross_models", fig3_cross_models.main),
    ("bench_gemm", bench_gemm.csv_main),
    ("bench_codesign", bench_codesign.csv_main),
    ("bench_kernels", bench_kernels.main),
    ("bench_accuracy", bench_accuracy.main),
    ("beyond_lm_codesign", beyond_lm_codesign.main),
]


def main() -> int:
    filt = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in SUITES:
        if filt and not name.startswith(filt):
            continue
        t0 = time.time()
        try:
            for line in fn():
                print(line)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failed += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
