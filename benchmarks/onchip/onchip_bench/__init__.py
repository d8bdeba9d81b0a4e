"""On-chip benchmark harness: traffic, serving window, trace reduction,
work counts and the correctness check.  Everything a cell needs beyond
these modules is data found by name under `benchmarks/onchip/`."""
