"""The whole step against the chip's bf16 peak: the model's own
FLOPs for every prompt and generated token of the traced steps (counted
from the configuration by `onchip_bench/work.py`, whatever GEMM path
serves it) over the traced window times the peak."""


def read(record):
    tr, steps = record["trace"], record["traced_steps"]
    if tr is None or not steps or steps["flops"] <= 0:
        return None
    peak = record["peaks"]["bf16_flops_s"]
    return steps["flops"] / (tr["window_s"] * peak) * 100.0
