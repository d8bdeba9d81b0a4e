"""The decode step against the HBM roofline: the least bytes the traced
decode steps had to read (weights at the tier's least width and the
valid cache, `onchip_bench/work.py`) over their device time and the
chip's HBM bandwidth."""

DECODE = "decode_impl"


def read(record):
    tr, steps = record["trace"], record["traced_steps"]
    mod = tr and tr["modules"].get(DECODE)
    if not mod or not mod["total_s"] or not steps["decode_bytes"]:
        return None
    bw = record["peaks"]["hbm_bytes_s"]
    return steps["decode_bytes"] / (mod["total_s"] * bw) * 100.0
