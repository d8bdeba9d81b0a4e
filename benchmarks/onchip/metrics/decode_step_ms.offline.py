"""Device time of the decode program (`Engine._decode`, jitted as
`decode_impl`) per call (device trace)."""

DECODE = "decode_impl"


def read(record):
    tr = record["trace"]
    mod = tr and tr["modules"].get(DECODE)
    if not mod or not mod["calls"]:
        return None
    return mod["total_s"] / mod["calls"] * 1e3
