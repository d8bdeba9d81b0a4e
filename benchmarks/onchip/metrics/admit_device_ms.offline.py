"""Device time of admission per admitted request: the prefill program
(`make_prefill_step`, jitted as `wrapped`) and the arena insert
(`SlotArena._insert_impl`), over the number of prefills (device trace)."""

PREFILL, INSERT = "wrapped", "_insert_impl"


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    pre = tr["modules"].get(PREFILL)
    if not pre or not pre["calls"]:
        return None
    ins = tr["modules"].get(INSERT, {"total_s": 0.0})
    return (pre["total_s"] + ins["total_s"]) / pre["calls"] * 1e3
