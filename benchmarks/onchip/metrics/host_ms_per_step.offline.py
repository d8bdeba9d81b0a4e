"""Host time per engine step: the wall time of the benchmark's span
around `Engine.step()` less the device's busy time inside it, averaged
over the traced steps (device trace)."""


def read(record):
    tr = record["trace"]
    if tr is None or not tr["step_host_s"]:
        return None
    return sum(tr["step_host_s"]) / len(tr["step_host_s"]) * 1e3
