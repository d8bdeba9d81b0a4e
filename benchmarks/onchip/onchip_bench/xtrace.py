"""Reduce a profiler trace (`.xplane.pb`) to what the metrics read.

The device plane (`/device:TPU:<i>`) has a line of compiled programs
(`XLA Modules`, one event per launch, named `jit_<function>(<hash>)`) and a
line of operations (`XLA Ops`, named by their HLO text).  Host planes hold
the benchmark's own spans (`jax.profiler.TraceAnnotation`) and the
runtime's activity.  All times below are in seconds, clipped to the traced
window.
"""

from __future__ import annotations

import collections
import re

#: the benchmark's span around one `Engine.step()`
STEP_SPAN = "onchip.step"
#: gaps shorter than this are launch overheads, not idle periods worth naming
MIN_GAP_S = 50e-6


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(merged: list[tuple[float, float]], s: float, e: float) -> float:
    """Length of [s, e) covered by sorted disjoint `merged`."""
    tot = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


def module_name(event_name: str) -> str:
    """`jit_decode_impl(1234)` -> `decode_impl`."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo_text: str) -> str:
    """`%while.1 = (s32[]{:T(128)}, bf16[64,1,1024]{...}) while(...)` ->
    `while.1 (s32[], bf16[64,1,1024])`: the op and its result type."""
    head, _, rest = hlo_text.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head[:96]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    ty = re.sub(r"\{[^{}]*\}", "", rest[:end])
    return f"{head} {ty}"[:96]


def _self_times(events: list[tuple[float, float, str]]
                ) -> dict[str, float]:
    """Exclusive time per name: nested events (a loop's body ops inside
    the loop op) are subtracted from their parent."""
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[list] = []          # [end, name, child_time, dur]

    def pop():
        end, name, child, dur = stack.pop()
        out[name] += max(0.0, dur - child)

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        pop()
    return out


def reduce(path: str, t0_ns: float | None = None, t1_ns: float | None = None,
           device_index: int = 0) -> dict:
    """Reduce one trace file.  `t0_ns`/`t1_ns` bound the window in the
    trace's own clock; by default the window is the span of the
    benchmark's step spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device = host = None
    dev_name = f"/device:TPU:{device_index}"
    host_planes = []
    for plane in pd.planes:
        if plane.name == dev_name:
            device = plane
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
    if device is None:
        raise ValueError(f"no {dev_name} plane in {path}")
    lines = {ln.name: ln for ln in device.lines}
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in lines["XLA Modules"].events] \
        if "XLA Modules" in lines else []
    host = []
    for plane in host_planes:
        for ln in plane.lines:
            host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in ln.events)
    steps = sorted((s, e) for s, e, n in host if n == STEP_SPAN)
    if t0_ns is None:
        t0_ns = steps[0][0] if steps else min(s for s, _, _ in ops)
    if t1_ns is None:
        t1_ns = steps[-1][1] if steps else max(e for _, e, _ in ops)

    def clip(evs):
        return [(max(s, t0_ns), min(e, t1_ns), n) for s, e, n in evs
                if e > t0_ns and s < t1_ns]

    ops, mods, host = clip(ops), clip(mods), clip(host)
    busy = _merge([(s, e) for s, e, _ in ops])
    busy_ns = sum(e - s for s, e in busy)
    window_ns = t1_ns - t0_ns

    modules: dict[str, list[float]] = collections.defaultdict(list)
    for s, e, n in mods:
        modules[module_name(n)].append((e - s) * 1e-9)

    step_host = []
    for s, e in steps:
        if s >= t0_ns and e <= t1_ns:
            step_host.append(((e - s) - _overlap(busy, s, e)) * 1e-9)

    op_self = _self_times([(s, e, op_name(n)) for s, e, n in ops])
    top_ops = sorted(((k, v * 1e-9) for k, v in op_self.items()),
                     key=lambda kv: -kv[1])[:10]

    # idle gaps, each named by the shortest host event covering its middle
    edges = [t0_ns] + [x for iv in busy for x in iv] + [t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > MIN_GAP_S * 1e9]
    host_sorted = sorted(host)
    idle: dict[str, float] = collections.defaultdict(float)
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        best = None
        for s, e, n in host_sorted:
            if s > mid:
                break
            if e >= mid and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        idle[best[2] if best else "(no host event)"] += (ge - gs) * 1e-9
    top_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]

    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "modules": {k: {"calls": len(v), "total_s": sum(v)}
                    for k, v in modules.items()},
        "step_host_s": step_host,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
    }
