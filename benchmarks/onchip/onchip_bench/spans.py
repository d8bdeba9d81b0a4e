"""Reduce the serving engine's own spans in a profiler trace.

`repro.serving.engine` runs each phase of `Engine.step` inside a
`jax.profiler.TraceAnnotation` named under the `engine.` prefix, and tags
each admission with counters (`request_id`, `prompt_len`, `bucket`).
They land on the host planes of the trace, on the device planes' clock.
`load` reads them with the device's busy time, and `reduce_lines` gives
for each span name its calls, the calls that carried counters, its wall
time, the device's busy time inside it (the union of `XLA Ops`, as `xtrace` takes it), the
device's idle time inside its self intervals (the parts that no nested
`engine.` span covers), and the sum of each numeric counter; `per_layer`
turns those into per-layer numbers, and `gap_owners` puts each idle gap
of the device under both the engine span and the runtime event that were
open on the host in it.  All times are in seconds, clipped to the traced
window: the span of the benchmark's step spans, as `xtrace.reduce` takes
it by default.

`xtrace.reduce` does not call this, so no metric reader sees these
numbers yet; `trace_spans.py` reduces a cell's traced run with both.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import itertools

from onchip_bench.xtrace import MIN_GAP_S, STEP_SPAN, _merge

PREFIX = "engine."
STEP = "engine.step"
SCHEDULE = "engine.schedule"
ADMIT = "engine.admit"
EMIT = "engine.emit"


def reduce_lines(busy: list[tuple[float, float]],
                 lines: list[list[tuple[float, float, str, dict]]]
                 ) -> dict[str, dict]:
    """Per span name: `calls`, `tagged` (the calls that carried numeric
    counters), `wall_s`, `busy_s`, `self_idle_s` and `meta` (the sums of
    the numeric counters).  `busy` is the device's
    busy intervals, sorted and disjoint; each of `lines` holds one
    thread's spans as (start, end, name, counters), which nest.  Times in
    ns in, seconds out."""
    starts = [s for s, _ in busy]
    cum = [0.0, *itertools.accumulate(e - s for s, e in busy)]

    def busy_to(t):
        i = bisect.bisect_right(starts, t)
        return cum[i] - max(0.0, busy[i - 1][1] - t) if i else 0.0

    def idle(s, e):
        return (e - s) - (busy_to(e) - busy_to(s))

    out: dict[str, dict] = {}
    stack: list[list] = []     # [end, name, start of its open self interval]

    def close():
        end, name, at = stack.pop()
        out[name]["self_idle_s"] += idle(at, end) * 1e-9
        if stack:
            stack[-1][2] = end

    for spans in lines:
        for s, e, name, meta in sorted(spans, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][0] <= s:
                close()
            if stack:
                out[stack[-1][1]]["self_idle_s"] += \
                    idle(stack[-1][2], s) * 1e-9
            rec = out.setdefault(name, {"calls": 0, "tagged": 0,
                                        "wall_s": 0.0, "busy_s": 0.0,
                                        "self_idle_s": 0.0, "meta": {}})
            rec["calls"] += 1
            rec["wall_s"] += (e - s) * 1e-9
            rec["busy_s"] += (busy_to(e) - busy_to(s)) * 1e-9
            nums = {k: v for k, v in meta.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
            rec["tagged"] += bool(nums)
            for k, v in nums.items():
                rec["meta"][k] = rec["meta"].get(k, 0) + v
            stack.append([e, name, s])
        while stack:
            close()
    return out


def load(path: str):
    """(busy, engine lines, other host events, window) of one trace file,
    clipped to the window: the device's busy intervals (sorted, disjoint),
    each host thread's engine spans as (start, end, name, counters), every
    other host event as (start, end, name), and (t0, t1).  Times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev_name = "/device:TPU:0"
    ops, lines, host, steps = [], [], [], []
    for plane in pd.planes:
        if plane.name == dev_name:
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for ln in plane.lines if ln.name == "XLA Ops"
                   for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans = []
                for e in ln.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIX):
                        spans.append((e.start_ns, end, e.name,
                                      dict(e.stats)))
                    else:
                        host.append((e.start_ns, end, e.name))
                        if e.name == STEP_SPAN:
                            steps.append((e.start_ns, end))
                lines.append(spans)
    if not ops:
        raise ValueError(f"no operations on {dev_name} in {path}")
    t0_ns = min(steps)[0] if steps else min(s for s, _ in ops)
    t1_ns = max(e for _, e in steps) if steps else max(e for _, e in ops)

    def clip(evs):
        return [(max(ev[0], t0_ns), min(ev[1], t1_ns), *ev[2:]) for ev in evs
                if ev[1] > t0_ns and ev[0] < t1_ns]

    busy = _merge([(s, e) for s, e in clip(ops)])
    return busy, [clip(spans) for spans in lines], clip(host), (t0_ns, t1_ns)


def gap_owners(busy, lines, host, window) -> dict[str, dict[str, float]]:
    """Device idle by (engine span, runtime event): each idle gap longer
    than `xtrace.MIN_GAP_S` goes under the innermost engine span and the
    shortest other host event (not the benchmark's step span) open at its
    middle, as `xtrace` names a gap by the shortest event of all.
    Arguments as `load` returns them."""
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > MIN_GAP_S * 1e9]
    events = sorted([(s, e, n, True) for spans in lines
                     for s, e, n, _ in spans]
                    + [(s, e, n, False) for s, e, n in host
                       if n != STEP_SPAN])
    out: dict[str, dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    open_: list = []            # heap of (end, start, name, is_engine)
    i = 0
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        while i < len(events) and events[i][0] <= mid:
            s, e, n, is_engine = events[i]
            heapq.heappush(open_, (e, s, n, is_engine))
            i += 1
        while open_ and open_[0][0] < mid:
            heapq.heappop(open_)
        best = {True: None, False: None}
        for e, s, n, is_engine in open_:
            if best[is_engine] is None or e - s < best[is_engine][0]:
                best[is_engine] = (e - s, n)
        span = best[True][1] if best[True] else "(no engine span)"
        event = best[False][1] if best[False] else "(no host event)"
        out[span][event] += (ge - gs) * 1e-9
    return {k: dict(v) for k, v in out.items()}


def per_layer(spans: dict[str, dict]) -> dict[str, float]:
    """What the engine spans say of each layer; a number is left out
    where its span is absent.

    - `engine_host_ms`: host time per step, from inside `Engine.step`
      (wall less device busy, per `engine.step`);
    - `schedule_host_ms`: the scheduler's wall time per step;
    - `admit_host_ms`: device idle per admission (wall less busy, over
      the admissions' counted spans: the paged engine's later prefill
      chunks are `engine.admit` spans without counters);
    - `emit_host_ms`: device idle per emit loop, one per decode step;
    - `prefill_pad_share`: % of the prefilled tokens that are padding.
    """
    out = {}
    for key, name, less_busy, per in (
            ("engine_host_ms", STEP, True, "calls"),
            ("schedule_host_ms", SCHEDULE, False, "calls"),
            ("admit_host_ms", ADMIT, True, "tagged"),
            ("emit_host_ms", EMIT, True, "calls")):
        sp = spans.get(name)
        if sp and sp[per]:
            wall = sp["wall_s"] - (sp["busy_s"] if less_busy else 0.0)
            out[key] = wall / sp[per] * 1e3
    meta = spans.get(ADMIT, {}).get("meta", {})
    if meta.get("bucket") and "prompt_len" in meta:
        out["prefill_pad_share"] = \
            (1.0 - meta["prompt_len"] / meta["bucket"]) * 100.0
    return out
