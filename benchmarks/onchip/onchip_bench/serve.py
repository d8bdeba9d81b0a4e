"""Drive `repro.serving.Engine` through one cell's traffic.

Set-up builds the engine on weights the configuration's reference file
makes from the seed, then warms up: one request per prefill bucket (and
a sampled one, where the mix samples) compiles every program the window
can use, and the traffic itself runs for the cell's `warmup_s` so that
occupancy is steady when the window opens.  Requests are submitted once
their due time has passed; every time is taken from the due time, so a
late generator or a stalled step counts.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np

from onchip_bench import work
from onchip_bench.traffic import Item
from onchip_bench.xtrace import STEP_SPAN

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


@dataclasses.dataclass
class Req:
    item: Item
    due: float = 0.0            # perf_counter seconds
    submitted: float = 0.0
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Step:
    start: float
    end: float
    flops: float
    decode_bytes: float


class Recorder:
    """Token times from the engine's streaming callback, work per step,
    and compile events by phase."""

    def __init__(self, sizes: dict):
        self.sizes = sizes
        self.reqs: dict[str, Req] = {}
        self.steps: list[Step] = []
        self.compiles = {"backend": 0, "trace": 0}
        self.gc_pauses: list[float] = []
        self._gc_start = 0.0
        self.counting = False
        self._flops = 0.0
        self._contexts: list[int] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self.counting:
            self.gc_pauses.append(time.perf_counter() - self._gc_start)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if not self.counting:
            return
        if event == COMPILE_EVENT:
            self.compiles["backend"] += 1
        elif event == TRACE_EVENT:
            self.compiles["trace"] += 1

    def on_token(self, rid: str, token: int) -> None:
        r = self.reqs[rid]
        r.times.append(time.perf_counter())
        k = len(r.tokens)
        r.tokens.append(int(token))
        plen = len(r.item.prompt)
        if k == 0:
            self._flops += work.prefill_flops(self.sizes, plen)
        else:
            ctx = plen + k
            self._flops += work.decode_flops(self.sizes, ctx)
            self._contexts.append(ctx)
        if len(r.tokens) >= r.item.max_new:
            r.done = True

    def step(self, engine) -> None:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            engine.step()
        t1 = time.perf_counter()
        ctx = self._contexts
        self.steps.append(Step(
            t0, t1, self._flops,
            work.decode_step_bytes(self.sizes, ctx) if ctx else 0.0))
        self._flops, self._contexts = 0.0, []


def build_engine(conf: dict, cell: dict, params, seed32: int, rec: Recorder):
    """The engine under test, on the given weights."""
    from repro.configs import get_config
    from repro.serving import Engine
    cfg = get_config(conf["model"], **conf["config"])
    return Engine(cfg, params=params, capacity=cell["capacity"],
                  max_len=cell["max_len"],
                  prefill_buckets=tuple(cell["prefill_buckets"]),
                  seed=seed32, on_token=rec.on_token)


def prepare(conf: dict, cell: dict, mix: dict, ref_mod, seed: int):
    """Weights from the seed, the engine on them, and the compile warm-up.
    Returns (params, recorder, engine)."""
    from onchip_bench.traffic import rng_for
    sizes = conf["config"]
    seed32 = int(rng_for(seed, "weights").integers(1, 2**31 - 1))
    params = ref_mod.make_weights(sizes, seed32)
    rec = Recorder(sizes)
    engine = build_engine(conf, cell, params, seed32, rec)
    compile_warmup(engine, rec, cell, mix, sizes["vocab"])
    return params, rec, engine


def _request(engine, rid: str, item: Item):
    from repro.serving.types import Request, SamplingParams
    return Request(rid, item.prompt.tolist(), SamplingParams(
        temperature=item.temperature, top_k=item.top_k,
        max_new_tokens=item.max_new, seed=item.seed), arrival=engine.tick)


def compile_warmup(engine, rec: Recorder, cell: dict, mix: dict,
                   vocab: int) -> None:
    """Run one greedy request per prefill bucket at the bucket's full
    length, and a sampled one where the mix samples, so that every program
    the window uses compiles."""
    rng = np.random.default_rng(0)
    buckets = cell["prefill_buckets"]
    kinds = [(b, 0.0, 0) for b in buckets]
    if mix.get("sampled_share", 0.0) > 0:
        kinds.append((buckets[0], mix["temperature"], mix.get("top_k", 0)))
    for n, (b, temperature, top_k) in enumerate(kinds):
        it = Item(-1, 0.0, rng.integers(0, vocab, b).astype(np.int32), 2,
                  temperature, top_k, 1 + n)
        rid = f"compile-{n}"
        rec.reqs[rid] = Req(it)
        engine.submit(_request(engine, rid, it))
    while engine.n_queued or engine.n_active:
        rec.step(engine)


def serve(engine, rec: Recorder, items: list[Item], *, warmup_s: float,
          seconds: float, on_window_start=None, on_step=None,
          prefix: str = "r") -> dict:
    """Warm-up traffic, then the window.  Returns the window's bounds and
    the requests."""
    t_base = time.perf_counter()
    t0 = t_base + warmup_s
    t_end = t0 + seconds
    reqs = []
    for it in items:
        r = Req(it, due=t0 + it.due_s)
        rec.reqs[f"{prefix}{it.index}"] = r
        reqs.append(r)
    i, n = 0, len(reqs)
    started = False
    while True:
        now = time.perf_counter()
        if not started and now >= t0:
            started = True
            rec.counting = True
            if on_window_start is not None:
                on_window_start()
        if now >= t_end:
            break
        while i < n and reqs[i].due <= now:
            reqs[i].submitted = time.perf_counter()
            engine.submit(_request(engine, f"{prefix}{reqs[i].item.index}",
                                   reqs[i].item))
            i += 1
        if engine.n_active or engine.n_queued:
            rec.step(engine)
            if on_step is not None:
                on_step()
        else:
            nxt = min(reqs[i].due if i < n else t_end,
                      t_end if started else t0)
            time.sleep(max(0.0, nxt - now))
    rec.counting = False
    return {"t0": t0, "t_end": t_end, "reqs": reqs}


def window_requests(win: dict) -> tuple[list, list]:
    """(served, finished): the requests that got a token in the window,
    and those of them finished in it."""
    t0, t_end = win["t0"], win["t_end"]
    served = [r for r in win["reqs"]
              if any(t0 <= t < t_end for t in r.times)]
    return served, [r for r in served if r.done and r.times[-1] < t_end]
