"""The serving engine's profiler spans: a tiny engine served under
`jax.profiler.start_trace`, its trace read back with `ProfileData`.  The
spans must appear nested as `repro.serving.engine` documents them, once
per step, admission and decode step, with the admission counters that
the benchmark's padding metric sums."""

import glob

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.serving import Engine, PagedEngine, Request, SamplingParams
from repro.serving import engine as eng

#: span -> the engine span it runs inside (None: outermost)
PARENT = {
    eng.SPAN_STEP: None,
    eng.SPAN_SCHEDULE: eng.SPAN_STEP,
    eng.SPAN_ADMIT: eng.SPAN_STEP,
    eng.SPAN_ADMIT_PREFILL: eng.SPAN_ADMIT,
    eng.SPAN_ADMIT_INSTALL: eng.SPAN_ADMIT,
    eng.SPAN_ADMIT_FIRST_TOKEN: eng.SPAN_ADMIT,
    eng.SPAN_DECODE: eng.SPAN_STEP,
    eng.SPAN_READBACK: eng.SPAN_STEP,
    eng.SPAN_EMIT: eng.SPAN_STEP,
}
BUCKETS = (8, 16, 32)
LENS = (5, 8, 13, 30, 3)


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.reduced(configs.get_config("tinyllama-1.1b"))
    return cfg, api.init_params(cfg, jax.random.key(0))


def _traced(engine, tmp_path):
    """Submit one request per length in LENS, then step the engine until
    it is idle, under the profiler.  Returns the number of steps and the
    engine spans as (start, end, name, stats), one list per host
    thread."""
    rng = np.random.default_rng(0)
    for i, n in enumerate(LENS):
        engine.submit(Request(f"r{i}", rng.integers(1, 256, n).tolist(),
                              SamplingParams(max_new_tokens=3)))
    driven = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        while engine.n_queued or engine.n_active:
            engine.step()
            driven += 1
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats)) for e in ln.events
                       if e.name.startswith("engine.")]
                if evs:
                    lines.append(evs)
    return driven, lines


def _parents(evs):
    """(name, innermost enclosing span's name) for each span of one
    thread."""
    out, stack = [], []
    for s, e, name, _ in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out.append((name, stack[-1][1] if stack else None))
        stack.append((e, name))
    return out


def test_engine_spans_nest_and_count(tiny, tmp_path):
    cfg, params = tiny
    engine = Engine(cfg, params, capacity=2, max_len=48,
                    prefill_buckets=BUCKETS, seed=0)
    steps, lines = _traced(engine, tmp_path)
    assert len(lines) == 1                  # the engine runs on one thread
    evs = lines[0]
    pairs = _parents(evs)
    assert {name for name, _ in pairs} == set(PARENT)
    for name, parent in pairs:
        assert parent == PARENT[name], (name, parent)
    calls = {name: sum(1 for n, _ in pairs if n == name) for name in PARENT}
    st = engine.stats()
    assert calls[eng.SPAN_STEP] == steps == st["ticks"]
    assert calls[eng.SPAN_SCHEDULE] == steps
    assert calls[eng.SPAN_ADMIT] == st["admitted"] == len(LENS)
    for name in (eng.SPAN_ADMIT_PREFILL, eng.SPAN_ADMIT_INSTALL,
                 eng.SPAN_ADMIT_FIRST_TOKEN):
        assert calls[name] == st["admitted"]
    for name in (eng.SPAN_DECODE, eng.SPAN_READBACK, eng.SPAN_EMIT):
        assert calls[name] == st["decode_steps"]
    admits = sorted((s, m) for s, _, n, m in evs if n == eng.SPAN_ADMIT)
    # admission is FIFO: the spans' order is the submission order
    assert [m["request_id"] for _, m in admits] == \
        [f"r{i}" for i in range(len(LENS))]
    assert [m["prompt_len"] for _, m in admits] == list(LENS)
    assert [m["bucket"] for _, m in admits] == \
        [next(b for b in BUCKETS if b >= n) for n in LENS]
    for s, e, name, m in evs:
        assert e >= s
        if name != eng.SPAN_ADMIT:
            assert m == {}                  # counters only on admissions


def test_untraced_engine_serves_the_same_tokens(tiny, tmp_path):
    """The spans change nothing the engine serves."""
    cfg, params = tiny
    out = []
    for traced in (False, True):
        engine = Engine(cfg, params, capacity=2, max_len=48,
                        prefill_buckets=BUCKETS, seed=0)
        if traced:
            _traced(engine, tmp_path)
        else:
            rng = np.random.default_rng(0)
            for i, n in enumerate(LENS):
                engine.submit(Request(
                    f"r{i}", rng.integers(1, 256, n).tolist(),
                    SamplingParams(max_new_tokens=3)))
            engine.run_until_complete()
        out.append(sorted((c.request_id, c.tokens)
                          for c in engine.completions))
    assert out[0] == out[1]


def test_paged_engine_emits_the_same_spans(tiny, tmp_path):
    """The paged engine's spans nest as the slot engine's do, on the
    whole-prompt path and on the chunked one, where each later chunk is
    an `engine.admit` of its own and only the first carries counters."""
    cfg, params = tiny
    engine = PagedEngine(cfg, params, capacity=2, max_len=48,
                         prefill_buckets=BUCKETS, seed=0, page_size=8,
                         prefill_chunk=12)
    steps, lines = _traced(engine, tmp_path)
    assert len(lines) == 1
    evs = lines[0]
    pairs = _parents(evs)
    assert {name for name, _ in pairs} == set(PARENT)
    for name, parent in pairs:
        assert parent == PARENT[name], (name, parent)
    calls = {name: sum(1 for n, _ in pairs if n == name) for name in PARENT}
    st = engine.stats()
    assert calls[eng.SPAN_STEP] == steps == st["ticks"]
    assert calls[eng.SPAN_DECODE] == st["decode_steps"] > 0
    # prompts over 12 tokens prefill in chunks of 12, the last one padded
    chunked = [n for n in LENS if n > 12]
    assert chunked and len(chunked) < len(LENS)
    n_chunks = st["paged"]["chunked"]["chunks"]
    assert n_chunks == sum(-(-n // 12) for n in chunked)
    prefills = st["admitted"] + n_chunks - len(chunked)
    assert calls[eng.SPAN_ADMIT] == calls[eng.SPAN_ADMIT_PREFILL] == prefills
    assert calls[eng.SPAN_ADMIT_INSTALL] == st["admitted"] == len(LENS)
    assert calls[eng.SPAN_ADMIT_FIRST_TOKEN] == st["admitted"]
    tagged = [m for _, _, n, m in evs if n == eng.SPAN_ADMIT and m]
    assert len(tagged) == st["admitted"]
    admits = {m["request_id"]: m for m in tagged}
    for i, n in enumerate(LENS):
        bucket = (-(-n // 12) * 12 if n > 12
                  else next(b for b in BUCKETS if b >= n))
        assert (admits[f"r{i}"]["prompt_len"],
                admits[f"r{i}"]["bucket"]) == (n, bucket)
    for s, e, name, m in evs:
        if name != eng.SPAN_ADMIT:
            assert m == {}
