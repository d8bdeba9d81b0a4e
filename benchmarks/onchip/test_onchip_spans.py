"""The reduction of the serving engine's spans (`onchip_bench/spans.py`):
its pieces by hand on synthetic events, and the whole on two small traces
recorded on a TPU v5e, one from before the engine had spans and one from
after."""

import pathlib

import pytest

from onchip_bench import spans, xtrace

TESTDATA = pathlib.Path(__file__).parent / "testdata"
OLD = TESTDATA / "tiny_serve.xplane.pb"
NEW = TESTDATA / "tiny_serve_spans.xplane.pb"


def test_nested_self_idle_busy_and_counters():
    # device busy over [2, 4) and [6, 12); times in ns
    busy = [(2, 4), (6, 12)]
    line = [(0, 20, "engine.step", {}),
            (1, 5, "engine.admit", {"request_id": "a", "prompt_len": 3,
                                    "bucket": 8}),
            (2, 3, "engine.admit.prefill", {}),
            (5, 9, "engine.admit", {"request_id": "b", "prompt_len": 7,
                                    "bucket": 8}),
            (10, 11, "engine.decode", {}),
            (14, 18, "engine.emit", {})]
    r = spans.reduce_lines(busy, [line])
    ns = 1e-9
    step, admit = r["engine.step"], r["engine.admit"]
    assert step["calls"] == 1 and admit["calls"] == 2
    assert step["wall_s"] == pytest.approx(20 * ns)
    assert step["busy_s"] == pytest.approx(8 * ns)
    # step's own intervals: [0,1) [9,10) [11,14) [18,20): idle 1+0+2+2
    assert step["self_idle_s"] == pytest.approx(5 * ns)
    assert admit["wall_s"] == pytest.approx(8 * ns)
    assert admit["busy_s"] == pytest.approx((2 + 3) * ns)
    # admit a minus its prefill: [1,2) idle, [3,4) busy, [4,5) idle;
    # admit b: [5,6) idle, [6,9) busy
    assert admit["self_idle_s"] == pytest.approx(3 * ns)
    assert r["engine.admit.prefill"]["self_idle_s"] == 0
    assert r["engine.decode"]["busy_s"] == pytest.approx(1 * ns)
    assert r["engine.emit"]["self_idle_s"] == pytest.approx(4 * ns)
    # numeric counters are summed, the request id is not
    assert admit["meta"] == {"prompt_len": 10, "bucket": 16}
    assert step["meta"] == {}
    assert (admit["tagged"], step["tagged"]) == (2, 0)


def test_threads_are_reduced_apart():
    busy = [(0, 1)]
    a = [(0, 10, "engine.step", {}), (2, 4, "engine.emit", {})]
    b = [(3, 6, "engine.step", {})]
    r = spans.reduce_lines(busy, [a, b])
    assert r["engine.step"]["calls"] == 2
    # thread a's step owns [0,2) and [4,10) (1 busy); thread b's [3,6)
    assert r["engine.step"]["self_idle_s"] == pytest.approx((7 + 3) * 1e-9)


def test_per_layer():
    assert spans.per_layer({}) == {}
    r = {"engine.step": {"calls": 4, "tagged": 0, "wall_s": 0.2,
                         "busy_s": 0.12, "self_idle_s": 0.0, "meta": {}},
         "engine.schedule": {"calls": 4, "tagged": 0, "wall_s": 0.004,
                             "busy_s": 0.003, "self_idle_s": 0.0,
                             "meta": {}},
         # two admissions, one of them chunked: its later chunk is an
         # admission span without counters
         "engine.admit": {"calls": 3, "tagged": 2, "wall_s": 0.05,
                          "busy_s": 0.03, "self_idle_s": 0.0,
                          "meta": {"prompt_len": 300, "bucket": 400}},
         "engine.emit": {"calls": 4, "tagged": 0, "wall_s": 0.008,
                         "busy_s": 0.0, "self_idle_s": 0.008, "meta": {}}}
    out = spans.per_layer(r)
    assert out == pytest.approx({"engine_host_ms": 20.0,
                                 "schedule_host_ms": 1.0,
                                 "admit_host_ms": 10.0,
                                 "emit_host_ms": 2.0,
                                 "prefill_pad_share": 25.0})
    # admissions whose counters all fell outside the trace: no per-
    # admission number
    r["engine.admit"]["tagged"] = 0
    assert "admit_host_ms" not in spans.per_layer(r)
    del r["engine.admit"]
    assert set(spans.per_layer(r)) == {"engine_host_ms", "schedule_host_ms",
                                       "emit_host_ms"}


def test_gap_owners():
    busy = [(10, 20), (1000, 2000)]
    lines = [[(0, 3000, "engine.step", {}),
              (100, 900, "engine.emit", {})]]
    host = [(0, 3000, xtrace.STEP_SPAN), (200, 800, "Allocate"),
            (150, 850, "Linearize")]
    ms = 1e6
    own = spans.gap_owners([(a * ms, b * ms) for a, b in busy],
                           [[(s * ms, e * ms, n, m) for s, e, n, m in ln]
                            for ln in lines],
                           [(s * ms, e * ms, n) for s, e, n in host],
                           (0, 3000 * ms))
    # gaps [0,10) [20,1000) [2000,3000) ms; the middle one's middle (510)
    # is inside emit and both runtime events, the shortest of which names it
    assert own == {"engine.step": {"(no host event)": pytest.approx(1.01)},
                   "engine.emit": {"Allocate": pytest.approx(0.98)}}


def test_engine_span_names_are_the_engine_s():
    from repro.serving import engine
    assert (spans.STEP, spans.SCHEDULE, spans.ADMIT, spans.EMIT) == (
        engine.SPAN_STEP, engine.SPAN_SCHEDULE, engine.SPAN_ADMIT,
        engine.SPAN_EMIT)
    assert all(getattr(engine, n).startswith(spans.PREFIX)
               for n in dir(engine) if n.startswith("SPAN_"))


def test_trace_from_before_the_spans_has_none():
    busy, lines, host, window = spans.load(str(OLD))
    assert spans.reduce_lines(busy, lines) == {}
    r = xtrace.reduce(str(OLD))
    assert sum(e - s for s, e in busy) * 1e-9 == pytest.approx(r["busy_s"])
    assert (window[1] - window[0]) * 1e-9 == pytest.approx(r["window_s"])


def test_recorded_trace_with_spans():
    from repro.serving import engine
    busy, lines, host, window = spans.load(str(NEW))
    r = spans.reduce_lines(busy, lines)
    x = xtrace.reduce(str(NEW))
    assert set(r) == {getattr(engine, n) for n in dir(engine)
                      if n.startswith("SPAN_")}
    # one engine step inside each of the benchmark's steps, one admission
    # span per prefill program
    assert r[spans.STEP]["calls"] == len(x["step_host_s"])
    assert r[spans.ADMIT]["calls"] == x["modules"]["wrapped"]["calls"]
    assert r[spans.ADMIT]["tagged"] == r[spans.ADMIT]["calls"]
    out = spans.per_layer(r)
    assert set(out) == {"engine_host_ms", "schedule_host_ms",
                        "admit_host_ms", "emit_host_ms", "prefill_pad_share"}
    assert all(v > 0 for v in out.values())
    assert out["prefill_pad_share"] < 100
    # inside and outside: the engine's step is the benchmark's step less
    # the benchmark's own wrapper
    outside = sum(x["step_host_s"]) / len(x["step_host_s"]) * 1e3
    assert 0.85 * outside <= out["engine_host_ms"] <= outside
    idle = x["window_s"] - x["busy_s"]
    self_idle = sum(v["self_idle_s"] for v in r.values())
    assert self_idle <= idle + 1e-9
    # nearly all of the step's idle lies under a named phase
    assert r[spans.STEP]["self_idle_s"] < 0.1 * self_idle
    owners = spans.gap_owners(busy, lines, host, window)
    assert 0 < sum(sum(v.values()) for v in owners.values()) <= idle + 1e-9
    assert xtrace.STEP_SPAN not in dict(x["idle_gaps"])
