"""The reduction from a profiler trace to the per-layer metrics: its
pieces by hand, and the whole on a small trace recorded on a TPU v5e."""

import pathlib

import pytest

from onchip_bench import spec, xtrace

TRACE = pathlib.Path(__file__).parent / "testdata" / "tiny_serve.xplane.pb"


def test_merge_and_overlap():
    m = xtrace._merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)]
    assert xtrace._overlap(m, 2, 6) == 2
    assert xtrace._overlap(m, 8, 9) == 0


def test_self_time_subtracts_nested_ops():
    evs = [(0, 10, "while"), (1, 3, "body"), (4, 8, "body"),
           (12, 13, "sort")]
    st = xtrace._self_times(evs)
    assert st == {"while": 4, "body": 6, "sort": 1}


def test_names():
    assert xtrace.module_name("jit_decode_impl(1444191175)") == "decode_impl"
    text = ("%while.1 = (s32[]{:T(128)}, bf16[64,1,1024]{2,0,1:T(8,128)(2,1)}"
            ") while(s32[] %a), condition=%c")
    assert xtrace.op_name(text) == "while.1 (s32[], bf16[64,1,1024])"
    assert xtrace.op_name("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)") \
        == "fusion.2 f32[8]"


def test_recorded_trace():
    r = xtrace.reduce(str(TRACE))
    # the recorded run: 23 engine steps, 8 admissions, in about one second
    assert r["window_s"] == pytest.approx(1.014035107)
    assert r["busy_s"] == pytest.approx(0.001573206)
    assert len(r["step_host_s"]) == 23
    assert sum(r["step_host_s"]) == pytest.approx(0.205513645)
    assert r["device_ops"][0][0] == \
        "dynamic-slice_select_fusion.4 bf16[1,1,2,16]"
    mods = r["modules"]
    assert mods["decode_impl"]["total_s"] == pytest.approx(0.001348025)
    # every step of the recorded run decoded once
    assert mods["decode_impl"]["calls"] == len(r["step_host_s"])
    assert mods["wrapped"]["calls"] >= 1          # prefills
    assert mods["_insert_impl"]["calls"] == mods["wrapped"]["calls"]
    assert all(h >= 0 for h in r["step_host_s"])
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    ops = [s for _, s in r["device_ops"]]
    assert ops == sorted(ops, reverse=True) and sum(ops) <= r["busy_s"]
    record = {"trace": r, "traced_steps": {"flops": 1e9,
                                           "decode_bytes": 1e6},
              "peaks": spec.load_json(spec.HERE / "peaks.json")[
                  "TPU v5 lite"]}
    idle = spec.reader("idle_share.offline")(record)
    assert idle == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    dec = spec.reader("decode_step_ms.offline")(record)
    assert dec == pytest.approx(mods["decode_impl"]["total_s"]
                                / mods["decode_impl"]["calls"] * 1e3)
    admit = spec.reader("admit_device_ms.offline")(record)
    assert admit > 0
    host = spec.reader("host_ms_per_step.offline")(record)
    assert host > 0
    assert 0 < spec.reader("decode_hbm_share.offline")(record) < 100
    assert 0 < spec.reader("mfu.offline")(record) < 100
    none = {"trace": None, "traced_steps": None}
    for name in ("idle_share.offline", "decode_step_ms.offline",
                 "admit_device_ms.offline", "host_ms_per_step.offline",
                 "mfu.offline", "decode_hbm_share.offline"):
        assert spec.reader(name)(none) is None
