"""Roofline HLO analyzer: while-trip scaling, collective parsing, terms."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis, hlo_parse

V5E = analysis.chip_peaks("TPU v5 lite")


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_scale_by_trip_count():
    def body(c, _):
        return c @ c.T @ c, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jnp.ones((256, 128), jnp.bfloat16)
    st = hlo_parse.analyze_module(_compile_text(f, x))
    expect = (2 * 256 * 256 * 128 + 2 * 256 * 128 * 256) * 10
    assert st.flops == pytest.approx(expect, rel=1e-6)


def test_unrolled_matches_scan():
    def f_scan(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=4)
        return y

    def f_unroll(x):
        for _ in range(4):
            x = x @ x
        return x

    x = jnp.ones((128, 128), jnp.float32)
    s1 = hlo_parse.analyze_module(_compile_text(f_scan, x))
    s2 = hlo_parse.analyze_module(_compile_text(f_unroll, x))
    assert s1.flops == pytest.approx(s2.flops, rel=1e-6)


def test_nested_scan_multiplies():
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=3)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jnp.ones((64, 64), jnp.float32)
    st = hlo_parse.analyze_module(_compile_text(f, x))
    assert st.flops == pytest.approx(2 * 64 ** 3 * 15, rel=1e-6)


def test_collective_parsing_synthetic_text():
    txt = """
HloModule m, entry_computation_layout={()->f32[]}

ENTRY %main.1 (p0.1: f32[16,128]) -> f32[16,128] {
  %p0.1 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0.1), replica_groups={}
  %ag = f32[32,128]{1,0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[16,128]{1,0} reduce-scatter(%ag), dimensions={0}
}
"""
    st = hlo_parse.analyze_module(txt, entry="main.1")
    assert st.collectives["all-reduce"] == 16 * 128 * 4
    assert st.collectives["all-gather"] == 16 * 128 * 4
    assert st.collectives["reduce-scatter"] == 32 * 128 * 4


def test_parse_collectives_sums_operand_bytes():
    """analysis.parse_collectives: the regex-only fallback parser (no
    module structure needed) sums operand bytes per collective kind,
    including -start async forms and multi-operand tuples."""
    txt = """
  %ar = f32[16,128]{1,0} all-reduce(%a), replica_groups={}
  %ag = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) all-gather-start(%b, %c)
  %cp = s8[1024]{0} collective-permute(%d)
  %a2a = f32[4,4]{1,0} all-to-all(%e)
  %rs = f32[32,128]{1,0} reduce-scatter(%f)
"""
    # operand types come from the argument list, which in real HLO
    # carries the full typed operands; synthesize that here
    txt = txt.replace("(%a)", "(f32[16,128] %a)")
    txt = txt.replace("(%b, %c)", "(bf16[8,64] %b, bf16[8,64] %c)")
    txt = txt.replace("(%d)", "(s8[1024] %d)")
    txt = txt.replace("(%e)", "(f32[4,4] %e)")
    txt = txt.replace("(%f)", "(f32[32,128] %f)")
    got = analysis.parse_collectives(txt)
    assert got["all-reduce"] == 16 * 128 * 4
    assert got["all-gather"] == 2 * 8 * 64 * 2
    assert got["collective-permute"] == 1024
    assert got["all-to-all"] == 4 * 4 * 4
    assert got["reduce-scatter"] == 32 * 128 * 4


def test_parse_collectives_ignores_non_collectives():
    txt = """
  %dot = f32[128,128]{1,0} dot(f32[128,64] %x, f32[64,128] %y)
  %add = f32[128,128]{1,0} add(f32[128,128] %dot, f32[128,128] %dot)
"""
    got = analysis.parse_collectives(txt)
    assert set(got) == set(analysis.COLLECTIVE_KINDS)
    assert all(v == 0 for v in got.values())


def test_parse_collectives_scalar_and_unknown_dtype():
    txt = ("  %ar = bf16[] all-reduce(bf16[] %s)\n"
           "  %ar2 = f32[8]{0} all-reduce(mystery[8] %t)\n")
    got = analysis.parse_collectives(txt)
    # scalar: 1 element * 2 bytes; unknown dtype contributes 0
    assert got["all-reduce"] == 2


def test_terms_and_bottleneck():
    t = analysis.RooflineTerms(
        flops=1e18, hbm_bytes=1e15, collective_bytes=1e14,
        collectives={}, chips=256, model_flops=5e17, peaks=V5E)
    assert t.compute_s == pytest.approx(1e18 / (256 * 197e12))
    assert t.memory_s == pytest.approx(1e15 / (256 * 819e9))
    assert t.collective_s == pytest.approx(1e14 / (256 * 50e9))
    assert t.bottleneck == "compute"
    assert 0 < t.roofline_fraction <= 1


def test_terms_bottleneck_variants_and_ratios():
    mem = analysis.RooflineTerms(
        flops=1e12, hbm_bytes=1e15, collective_bytes=0.0, collectives={},
        chips=1, model_flops=1e12, peaks=V5E)
    assert mem.bottleneck == "memory"
    coll = analysis.RooflineTerms(
        flops=1e12, hbm_bytes=1e9, collective_bytes=1e15, collectives={},
        chips=1, model_flops=1e12, peaks=V5E)
    assert coll.bottleneck == "collective"
    # useful_flops_ratio is MODEL/HLO; remat (HLO > MODEL) gives < 1
    assert coll.useful_flops_ratio == pytest.approx(1.0)
    remat = analysis.RooflineTerms(
        flops=2e12, hbm_bytes=1e9, collective_bytes=0.0, collectives={},
        chips=1, model_flops=1e12, peaks=V5E)
    assert remat.useful_flops_ratio == pytest.approx(0.5)
    assert remat.roofline_fraction == pytest.approx(0.5)


def test_terms_zero_edges():
    z = analysis.RooflineTerms(
        flops=0.0, hbm_bytes=0.0, collective_bytes=0.0, collectives={},
        chips=4, model_flops=0.0, peaks=V5E)
    assert z.useful_flops_ratio == 0.0
    assert z.roofline_fraction == 0.0
    assert z.roofline_fraction_kernel_adj == 0.0


def test_terms_as_dict_round_trip():
    t = analysis.RooflineTerms(
        flops=1e18, hbm_bytes=1e15, collective_bytes=1e14,
        collectives={"all-reduce": 1e14}, chips=256, model_flops=5e17,
        peaks=V5E,
        tagged_bytes=2e14, kernel_io_bytes=1e13)
    d = t.as_dict()
    assert {"flops", "hbm_bytes", "collective_bytes", "collectives",
            "chips", "model_flops", "compute_s", "memory_s",
            "collective_s", "bottleneck", "useful_flops_ratio",
            "roofline_fraction", "tagged_bytes", "kernel_io_bytes",
            "memory_kernel_adj_s",
            "roofline_fraction_kernel_adj"} <= set(d)
    assert d["compute_s"] == pytest.approx(t.compute_s)
    assert d["bottleneck"] == t.bottleneck
    import json
    json.dumps(d)  # JSON-serializable for the dry-run artifact


def test_kernel_adjustment_reduces_memory_term():
    t = analysis.RooflineTerms(
        flops=1e18, hbm_bytes=1e16, collective_bytes=0.0, collectives={},
        chips=256, model_flops=5e17, peaks=V5E, tagged_bytes=8e15,
        kernel_io_bytes=1e14)
    assert t.hbm_bytes_kernel_adj == pytest.approx(2e15 + 1e14)
    assert t.memory_kernel_adj_s < t.memory_s
    assert t.roofline_fraction_kernel_adj >= t.roofline_fraction


def test_chip_peaks_table():
    """Published v5e peaks (Google Cloud, "TPU v5e"); an unknown chip kind
    is an error, not a default."""
    assert V5E.bf16_flops == 197e12
    assert V5E.int8_ops == 393e12
    assert V5E.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.chip_peaks("TPU v99")


def test_unknown_tpu_kind_fails_dispatch(monkeypatch):
    """The roofline dispatch plan on a TPU prices GEMMs at the local chip's
    peaks and refuses a chip kind it has no peaks for."""
    import types
    from repro.kernels import dispatch

    fake = types.SimpleNamespace(device_kind="TPU v99", platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(ValueError, match="TPU v99"):
        dispatch._roofline_plan(256, 2048, 2048, 3, 256, 512, 256)
    fake.device_kind = "TPU v5 lite"
    plan = dispatch._roofline_plan(256, 2048, 2048, 3, 256, 512, 256)
    assert plan.source == "roofline"


def test_model_flops_shapes():
    from repro import configs
    cfg = configs.get_config("tinyllama-1.1b")
    tr = analysis.model_flops_for_cell(cfg, configs.SHAPES["train_4k"])
    pf = analysis.model_flops_for_cell(cfg, configs.SHAPES["prefill_32k"])
    dc = analysis.model_flops_for_cell(cfg, configs.SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert pf == pytest.approx(2 * n * 32 * 32768)
    assert dc == pytest.approx(2 * n * 128)


def test_vmem_tag_detected():
    from repro.models import attention as A
    q = jnp.ones((1, 64, 4, 32), jnp.float32)

    def f(q):
        return A.blockwise_attention(q, q, q, 32, True, 0)

    st = hlo_parse.analyze_module(_compile_text(f, q))
    assert st.tagged_traffic_bytes > 0
    assert st.tagged_traffic_bytes <= st.traffic_bytes


def test_dryrun_results_json_schema():
    """The committed sweep artifacts stay consistent with the analyzer."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "dryrun_results_optimized.json")
    if not os.path.exists(path):
        pytest.skip("sweep artifact not present")
    results = json.load(open(path))
    assert len(results) == 80
    ok = [r for r in results if r["ok"]]
    assert len(ok) == 64
    for r in ok:
        rf = r["roofline"]
        assert rf["flops"] > 0
        assert rf["hbm_bytes"] > 0
        assert rf["bottleneck"] in ("compute", "memory", "collective")
    skips = [r for r in results if r.get("skip_reason")]
    assert len(skips) == 16
