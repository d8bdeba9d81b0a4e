"""chip_smoke.py's phases on the CPU at `configs.reduced` size.

The script itself refuses to run without a TPU; here its phase functions
are driven directly, with $REPRO_KERNEL_POLICY=pallas so that the "auto"
policy resolves to the (interpret-mode) Pallas kernels the chip would run
— the token-identity checks then compare kernels against the XLA path
rather than XLA against itself.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro import configs
from repro.configs.base import reduced

REPO = pathlib.Path(__file__).resolve().parent.parent
SMALL = dict(n_requests=2, prompt_len=16, new_tokens=3)


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def _cfg():
    return reduced(configs.get_config("tinyllama-1.1b"))


def test_tier_list(smoke):
    """exact, one truncation tier, one low-rank tier of rank 2."""
    from repro.approx import gemm as G
    assert smoke.ARCH == "tinyllama-1.1b"
    assert smoke.TIERS[0] == "exact"
    assert G.spec_from_name(smoke.TIERS[1]).mode == "trunc"
    lr = G.spec_from_name(smoke.TIERS[2])
    assert (lr.mode, lr.rank) == ("lowrank", 2)
    assert smoke.TP_TIERS == ("exact", "trunc2x2")
    assert (smoke.N_REQUESTS, smoke.PROMPT_LEN, smoke.NEW_TOKENS) == \
        (8, 128, 32)


def test_main_refuses_cpu(smoke, capsys):
    """Off-TPU the script fails before any work and prints no result."""
    with pytest.raises(smoke.SmokeFailure, match="needs a TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_gemm_widths_are_the_configs(smoke):
    full = configs.get_config("tinyllama-1.1b")
    assert {(k, n) for _, k, n in smoke.gemm_widths(full)} == {
        (2048, 2048), (2048, 256), (2048, 5632), (5632, 2048),
        (2048, 32000)}


def test_token_mismatch_is_caught(smoke):
    want = {"r0": [1, 2, 3], "r1": [4, 5, 6]}
    smoke.check_same_tokens("same", dict(want), want)
    with pytest.raises(smoke.SmokeFailure, match="1 of 2"):
        smoke.check_same_tokens("differs", {"r0": [1, 2, 3],
                                            "r1": [4, 5, 7]}, want)


def test_one_chip_phases(smoke, monkeypatch, capsys):
    """All three tiers serve, trunc2x2 tokens equal the XLA engine's, and
    the low-rank GEMM check passes — through the Pallas kernels."""
    monkeypatch.setenv("REPRO_KERNEL_POLICY", "pallas")
    out = smoke.run_one_chip(_cfg(), **SMALL)
    assert set(out) == set(smoke.TIERS)
    for tier, toks in out.items():
        assert sorted(toks) == ["r0", "r1"], tier
        assert all(len(t) == SMALL["new_tokens"] for t in toks.values())
    log = capsys.readouterr().out
    assert "trunc2x2 auto vs xla: tokens identical" in log
    assert "skinny == stacked bit for bit" in log
    plans = [line for line in log.splitlines()
             if "tier pareto:0.02:r2 plans" in line][0]
    assert "fused-skinny" in plans and "[policy]" in plans


def test_lowrank_gemm_check_catches_a_broken_kernel(smoke, monkeypatch):
    """The bit-identity check is live: a skinny kernel whose output is off
    by one fails it."""
    from repro.approx import gemm as G
    from repro.kernels import ops
    spec = G.spec_from_name(smoke.TIERS[2])
    real = ops.approx_qgemm

    def broken(a, b, sp, **kw):
        out = real(a, b, sp, **kw)
        return out if not kw.get("skinny") else out + 1.0

    monkeypatch.setattr(ops, "approx_qgemm", broken)
    with pytest.raises(smoke.SmokeFailure, match="skinny != stacked"):
        smoke.check_lowrank_gemm(spec, _cfg(), m_prefill=16, m_decode=2,
                                 seed=0)


def test_tp_phase_on_four_cpu_devices():
    """The four-chip option's check, rehearsed on four virtual CPU
    devices: TP tokens equal one-device tokens on exact and trunc2x2."""
    code = f"""
import dataclasses, importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              {str(REPO / 'chip_smoke.py')!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import configs
from repro.configs.base import reduced
cfg = reduced(configs.get_config("tinyllama-1.1b"))
out = smoke.run_tp(cfg, "model=4", **{SMALL!r})
print("RESULT", json.dumps(sorted(out)))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"), REPRO_KERNEL_POLICY="pallas")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "exact TP model=4 vs one chip: tokens identical" in res.stdout
    assert "trunc2x2 TP model=4 vs one chip: tokens identical" in res.stdout
    last = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT")]
    assert json.loads(last[0].split(" ", 1)[1]) == ["exact", "trunc2x2"]


@pytest.mark.parametrize("case", ["near_tie", "wide_gap", "large_error"])
def test_tp_near_tie_rule(smoke, monkeypatch, case):
    """The exact-tier TP check admits a divergence only at a near-tie that
    the forward's TP logit error explains, with that error small against
    the logit scale."""
    import numpy as np
    one, tp = object(), object()
    l1 = np.zeros(8, np.float32)
    l1[:2] = [1.0, 0.995]                      # one chip picks token 0
    lt = l1.copy()
    if case == "near_tie":
        lt[1] = 1.004                          # TP picks token 1, err .009
    elif case == "wide_gap":
        # the forwards agree, so the decode path diverged for another
        # reason (a cache bug, say): no tie explains it
        l1[1] = lt[1] = 0.5
    else:
        lt[:2] = [0.0, 1.2]                    # error ~ the logit scale
    rows = {one: l1, tp: lt}
    monkeypatch.setattr(smoke, "_last_logits_fn",
                        lambda e, n: (lambda ctx: rows[e]))
    want = {"r0": [5, 0], "r1": [3, 3]}
    got = {"r0": [5, 1], "r1": [3, 3]}
    requests = [("r0", [7, 7]), ("r1", [6, 6])]
    if case == "near_tie":
        smoke.check_tp_near_ties("t", one, tp, requests, got, want)
    else:
        with pytest.raises(smoke.SmokeFailure, match="beyond a near-tie"):
            smoke.check_tp_near_ties("t", one, tp, requests, got, want)
