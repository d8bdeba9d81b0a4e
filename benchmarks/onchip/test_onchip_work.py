"""Operation and byte counts against hand-computed values at a tiny
shape, and their independence of the GEMM path."""

import pytest

from onchip_bench import work

LM = {"family": "lm", "n_layers": 2, "d_model": 8, "n_heads": 2,
      "n_kv_heads": 1, "head_dim": 4, "d_ff": 16, "vocab": 10,
      "mlp_style": "gelu", "dtype": "bfloat16", "mult": "exact"}
SSM = {"family": "ssm", "n_layers": 1, "d_model": 8, "ssm_expand": 2,
       "ssm_heads": 2, "ssm_state": 4, "conv_width": 4, "vocab": 10,
       "dtype": "bfloat16", "mult": "exact"}


def test_lm_counts_by_hand():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 = 192; MLP 2*8*16 = 256
    assert work.layer_matmul_params(LM) == 448
    # 2 FLOPs a weight over 2 layers, attention 4*L*h*hd*ctx, head 2*d*V
    assert work.decode_flops(LM, 5) == 2 * 2 * 448 + 4 * 2 * 2 * 4 * 5 + 160
    assert work.prefill_flops(LM, 3) == sum(
        work.token_flops(LM, i + 1, False) for i in range(3)) + 160
    assert work.prefill_flops(LM, 3) == 5920
    # weights 2 B each; 2 embedding rows; K and V: 2*L*kv*hd*2 B a position
    assert work.decode_step_bytes(LM, [5, 7]) == 1952 + 32 + 12 * 32


def test_ssm_counts_by_hand():
    # in_proj 8 * (2*16 + 2*4 + 2) + out_proj 16 * 8
    assert work.layer_matmul_params(SSM) == 464
    assert work.token_flops(SSM, 0, False) == 2 * 464 + 2 * 8 * 4 * 6
    # weights + one embedding row + f32 state 2*8*4*4 + conv tail 3*24*2
    assert work.decode_step_bytes(SSM, [9]) == 1088 + 16 + 256 + 144


def test_an_approximate_tier_counts_its_int8_weight():
    lowrank = dict(LM, mult="pareto:0.02:r2")
    assert work.decode_step_bytes(lowrank, [5, 7]) == 976 + 32 + 12 * 32
    assert work.decode_flops(lowrank, 5) == work.decode_flops(LM, 5)


@pytest.mark.parametrize("sizes", [LM, dict(LM, mult="pareto:0.02:r2"),
                                   SSM])
def test_counts_do_not_depend_on_the_gemm_path(sizes):
    got = set()
    for policy in ("auto", "xla", "pallas"):
        s = dict(sizes, kernel_policy=policy)
        got.add((work.decode_flops(s, 33), work.prefill_flops(s, 17),
                 work.decode_step_bytes(s, [3, 40, 9])))
    assert len(got) == 1
