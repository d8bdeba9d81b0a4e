"""The work a model needs, counted from its configuration alone.

FLOPs are the model's own matmul and attention operations; bytes are the
least a decode step must read.  Neither depends on which GEMM path or
kernel serves the model, so a later change of implementation cannot move
the count (and a share of the roofline cannot pass 100% by a recount).
A weight is counted at the tier's least width: 2 bytes a parameter on
the exact (bf16) tier, 1 byte (the int8 weight) on an approximate tier.
"""

from __future__ import annotations


def weight_bytes_per_param(sizes: dict) -> int:
    return 2 if sizes.get("mult", "exact") == "exact" else 1


def cache_bytes_per_elem(sizes: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[sizes["dtype"]]


def _lm_layer_matmul_params(s: dict) -> int:
    d, hd = s["d_model"], s["head_dim"]
    h, kv, f = s["n_heads"], s["n_kv_heads"], s["d_ff"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (2 if s["mlp_style"] == "gelu" else 3) * d * f
    return attn + mlp


def _ssm_dims(s: dict) -> tuple[int, int, int, int]:
    d_in = s["ssm_expand"] * s["d_model"]
    h = s["ssm_heads"]
    return d_in, h, d_in // h, s["ssm_state"]


def _ssm_layer_matmul_params(s: dict) -> int:
    d_in, h, _, n = _ssm_dims(s)
    return s["d_model"] * (2 * d_in + 2 * n + h) + d_in * s["d_model"]


def layer_matmul_params(s: dict) -> int:
    if s["family"] == "lm":
        return _lm_layer_matmul_params(s)
    if s["family"] == "ssm":
        return _ssm_layer_matmul_params(s)
    raise ValueError(f"no work count for family {s['family']!r}")


def head_params(s: dict) -> int:
    return s["d_model"] * s["vocab"]


def token_flops(s: dict, ctx: int, with_head: bool) -> float:
    """FLOPs to run one token whose attention sees `ctx` positions
    (itself included)."""
    L = s["n_layers"]
    fl = 2.0 * L * layer_matmul_params(s)
    if s["family"] == "lm":
        fl += 4.0 * L * s["n_heads"] * s["head_dim"] * ctx
    else:
        _, h, p, n = _ssm_dims(s)
        # state decay and update, then the read-out, per head
        fl += L * h * p * n * 6.0
    if with_head:
        fl += 2.0 * head_params(s)
    return fl


def prefill_flops(s: dict, prompt_len: int) -> float:
    """A prompt of `prompt_len` tokens; the head runs for the last one."""
    # attention's share grows with the position: sum of ctx = P (P + 1) / 2
    total = prompt_len * token_flops(s, 0, False)
    if s["family"] == "lm":
        total += 4.0 * s["n_layers"] * s["n_heads"] * s["head_dim"] \
            * prompt_len * (prompt_len + 1) / 2
    return total + 2.0 * head_params(s)


def decode_flops(s: dict, ctx: int) -> float:
    """One generated token at a cache of `ctx` positions (itself
    included)."""
    return token_flops(s, ctx, True)


def decode_step_bytes(s: dict, contexts: list[int]) -> float:
    """Least bytes one decode step reads: every GEMM weight once, one
    embedding row per active slot, and each active slot's valid cache."""
    bpp = weight_bytes_per_param(s)
    cb = cache_bytes_per_elem(s)
    L, d = s["n_layers"], s["d_model"]
    weights = (L * layer_matmul_params(s) + head_params(s)) * bpp
    rows = len(contexts) * d * 2
    if s["family"] == "lm":
        per_pos = 2 * L * s["n_kv_heads"] * s["head_dim"] * cb
        cache = sum(contexts) * per_pos
    else:
        d_in, h, p, n = _ssm_dims(s)
        conv_ch = d_in + 2 * n
        per_slot = L * (h * p * n * 4 + (s["conv_width"] - 1) * conv_ch * cb)
        cache = len(contexts) * per_slot
    return float(weights + rows + cache)
